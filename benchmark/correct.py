"""The comparisons that decide `correct`, made outside the window against the
plain reference the configuration names, on the seeded weights the run used.
"""
from __future__ import annotations

import importlib.util

import numpy as np

from . import spec

# Serving. At every generated position the reference's logit of the token the
# server chose may lie at most SERVE_MARGIN under the reference's maximum.
# Logits and not tokens: with random weights the two largest logits are often
# closer than rounding. The limit stands between two readings on the chip at
# the cell's own size (PERF.md section 2 has them run by run). Lower: the
# server multiplies in bf16 and accumulates in f32, and through 24 layers that
# moved a logit by at most 0.050 against this reference in 70 sound runs of
# 120-370 positions (my chip runs, PR 22 and PR 28; the logits' own spread is
# about 0.9). Upper: the control, this reference with every matrix in
# float8_e4m3fn (`lower_precision`), reads 0.168, 0.229 and 0.361 on three
# seeds (`run.py --control 1`; my chip run, PR 28). The limit leaves the more
# room above the lower reading: fresh seeds read higher than these did.
# Weight-only int8 (0.058-0.087) and the program's int8 KV arena (0.017-0.036)
# do not separate from bf16 by any number read from tokens, and are no control.
SERVE_MARGIN = 0.12
SERVE_SAMPLE = 4          # requests checked a run
SERVE_MIN_TOKENS = 8      # a request needs this many tokens to be drawn
PAD_TO = 256

# Training. The first step's loss (bf16 weights and activations, f32
# accumulation and loss) against the reference's on the same batch and the
# same initial weights. Both sit near ln(vocab) = 10.8; bf16 moved the mean
# over the batch's 8,192 to 16,384 tokens by at most 0.0004 in 19 runs on the
# chip (my chip runs, PR 22: 0.0002 at 590M, 0.0004 at 1.3B on four chips).
# The tolerance is five times that; a wrong mask or a dropped layer moves the
# loss by tenths, an 8-bit path by hundredths.
TRAIN_LOSS_TOL = 0.002


def load_reference(config):
    path = spec.ROOT / config["reference"]
    s = importlib.util.spec_from_file_location("benchmark_reference", path)
    module = importlib.util.module_from_spec(s)
    s.loader.exec_module(module)
    return module


def compared(name, value, limit):
    """One number compared, beside its limit: `report.py` prints these as a
    run's last lines on standard error and under the result line's last
    key."""
    return {name: {"value": value, "limit": limit}}


def serving_sample(samples, seed):
    """The requests held to the reference, drawn from the seed: of those the
    load finished, the longest (prompt and answer) and SERVE_SAMPLE - 1
    others; and of those still in flight when it stopped, the one with most
    served tokens, whose positions are the window's long contexts (every
    generated position is checked against the tokens before it, finished or
    not). Where fewer finished, requests in flight fill their places."""
    long_enough = [s for s in samples if len(s.tokens) >= SERVE_MIN_TOKENS]
    done = [s for s in long_enough if s.ended is not None and not s.failed]
    flying = sorted((s for s in long_enough if s.ended is None),
                    key=lambda s: -len(s.tokens))
    rng = np.random.default_rng([seed, 9])
    picked = []
    if done:
        picked.append(max(done, key=lambda s: len(s.prompt) + len(s.tokens)))
    picked += flying[:1]
    for pool in (done, flying):
        rest = [s for s in pool if s not in picked]
        want = SERVE_SAMPLE + 1 - len(picked)
        if want > 0 and rest:
            picked += [rest[i] for i in rng.choice(
                len(rest), min(want, len(rest)), replace=False)]
    return sorted(picked, key=lambda s: s.index)


def lower_precision(params, kind):
    """The control's weights: every matrix in the nearest precision below
    bfloat16, as a weight-only path of that precision would store it (a scale
    a column, absmax on the type's largest value), and back in the type it
    came in. `fp8`: float8_e4m3fn, 3 bits of mantissa; `int8`: 255 levels.
    Vectors (biases, LayerNorm) stay as they are."""
    import jax.numpy as jnp

    def low(w):
        wide = w.astype(jnp.float32)
        top = jnp.abs(wide).max(axis=0, keepdims=True)
        top = jnp.where(top > 0, top, 1.0)      # a column of noughts stays
        if kind == "fp8":
            scale = top / 448.0
            stored = (wide / scale).astype(jnp.float8_e4m3fn)
        elif kind == "int8":
            scale = top / 127.0
            stored = jnp.round(wide / scale)
        else:
            raise ValueError(f"no control precision {kind!r}")
        return (stored.astype(jnp.float32) * scale).astype(w.dtype)

    return {k: low(v) if v.ndim >= 2 else v for k, v in params.items()}


def serving(config, params, samples, seed, log, control=()):
    """`(ok, numbers compared)`: the served tokens of `serving_sample` held
    to the margin. `control` names lower precisions (`lower_precision`): the
    reference is then also put in the program's place in each, and at every
    position the token it puts first is held to the same margin
    (`control.<kind>_deficit`): a control run comes out not correct when
    any of them fails it."""
    ref = load_reference(config)
    picked = serving_sample(samples, seed)
    if len(picked) < SERVE_SAMPLE:
        log(f"correct: only {len(picked)} requests got {SERVE_MIN_TOKENS} "
            f"tokens; {SERVE_SAMPLE} are needed")
        return False, compared("served_requests", len(picked), SERVE_SAMPLE)
    worst, positions = 0.0, 0
    lows = {kind: lower_precision(params, kind) for kind in control}
    low_worst = dict.fromkeys(control, 0.0)
    for s in picked:
        ids = (s.prompt + s.tokens)[:-1]
        # the logits at position p choose the token at p + 1
        rows = np.arange(len(s.prompt) - 1, len(ids))
        # padded behind (causal: the rows asked for cannot see it), so that
        # the reference compiles for a few lengths and not for every one
        pad = min(-len(ids) % PAD_TO, config["n_positions"] - len(ids))
        lg = np.asarray(ref.sequence_logits(params, ids + [0] * pad, config,
                                            rows=rows))
        deficit = lg.max(axis=-1) - lg[np.arange(len(rows)), s.tokens]
        if not np.isfinite(lg).all():
            log(f"correct: reference logits of request {s.index} not finite")
            return False, compared("logit_deficit", float("inf"),
                                   SERVE_MARGIN)
        worst = max(worst, float(deficit.max()))
        positions += len(rows)
        for kind, low in lows.items():
            first = np.asarray(ref.sequence_logits(
                low, ids + [0] * pad, config, rows=rows)).argmax(axis=-1)
            gap = lg.max(axis=-1) - lg[np.arange(len(rows)), first]
            low_worst[kind] = max(low_worst[kind], float(gap.max()))
            log(f"correct: control {kind}, request {s.index}: largest "
                f"deficit of the token it puts first {float(gap.max()):.4f}, "
                f"{int((gap > 0).sum())} of {len(rows)} not the "
                "reference's argmax")
        log(f"correct: request {s.index} ({len(s.prompt)} prompt tokens, "
            f"{s.shared_tokens} shared, {len(s.tokens)} served, "
            f"{'finished' if s.ended is not None else 'in flight'}): "
            f"largest deficit {float(deficit.max()):.4f}, "
            f"{int((deficit > 0).sum())} of {len(rows)} not the reference's "
            "argmax")
    ok = worst <= SERVE_MARGIN
    log(f"correct: {positions} positions, largest deficit {worst:.4f} "
        f"against a margin of {SERVE_MARGIN}: {'ok' if ok else 'FAILED'}")
    out = compared("logit_deficit", worst, SERVE_MARGIN)
    for kind, gap in low_worst.items():
        out.update(compared(f"control.{kind}_deficit", gap, SERVE_MARGIN))
        ok = ok and gap <= SERVE_MARGIN
    return ok, out


def training(config, params, first_batch, loss_first, losses, log):
    """`(ok, numbers compared)`: the first step's loss against the
    reference's, every loss finite, the last below the first."""
    ref = load_reference(config)
    ids, labels = first_batch
    want = ref.batch_loss(params, ids, labels, config)
    gap = abs(loss_first - want)
    close = gap <= TRAIN_LOSS_TOL
    bad = int((~np.isfinite(losses)).sum())
    fell = bool(losses[-1] < loss_first)
    log(f"correct: first loss {loss_first:.5f}, reference {want:.5f} "
        f"(tolerance {TRAIN_LOSS_TOL}): {'ok' if close else 'FAILED'}; "
        f"{bad} of {len(losses)} losses not finite; last "
        f"{losses[-1]:.4f} below the first: {fell}")
    return close and not bad and fell, {
        **compared("first_loss_gap", float(gap), TRAIN_LOSS_TOL),
        **compared("losses_not_finite", bad, 0),
        **compared("last_loss", float(losses[-1]), float(loss_first))}
