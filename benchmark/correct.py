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
# closer than rounding. The server multiplies in bf16 (weights, activations
# and KV all keep 8 bits, a relative step of 2^-8) and accumulates in f32;
# through 24 layers that moved a logit by at most 0.041 against this reference
# in 3,860 positions of 31 runs on the chip (my chip runs, PR 22; the logits'
# own spread is about 0.9). The margin is about twice that.
# An int8 path (step of absmax/127, about 1 % of a weight's spread against
# bf16's 0.2 %) or an fp8 one (2^-4) moves logits four and twenty times as
# far, and fails.
SERVE_MARGIN = 0.1
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


def serving(config, params, samples, seed, log):
    """Draw SERVE_SAMPLE requests that got tokens (finished or not: every
    generated position is checked against the tokens before it) and hold the
    served tokens to the margin."""
    ref = load_reference(config)
    pool = [s for s in samples if len(s.tokens) >= SERVE_MIN_TOKENS]
    if len(pool) < SERVE_SAMPLE:
        log(f"correct: only {len(pool)} requests got {SERVE_MIN_TOKENS} "
            f"tokens; {SERVE_SAMPLE} are needed")
        return False
    rng = np.random.default_rng([seed, 9])
    picked = rng.choice(len(pool), SERVE_SAMPLE, replace=False)
    worst, positions = 0.0, 0
    for i in sorted(picked):
        s = pool[i]
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
            return False
        worst = max(worst, float(deficit.max()))
        positions += len(rows)
        log(f"correct: request {s.index} ({len(s.prompt)} prompt tokens, "
            f"{s.shared_tokens} shared, {len(s.tokens)} served): largest "
            f"deficit {float(deficit.max()):.4f}, "
            f"{int((deficit > 0).sum())} of {len(rows)} not the reference's "
            "argmax")
    ok = worst <= SERVE_MARGIN
    log(f"correct: {positions} positions, largest deficit {worst:.4f} "
        f"against a margin of {SERVE_MARGIN}: {'ok' if ok else 'FAILED'}")
    return ok


def training(config, params, first_batch, loss_first, losses, log):
    """The first step's loss against the reference's, every loss finite, the
    last below the first."""
    ref = load_reference(config)
    ids, labels = first_batch
    want = ref.batch_loss(params, ids, labels, config)
    close = abs(loss_first - want) <= TRAIN_LOSS_TOL
    finite = bool(np.isfinite(losses).all())
    fell = bool(losses[-1] < loss_first)
    log(f"correct: first loss {loss_first:.5f}, reference {want:.5f} "
        f"(tolerance {TRAIN_LOSS_TOL}): {'ok' if close else 'FAILED'}; "
        f"all {len(losses)} losses finite: {finite}; last "
        f"{losses[-1]:.4f} below the first: {fell}")
    return close and finite and fell
