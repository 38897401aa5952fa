"""MFU / goodput accounting: model-FLOPs estimators, a peak-FLOPs
registry, and step-time statistics over recorded training traces.

MFU, tokens/s and step-time percentiles for ANY run: a hapi `Model.fit`
traced with `profiler.tracing.TrainTracer`, a raw `ShardedTrainStep`
loop, or a device capture read back through `profiler.xplane`. (The
benchmark keeps its own copies under `benchmark/reduce/`, which a PR that
claims a gain may not touch.)

Three layers:

- **Model FLOPs** (`gpt_train_flops_per_token`,
  `resnet50_train_flops_per_image`): *useful* model FLOPs only — e.g. the
  fused CE head's backward logit recompute (ops/fused_ce.py) is extra
  hardware work that buys HBM, so it raises throughput but is excluded;
  MFU stays honest.
- **Peaks** (`peak_flops`, `hbm_bytes_per_s`): bf16 peak and HBM
  bandwidth by TPU generation from public spec sheets, matched against
  `device.device_kind` (longest key wins). A device that is not in the
  table is an error, never a default: a utilization against somebody
  else's peak is not a measurement.
- **Goodput** (`goodput_summary`, `collective_time`): tokens/s,
  step-time p50/p95 from a `TrainTracer` export's ``train_step`` spans,
  and time-in-collectives from an xplane capture's op categories — the
  attribution layer the ragged-kernel and quantized-collective work
  (ROADMAP items 2–3) reports against.
"""
from __future__ import annotations

import re

# bf16 peak FLOP/s by TPU generation (public spec sheets)
PEAK_FLOPS_BF16 = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5": 459e12,
    "v5p": 459e12,
    "v6 lite": 918e12,
    "v6e": 918e12,
}

# HBM bytes/s by TPU generation (same spec sheets)
HBM_BYTES_PER_S = {
    "v4": 1228e9,
    "v5 lite": 819e9,
    "v5e": 819e9,
    "v5": 2765e9,
    "v5p": 2765e9,
    "v6 lite": 1640e9,
    "v6e": 1640e9,
}


def _by_device_kind(table, device, what):
    """`table`'s entry for `device` (a jax Device, or a device_kind
    string; None = the default backend's first device). Longest matching
    key wins, so "v5p" beats "v5"; no match raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device if isinstance(device, str) else getattr(
        device, "device_kind", "")
    for key, val in sorted(table.items(), key=lambda kv: -len(kv[0])):
        if key in kind.lower():
            return val
    raise ValueError(
        f"no {what} listed for device_kind {kind!r} (known: "
        f"{sorted(table)}); name a listed device_kind or add this one "
        "with its source")


def peak_flops(device=None) -> float:
    """bf16 peak FLOP/s for `device`; an unlisted device_kind raises."""
    return _by_device_kind(PEAK_FLOPS_BF16, device, "bf16 peak FLOP/s")


def hbm_bytes_per_s(device=None) -> float:
    """HBM bandwidth for `device`; an unlisted device_kind raises."""
    return _by_device_kind(HBM_BYTES_PER_S, device, "HBM bandwidth")


def dense_train_flops_per_token(hidden_size, num_layers, seq_len,
                                vocab_size, intermediate_size) -> float:
    """6*N for the matmuls (fwd+bwd) + causal attention score/value FLOPs
    of a decoder-only transformer."""
    H, L, S, V = hidden_size, num_layers, seq_len, vocab_size
    Ff = intermediate_size
    n_matmul = L * (4 * H * H + 2 * H * Ff) + V * H  # qkv+proj + mlp + unembed
    # causal attention: 2 matmuls of S*H per token fwd, x3 for train, /2 causal
    attn = L * 2 * S * H * 3
    return 6.0 * n_matmul + attn


def gpt_train_flops_per_token(cfg) -> float:
    """`dense_train_flops_per_token` off a GPTConfig-shaped object.

    Counts USEFUL model FLOPs only — the fused CE head's backward logit
    recompute (ops/fused_ce.py) is extra hardware work that buys HBM, so it
    raises throughput but is excluded here; MFU stays honest."""
    return dense_train_flops_per_token(
        cfg.hidden_size, cfg.num_layers, cfg.max_seq_len, cfg.vocab_size,
        cfg.intermediate_size,
    )


def resnet50_train_flops_per_image(image_size=224) -> float:
    """ResNet-50: ~4.1e9 fwd FLOPs per 224x224 image (published op
    count), train ~3x, scaled quadratically with resolution."""
    return 3 * 4.1e9 * (image_size / 224) ** 2


def mfu(tokens_per_sec, flops_per_token, device=None, peak=None) -> float:
    """Model FLOPs utilization: achieved useful FLOP/s over peak."""
    if peak is None:
        peak = peak_flops(device)
    return tokens_per_sec * flops_per_token / peak


# -- goodput over recorded train_step spans ---------------------------------

def _quantile(sorted_vals, pct):
    """Nearest-rank percentile: ceil(pct/100 * n) - 1 — the SAME
    convention serving.ServingMetrics uses, so p50/p95 never mean two
    different things across the stack."""
    return sorted_vals[max(0, -(-pct * len(sorted_vals) // 100) - 1)]


def train_step_spans(chrome_trace):
    """The ``train_step`` spans of a `TrainTracer.chrome_trace()` dict
    (or a path to its dumped JSON), sorted by step id."""
    import json as _json

    if isinstance(chrome_trace, str):
        with open(chrome_trace) as f:
            chrome_trace = _json.load(f)
    spans = [ev for ev in chrome_trace.get("traceEvents", ())
             if ev.get("ph") == "X" and ev.get("name") == "train_step"]
    spans.sort(key=lambda ev: (ev.get("args") or {}).get("step", 0))
    return spans


def goodput_summary(chrome_trace, tokens_per_step=None,
                    flops_per_token=None, device=None, peak=None):
    """Goodput over a recorded training trace: step count, step-time
    mean/p50/p95/max, wall span, and — when `tokens_per_step` is given —
    tokens/s over the span plus MFU (when `flops_per_token` is too).

    tokens/s here is GOODPUT: tokens over the whole wall span including
    reader stalls and callback time, not just device busy time — the
    number a cluster scheduler bills you for."""
    spans = train_step_spans(chrome_trace)
    if not spans:
        return {"steps": 0, "span_s": 0.0, "step_mean_ms": 0.0,
                "step_p50_ms": 0.0, "step_p95_ms": 0.0, "step_max_ms": 0.0}
    durs_ms = sorted(ev["dur"] / 1e3 for ev in spans)
    t0 = min(ev["ts"] for ev in spans)
    t1 = max(ev["ts"] + ev["dur"] for ev in spans)
    span_s = max((t1 - t0) / 1e6, 1e-12)
    out = {
        "steps": len(spans),
        "span_s": span_s,
        "step_mean_ms": sum(durs_ms) / len(durs_ms),
        "step_p50_ms": _quantile(durs_ms, 50),
        "step_p95_ms": _quantile(durs_ms, 95),
        "step_max_ms": durs_ms[-1],
    }
    if tokens_per_step:
        tps = len(spans) * tokens_per_step / span_s
        out["tokens_per_sec"] = tps
        if flops_per_token:
            out["mfu"] = mfu(tps, flops_per_token, device=device, peak=peak)
    return out


# -- time-in-collectives from xplane op categories --------------------------

# XLA collective op families (HLO names as they appear in device-plane op
# categories): the cross-chip communication bill of a sharded step.
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast|psum|ppermute", re.IGNORECASE)


def collective_time(logdir_or_file, device_only=True):
    """Per-plane time-in-collectives from an xplane capture: busy ms in
    collective op categories vs total busy ms, plus the per-category
    breakdown. The direct answer to "is this sharded step compute-bound
    or interconnect-bound" (EQuARX's motivating measurement)."""
    from .xplane import summarize

    out = {}
    for plane, entry in summarize(
            logdir_or_file, device_only=device_only, top=1 << 30).items():
        coll = [(name, ms) for name, ms in entry["by_category"]
                if COLLECTIVE_RE.search(name)]
        coll_ms = sum(ms for _, ms in coll)
        total = entry["total_ms"]
        out[plane] = {
            "collective_ms": coll_ms,
            "total_ms": total,
            "fraction": (coll_ms / total) if total else 0.0,
            "by_category": sorted(coll, key=lambda kv: -kv[1]),
        }
    return out
