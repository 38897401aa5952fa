"""MFU/goodput accounting (profiler/flops.py).

The formulas are restated here by hand as plain arithmetic and the module
must reproduce them (MFU to 4 decimal places), for two GPT configs and
every peak-flops registry entry.
"""
import json
import tempfile

import numpy as np
import pytest

from paddle_tpu.profiler import flops


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def _flops_per_token_by_hand(H, L, S, V, Ff):
    """6 x the matmul parameters + causal attention, a token."""
    n_matmul = L * (4 * H * H + 2 * H * Ff) + V * H
    attn = L * 2 * S * H * 3
    return 6.0 * n_matmul + attn


def test_gpt_train_flops_matches_formula_by_hand():
    from paddle_tpu.models.gpt import GPTConfig

    # a mid-size config and a tiny one
    cfgs = [
        GPTConfig(vocab_size=32768, hidden_size=1024, num_layers=12,
                  num_heads=8, max_seq_len=1024),
        GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                  num_heads=8, max_seq_len=128),
    ]
    for cfg in cfgs:
        want = _flops_per_token_by_hand(
            cfg.hidden_size, cfg.num_layers, cfg.max_seq_len,
            cfg.vocab_size, cfg.intermediate_size)
        assert flops.gpt_train_flops_per_token(cfg) == want


def test_mfu_matches_arithmetic_to_4_decimals():
    """End to end: round(tok/s * flops/token / peak, 4) through the
    module, at a fixed throughput on the mid-size config."""
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=32768, hidden_size=1024, num_layers=12,
                    num_heads=8, max_seq_len=1024)
    tokens_per_sec = 82400.0
    fpt = _flops_per_token_by_hand(1024, 12, 1024, 32768,
                                    cfg.intermediate_size)
    for kind, peak in (("TPU v5e", 197e12), ("TPU v4", 275e12)):
        want = round(tokens_per_sec * fpt / peak, 4)
        got = round(flops.mfu(tokens_per_sec,
                              flops.gpt_train_flops_per_token(cfg),
                              device=_Dev(kind)), 4)
        assert got == want


def test_peak_flops_registry():
    by_hand = {
        "TPU v4": 275e12,
        "TPU v5 lite": 197e12,
        "TPU v5e": 197e12,
        "TPU v5p": 459e12,        # longest-key-wins: v5p beats v5
        "TPU v6e": 918e12,
        "TPU v6 lite": 918e12,
    }
    for kind, want in by_hand.items():
        assert flops.peak_flops(_Dev(kind)) == want
        assert flops.peak_flops(kind) == want      # plain strings work too
    # a device the table does not list is an error, not a v5e
    for unknown in ("anything else", _Dev("cpu")):
        with pytest.raises(ValueError, match="device_kind"):
            flops.peak_flops(unknown)
        with pytest.raises(ValueError, match="device_kind"):
            flops.hbm_bytes_per_s(unknown)


def test_resnet50_flops_matches_formula_by_hand():
    assert flops.resnet50_train_flops_per_image(224) == 3 * 4.1e9
    assert flops.resnet50_train_flops_per_image(32) == \
        3 * 4.1e9 * (32 / 224) ** 2


# -- goodput over recorded train_step spans ---------------------------------

def _trace(durs_ms, gap_ms=1.0):
    evs, t = [], 0.0
    for i, d in enumerate(durs_ms):
        evs.append({"name": "train_step", "ph": "X", "pid": 1, "tid": 0,
                    "ts": t * 1e3, "dur": d * 1e3, "args": {"step": i}})
        t += d + gap_ms
    return {"traceEvents": evs}


def test_goodput_summary_math():
    tr = _trace([10.0] * 9 + [30.0], gap_ms=0.0)   # 9x10ms + 1x30ms back-to-back
    g = flops.goodput_summary(tr, tokens_per_step=1000,
                              flops_per_token=1e9, peak=1e12)
    assert g["steps"] == 10
    assert g["span_s"] == pytest.approx(0.120)
    assert g["step_p50_ms"] == pytest.approx(10.0)
    assert g["step_p95_ms"] == pytest.approx(30.0)   # nearest-rank: 10th of 10
    assert g["step_max_ms"] == pytest.approx(30.0)
    assert g["step_mean_ms"] == pytest.approx(12.0)
    assert g["tokens_per_sec"] == pytest.approx(10 * 1000 / 0.120)
    assert g["mfu"] == pytest.approx(g["tokens_per_sec"] * 1e9 / 1e12)


def test_goodput_summary_empty_and_path_roundtrip(tmp_path):
    assert flops.goodput_summary({"traceEvents": []})["steps"] == 0
    p = tmp_path / "t.json"
    p.write_text(json.dumps(_trace([5.0, 5.0])))
    assert flops.goodput_summary(str(p))["steps"] == 2


# -- time-in-collectives from xplane categories -----------------------------

def test_collective_time_from_capture(tmp_path):
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    ops = (("fusion.1", 0, 10), ("all-reduce.2", 10, 4),
           ("reduce-scatter.3", 14, 2), ("matmul.4", 16, 4))
    line = plane.lines.add()
    line.name = "XLA Ops"
    line.timestamp_ns = 0
    for mid, (name, off_ms, dur_ms) in enumerate(ops, start=1):
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = name
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = int(off_ms * 1e9)
        ev.duration_ps = int(dur_ms * 1e9)
    path = str(tmp_path / "cap.xplane.pb")
    with open(path, "wb") as f:
        f.write(xs.SerializeToString())

    ct = flops.collective_time(path)
    st = ct["/device:TPU:0"]
    assert st["total_ms"] == pytest.approx(20.0)
    assert st["collective_ms"] == pytest.approx(6.0)
    assert st["fraction"] == pytest.approx(0.3)
    names = [n for n, _ in st["by_category"]]
    assert "all-reduce" in names and "reduce-scatter" in names
    assert "fusion" not in names and "matmul" not in names
