"""Model FLOP/s utilization: tokens a second times the operations forward and
backward need a token (`reduce/flops.py`; recomputation not counted), over
chips times the chip's peak."""
from benchmark.reduce.peaks import peaks


def read(run):
    if not run.steps or not run.on_chip:
        return None
    peak = run.device["count"] * peaks(run.device["kind"])["flops_bf16"]
    return 100.0 * run.train_tok_s() * run.flops_per_token / peak
