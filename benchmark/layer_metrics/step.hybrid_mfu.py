"""The whole step's share of the chip's peak for the hybrid decoder, window:
the matrix-product operations the forward needs for the tokens the window's
steps were fed and emitted, the expert term from the (token, held expert)
pairs the steps computed (`reduce/hybrid.py serve_flops`, from the step
spans' `fed_tokens`, `emitted_tokens` and `moe_pairs_local`), over the
window's seconds times the peak. None where the spans carry no
`moe_pairs_local` (a program that has no such model)."""
from benchmark.reduce import hybrid
from benchmark.reduce.peaks import peaks


def read(run):
    steps = run.window_steps()
    if (not steps or not run.on_chip
            or "moe_pairs_local" not in steps[0]["args"]):
        return None
    flops = hybrid.serve_flops(
        run.config,
        sum(s["args"]["fed_tokens"] for s in steps),
        sum(s["args"]["emitted_tokens"] for s in steps),
        sum(s["args"]["moe_pairs_local"] for s in steps))
    peak = run.device["count"] * peaks(run.device["kind"])["flops_bf16"]
    return 100.0 * flops / (run.seconds * peak), len(steps)
