"""The whole step's share of the chip's peak for a looped decoder, window:
the operations the forward needs for the tokens the window's steps were fed
and emitted, every layer once a pass (`reduce/looped.py serve_flops`, from
the step spans' `fed_tokens` and `emitted_tokens`), over the window's seconds
times the peak. `step.mfu` counts a GPT's block once; this counts the
configuration's own keys. None where the spans do not say how many passes a
token ran (a program that has no looped model)."""
from benchmark.reduce import looped
from benchmark.reduce.peaks import peaks


def read(run):
    steps = run.window_steps()
    if not steps or not run.on_chip or "loop_steps" not in steps[0]["args"]:
        return None
    flops = looped.serve_flops(
        run.config,
        sum(s["args"]["fed_tokens"] for s in steps),
        sum(s["args"]["emitted_tokens"] for s in steps))
    peak = run.device["count"] * peaks(run.device["kind"])["flops_bf16"]
    return 100.0 * flops / (run.seconds * peak), len(steps)
