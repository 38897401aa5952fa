"""The whole step's share of the chip's peak, window: the operations the
model's forward needs for the tokens the window's steps were fed and emitted
(`reduce/flops.py dense_serve_flops`, from the step spans' `fed_tokens` and
`emitted_tokens`) over the window's seconds times the peak. It stands beside
`kernel.ragged_decode_roofline`: a change that takes the kernel off the path
leaves that silent, and this still bounds what it may claim."""
from benchmark import spec
from benchmark.reduce.flops import dense_serve_flops
from benchmark.reduce.peaks import peaks


def read(run):
    steps = run.window_steps()
    if not steps or not run.on_chip:
        return None
    kw = spec.model_kwargs(run.config)
    flops = dense_serve_flops(
        kw["hidden_size"], kw["num_layers"], kw["vocab_size"],
        kw["intermediate_size"],
        sum(s["args"]["fed_tokens"] for s in steps),
        sum(s["args"]["emitted_tokens"] for s in steps))
    peak = run.device["count"] * peaks(run.device["kind"])["flops_bf16"]
    return 100.0 * flops / (run.seconds * peak), len(steps)
