"""The flash kernels' share of their roofline in training: the least time the
chip needs for a layer's forward, dK/dV and dQ (`reduce/kernels.py
flash_train_layer`) over the three kernels' device time, over the steps the
trace holds whole, one chip's rows against one chip's time."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce.peaks import peaks


def read(run):
    if not breakdown.has_device_trace(run):
        return None
    tr = run.config["train"]
    rows = tr["batch"] // run.device["count"]
    flops, nbytes = kernels.flash_train_layer(
        rows, tr["seq_len"], run.config["n_embd"], 2)
    bound, which = kernels.least_seconds(flops, nbytes,
                                         peaks(run.device["kind"]))
    w0, w1 = run.capture.window()
    whole = [a for a in run.capture.named("bench.step")
             if a[0] >= w0 and a[1] <= w1]
    if not whole:
        return None
    shares = []
    for ops in breakdown.traced_ops(run).values():
        mine = kernels.matching(ops, run.config["kernels"]["flash"])
        took = sum(kernels.seconds(kernels.inside(mine, s, e))
                   for s, e, _ in whole)
        if not took:
            return None
        shares.append(bound * run.config["n_layer"] * len(whole) / took)
    run.notes.append(f"kernel.flash_roofline: {which}-bound")
    return 100.0 * sum(shares) / len(shares), len(whole)
