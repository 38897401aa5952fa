"""Calls of the ragged paged-attention kernel in a decode step, by the
kernel's own name in the device plane: operations whose name folds to
`ragged_paged_attention` (the `name=` of its `pallas_call`) inside each pure
decode step the trace holds whole, first chip, mean over those steps. It
reads the number of layers where the name reached the capture and no other
kernel took it; None where no operation of the capture has the name (a
program that does not name its kernels)."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce.intervals import category

NAME = "ragged_paged_attention"


def read(run):
    if not breakdown.has_device_trace(run) or not run.engine_steps:
        return None
    ops = next(iter(breakdown.traced_ops(run).values()))
    mine = [o for o in ops if category(o[2]) == NAME]
    if not mine:
        return None
    calls = [len(kernels.inside(mine, run.to_capture_ns(st["start"]),
                                run.to_capture_ns(st["end"])))
             for st in run.engine_steps
             if st["kind"] == "decode" and st["start"] >= run.traced[0]
             and st["end"] <= run.traced[1]]
    return (sum(calls) / len(calls), len(calls)) if calls else None
