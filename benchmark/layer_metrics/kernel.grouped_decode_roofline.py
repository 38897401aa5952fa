"""The grouped matmul's share of its roofline in pure decode steps: the least
time the chip needs to read once the two matrices of every held expert a
step's tokens hit (the span's `moe_experts_hit`, summed over the expert
layers, x `reduce/hybrid.py expert_elements` x the weights' item size, at the
chip's HBM bandwidth: a decode step's products are bound by those bytes, the
rows are a hundredth of them) over the kernel's device time in that step,
summed over the decode steps the trace holds whole.

A step's calls are the operations named `grouped_matmul` inside the step
program's own run on the device (`reduce/step_timeline.py`: the run is
matched to the step by id, and run and operations are stamped by one clock,
the device's, so no join of clocks stands between them). A step counts only
if it holds all of its calls, two an expert layer: a run cut by the window's
edge, or a step matched to another's run, would lend or borrow a call and
read over 100 %. None where the capture holds no such operation (a commit
before PR 39), where no run was placed, or where the spans carry no
`moe_experts_hit`."""
from benchmark.reduce import breakdown, hybrid, kernels, step_timeline
from benchmark.reduce import intervals as iv
from benchmark.reduce.peaks import peaks

NAME = "grouped_matmul"


def read(run):
    if not breakdown.has_device_trace(run) or not run.engine_steps:
        return None
    ops = next(iter(breakdown.traced_ops(run).values()))
    mine = [o for o in ops if iv.category(o[2]) == NAME]
    clock = step_timeline.clock(run) if mine else None
    if clock is None:
        return None
    hit = {s["id"]: s["args"]["moe_experts_hit"] for s in run.engine_steps
           if s["kind"] == "decode" and "moe_experts_hit" in s["args"]}
    calls_a_step = 2 * hybrid.layers(run.config)[2]
    expert_bytes = hybrid.expert_elements(run.config) \
        * hybrid.ITEMSIZE[run.config["dtype"]]
    rate = peaks(run.device["kind"])["hbm_bytes_per_s"]

    def on_device_ns(us):
        """A stamp of the join (the tracer's clock, the device's plane
        shifted) back on the device plane's own clock."""
        return (us - clock["shift_us"]) * 1e3 + clock["offset_ns"]

    w0, w1 = run.capture.window()
    least = took = 0.0
    steps = 0
    for s in step_timeline.joined(run).steps:
        if s["step"] not in hit or s["device_start_us"] is None:
            continue
        start, end = (on_device_ns(s[k])
                      for k in ("device_start_us", "device_end_us"))
        calls = kernels.inside(mine, start, end)
        if start < w0 or end > w1 or len(calls) != calls_a_step:
            continue
        least += hit[s["step"]] * expert_bytes / rate
        took += kernels.seconds(calls)
        steps += 1
    if not steps:
        return None
    return 100.0 * least / took, steps
