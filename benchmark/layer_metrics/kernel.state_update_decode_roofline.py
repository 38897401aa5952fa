"""The decode group's Mamba state update's share of its roofline in pure
decode steps: the least time the chip needs to read the recurrent state of
the step's rows once and write it once, `2 x state_rows x mamba_num_heads x
mamba_head_dim x ssm_state_size x 4` bytes (float32) for each Mamba layer
(the span's `state_rows`, the file's published keys; whatever implements
the update, those are the bytes its work needs) at the chip's HBM bandwidth,
over the device time of the operations named `ssm_state_update` (the
`name=` of the kernel's `pallas_call`, `paddle_tpu/ops/pallas/
ssm_state_update.py`) in that step, summed over the decode steps the trace
holds whole.

A step's calls are those inside the step program's own run on the device
(`reduce/step_timeline.py`, as `kernel.grouped_decode_roofline` takes
them), and a step counts only if it holds one call for each Mamba layer.
None where the capture holds no such operation (a program whose update
is the compiler's fusion and update-slice), where no run was placed, or
where the spans carry no `state_rows`."""
from benchmark.reduce import breakdown, hybrid, kernels, step_timeline
from benchmark.reduce import intervals as iv
from benchmark.reduce.peaks import peaks

NAME = "ssm_state_update"
STATE_ITEMSIZE = 4      # float32


def read(run):
    if not breakdown.has_device_trace(run) or not run.engine_steps:
        return None
    ops = next(iter(breakdown.traced_ops(run).values()))
    mine = [o for o in ops if iv.category(o[2]) == NAME]
    clock = step_timeline.clock(run) if mine else None
    if clock is None:
        return None
    rows = {s["id"]: s["args"]["state_rows"] for s in run.engine_steps
            if s["kind"] == "decode" and "state_rows" in s["args"]}
    c = run.config
    calls_a_step = hybrid.layers(c)[0]
    lane_bytes = (2 * c["mamba_num_heads"] * c["mamba_head_dim"]
                  * c["ssm_state_size"] * STATE_ITEMSIZE * calls_a_step)
    rate = peaks(run.device["kind"])["hbm_bytes_per_s"]

    def on_device_ns(us):
        """A stamp of the join (the tracer's clock, the device's plane
        shifted) back on the device plane's own clock."""
        return (us - clock["shift_us"]) * 1e3 + clock["offset_ns"]

    w0, w1 = run.capture.window()
    least = took = 0.0
    steps = 0
    for s in step_timeline.joined(run).steps:
        if s["step"] not in rows or s["device_start_us"] is None:
            continue
        start, end = (on_device_ns(s[k])
                      for k in ("device_start_us", "device_end_us"))
        calls = kernels.inside(mine, start, end)
        if start < w0 or end > w1 or len(calls) != calls_a_step:
            continue
        least += rows[s["step"]] * lane_bytes / rate
        took += kernels.seconds(calls)
        steps += 1
    if not steps:
        return None
    return 100.0 * least / took, steps
