"""Share of the traced window in which the device was idle and waited for
`launch`: a step's `launch_ns` -> its program's start on the device: `jit`'s
call path, the runtime's issue, the program's start. What flattening the
weights once moves; an overlapped loop hides it only if the launch is issued
while the predecessor runs. Step periods on the engine tracer's clock, fitted
to the capture's (`reduce/step_timeline.py`); with the two other
`idle.*_share` it sums to `device.idle_share` up to the window's two ends. The
device plane's place on that clock is known to an interval, not a point
(`trace.clock_fit_us`): this share and `idle.readback_share` trade what the
interval leaves open, and their sum does not. None where the runs could not be
placed."""
from benchmark.reduce import step_timeline


def read(run):
    return step_timeline.idle_share(run, "launch")
