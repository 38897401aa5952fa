"""Share of the traced window in which the device was idle and waited for
`host`: the read of a step's result returned -> the next step's `launch_ns`:
emit, the time outside any step, plan, build and the transfer. What a
vectorised `build` and an overlapped loop move. Step periods on the engine
tracer's clock, fitted to the capture's (`reduce/step_timeline.py`); with the
two other `idle.*_share` it sums to `device.idle_share` up to the window's two
ends. None where the runs could not be placed."""
from benchmark.reduce import step_timeline


def read(run):
    return step_timeline.idle_share(run, "host")
