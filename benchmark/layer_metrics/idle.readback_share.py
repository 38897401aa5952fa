"""Share of the traced window in which the device was idle and waited for
`readback`: a run's end on the device -> the read of its result returns: the
completion signal, the copy to the host, the thread's wake. What a fed token
kept on the device and an overlapped loop move. Step periods on the engine
tracer's clock, fitted to the capture's (`reduce/step_timeline.py`); with the
two other `idle.*_share` it sums to `device.idle_share` up to the window's two
ends. The device plane's place on that clock is known to an interval, not a
point (`trace.clock_fit_us`): this share and `idle.launch_share` trade what the
interval leaves open, and their sum does not. None where the runs could not be
placed."""
from benchmark.reduce import step_timeline


def read(run):
    return step_timeline.idle_share(run, "readback")
