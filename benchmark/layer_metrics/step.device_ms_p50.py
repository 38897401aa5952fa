"""Median length of the step program's run on the device, pure decode steps of
the traced stretch: the event of the device plane's `XLA Modules` line that
each step launched (`reduce/step_timeline.py`), by the device's own clock, so
it needs the match of steps to runs and no join of clocks. What a faster
kernel or a smaller program moves; `step.decode_ms_p50` is the host's span of
the same steps and holds the device's idle time too."""
from benchmark.reduce import step_timeline
from benchmark.reduce.stats import median


def read(run):
    ms = step_timeline.device_ms(run)
    return (median(ms), len(ms)) if ms else None
