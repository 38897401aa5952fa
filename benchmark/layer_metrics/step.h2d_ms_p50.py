"""Median time a step spends handing its host arrays to the device and
splitting the sampling key, before the step program is launched: the step
span's `h2d_ms`, the first part of the `dispatch` phase (the rest of
`dispatch` is the launch). Steps of the window. None where the spans carry no
`h2d_ms`."""
from benchmark.reduce.stats import median


def read(run):
    ms = [s["args"]["h2d_ms"] for s in run.window_steps()
          if "h2d_ms" in s["args"]]
    return (median(ms), len(ms)) if ms else None
