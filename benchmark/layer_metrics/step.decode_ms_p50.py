"""Median length of a pure decode step (plan to emit) in the window, from the
step spans."""
from benchmark.reduce.stats import median


def read(run):
    ms = [(s["end"] - s["start"]) * 1e3 for s in run.window_steps()
          if s["kind"] == "decode"]
    return (median(ms), len(ms)) if ms else None
