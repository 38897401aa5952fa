"""Median length of a step that carried a prompt chunk, from the step spans
of the whole load: today these steps are all in the ramp."""
from benchmark.reduce.stats import median


def read(run):
    ms = [(s["end"] - s["start"]) * 1e3 for s in run.engine_steps or ()
          if s["kind"] == "mixed"]
    return (median(ms), len(ms)) if ms else None
