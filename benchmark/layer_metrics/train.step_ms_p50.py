"""Median length of a step, from handing it its batch to reading its loss."""
from benchmark.reduce.stats import median


def read(run):
    ms = [(s["end"] - s["start"]) * 1e3 for s in run.steps or ()]
    return (median(ms), len(ms)) if ms else None
