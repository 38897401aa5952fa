"""99th percentile of the gap between consecutive tokens of one request,
client side: the steps that carried somebody else's prompt chunk."""
from benchmark.reduce import client
from benchmark.reduce.stats import quantile


def read(run):
    gaps = client.itl_gaps(run.samples, run.t0, run.t1)
    return (quantile(gaps, 99) * 1e3, len(gaps)) if gaps else None
