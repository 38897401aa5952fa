"""Median gap between consecutive tokens of one request, client side, pooled
over requests. Per-layer while a window holds decode steps only: with every
lane full it is then lanes / `out_tok_s` and would judge the same number
twice. It parts from `out_tok_s` once windows hold prompt chunks and
completions; a `benchmark` PR promotes it then."""
from benchmark.reduce import client
from benchmark.reduce.stats import median


def read(run):
    gaps = client.itl_gaps(run.samples, run.t0, run.t1)
    return (median(gaps) * 1e3, len(gaps)) if gaps else None
