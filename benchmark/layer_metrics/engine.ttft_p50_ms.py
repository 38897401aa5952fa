"""Time to first token, client side, over the whole load (the ramp's first
tokens included): a window holds too few for it to be judged end to end."""
from benchmark.reduce import client
from benchmark.reduce.stats import median


def read(run):
    ttfts = client.ttfts(run.samples, run.load_start, run.t1)
    return (median(ttfts) * 1e3, len(ttfts)) if ttfts else None
