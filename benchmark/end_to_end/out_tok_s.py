"""Output tokens a second as the clients received them, over whole steps
(`reduce/client.py out_tok_s`)."""
from benchmark.reduce import client


def read(run):
    tokens, seconds = client.out_tok_s(run.samples, run.t0, run.t1)
    return (tokens / seconds, tokens) if seconds else None
