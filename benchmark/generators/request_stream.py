"""The one general generator of serving traffic. A mix is a data file of
parameters (lengths, sharing, arrivals); this reads it and yields the
requests, the same for the same seed. A new mix is a new data file.

    prompt, output   length distributions (`draw`)
    prefix           optional shared part: documents of `length`, each used
                     `uses` times with a fresh `prompt` appended, the uses of
                     the `group` documents of one group interleaved
    loop             closed: `clients` callers, each sending its next request
                     when the last is answered, drawing in order from this
                     one list. open: arrivals on a schedule fixed by the seed,
                     `poisson` at `rate_rps`, or `onoff` (bursts: `rate_rps`
                     for `on_s` seconds, silence for `off_s`)
    lengths_seed     optional. The lengths (prompt, output, prefix) are then
                     drawn from it and are the same for every `seed`, which
                     still draws the token ids and the arrivals: every run
                     of the mix is the same amount of work
    start            optional, a closed loop's: `"stationary"` cuts the
                     outputs of the first `clients` requests as if the job
                     had run for a while (`stationary_output`)
"""
from __future__ import annotations

import math

import numpy as np


def draw(rng, d):
    """One whole number from the distribution `d`."""
    kind = d["dist"]
    if kind == "fixed":
        return int(d["value"])
    if kind == "uniform":
        return int(rng.integers(d["min"], d["max"] + 1))
    if kind == "lognormal":
        x = rng.lognormal(math.log(d["median"]), d["sigma"])
        return int(min(max(round(x), d["min"]), d["max"]))
    raise ValueError(f"unknown distribution {kind!r}")


def _arrivals(rng, loop):
    """Seconds from the start of the load at which each request is due, for
    an open loop; None for ever in a closed one."""
    if loop["type"] == "closed":
        while True:
            yield None
    rate = float(loop["rate_rps"])
    t = 0.0
    if loop["arrivals"] == "poisson":
        while True:
            t += rng.exponential(1.0 / rate)
            yield t
    if loop["arrivals"] == "onoff":
        on, off = float(loop["on_s"]), float(loop["off_s"])
        while True:
            t += rng.exponential(1.0 / rate)
            # time runs only while a burst is on: fold the silences back in
            yield t + math.floor(t / on) * off
    raise ValueError(f"unknown arrivals {loop['arrivals']!r}")


def stationary_output(output, i, clients):
    """What is left of the `i`-th first request's `output` tokens in a job
    caught mid-way: its clients' requests are evenly spread over their
    lengths, so completions (and the admissions that replace them) come from
    the start at the steady rate, not all after the shortest answer."""
    return max(1, round(output * (i + 0.5) / clients))


def stream(mix, seed, vocab_size):
    """Yield `{"prompt": [ids], "max_tokens": n, "due_s": t or None,
    "shared_tokens": k}` without end. `shared_tokens` is the length of the
    part an earlier request of the stream already sent."""
    lengths = np.random.default_rng([mix.get("lengths_seed", seed), 1])
    tokens = np.random.default_rng([seed, 2])
    due = _arrivals(np.random.default_rng([seed, 3]), mix["loop"])
    ids = lambda n: tokens.integers(0, vocab_size, n).tolist()  # noqa: E731

    start = mix.get("start")
    if start not in (None, "stationary"):
        raise ValueError(f"unknown start {start!r}")
    clients = int(mix["loop"]["clients"]) if start else 0
    sent = 0

    def one(prefix, first_use):
        nonlocal sent
        prompt = prefix + ids(draw(lengths, mix["prompt"]))
        output = draw(lengths, mix["output"])
        if sent < clients:
            output = stationary_output(output, sent, clients)
        sent += 1
        return {"prompt": prompt, "max_tokens": output, "due_s": next(due),
                "shared_tokens": 0 if first_use else len(prefix)}

    share = mix.get("prefix")
    while True:
        if not share:
            yield one([], True)
            continue
        docs = [ids(draw(lengths, share["length"]))
                for _ in range(share["group"])]
        for use in range(share["uses"]):
            for doc in docs:
                yield one(doc, use == 0)
