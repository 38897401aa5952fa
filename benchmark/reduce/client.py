"""End-to-end serving numbers from the clients' own clocks: every token was
stamped with `time.monotonic()` when its client read it (`loadgen.Sample`).
Nothing here is read from the program."""
from __future__ import annotations

from .stats import median


def itl_gaps(samples, t0, t1):
    """Seconds between consecutive tokens of one request, both read inside
    the window, pooled over requests."""
    gaps = []
    for s in samples:
        ts = s.token_times
        gaps.extend(b - a for a, b in zip(ts, ts[1:]) if a >= t0 and b <= t1)
    return gaps


def ttfts(samples, t0, t1):
    """First token read minus request sent (open loop: minus request due),
    for requests whose first token was read inside the window."""
    out = []
    for s in samples:
        if s.token_times and t0 <= s.token_times[0] <= t1:
            out.append(s.token_times[0] - (s.sent if s.due is None else s.due))
    return out


def lateness(samples, t0, t1):
    """How late the generator sent each open-loop request due in the window."""
    return [s.sent - s.due for s in samples
            if s.due is not None and s.sent is not None and t0 <= s.due <= t1]


def same_burst_s(samples, t0, t1):
    """Two tokens belong to one burst (one engine step's tokens) when they
    are read closer together than this: a quarter of the median gap between
    a request's own tokens. None where the window holds no gap."""
    gaps = itl_gaps(samples, t0, t1)
    return 0.25 * median(gaps) if gaps else None


def _burst_end(times, last, same_burst):
    """Index of the last token of the burst that `times[last]` belongs to."""
    while last + 1 < len(times) and times[last + 1] - times[last] < same_burst:
        last += 1
    return last


def close_on_tokens(samples, t0, tokens, cap):
    """Where a window that holds a fixed amount of work ends: `(t1, short)`.
    `t1` is when the burst that holds the `tokens`-th token read since `t0`
    had been read whole, so every run of one schedule counts the same steps.
    If the clients had not read that many by `cap`, the window closes there
    as a window of time does, and `short` says how many tokens were
    missing."""
    times = sorted(t for s in samples for t in s.token_times if t >= t0)
    read = sum(t <= cap for t in times)
    if read < tokens:
        return cap, tokens - read
    last = _burst_end(times, tokens - 1, same_burst_s(samples, t0, cap) or 0.0)
    return times[last], 0


def out_tok_s(samples, t0, t1):
    """Output tokens per second over whole steps: `(tokens, seconds)`.

    Tokens reach the clients in bursts, one burst an engine step. Counting
    from the window's edges would add or drop part of a burst, which at a few
    steps a second is percents. So the clock starts when the first burst
    inside the window has been read and stops at the last token read inside
    it (to the end of its burst); the tokens counted are those read after
    the first burst. Two tokens
    belong to one burst when they are read closer together than a quarter of
    the median gap between a request's own tokens."""
    times = sorted(t for s in samples for t in s.token_times if t >= t0)
    same_burst = same_burst_s(samples, t0, t1)
    inside = sum(t <= t1 for t in times)
    if inside < 2 or same_burst is None:
        return 0, 0.0
    first, last = 0, inside - 1
    while first < last and times[first + 1] - times[first] < same_burst:
        first += 1
    # a burst the window's end cut in two is read to its end
    last = _burst_end(times, last, same_burst)
    return last - first, times[last] - times[first]
