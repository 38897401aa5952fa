"""Interval arithmetic for device timelines. An interval is `(start, end,
name)` in one unit (nanoseconds for a device trace). The union and gap logic
is the program's `profiler/xplane.py interval_union_stats`, copied and cut to
what the metrics use; `exposed` is new."""
from __future__ import annotations


def clip(intervals, w0, w1):
    """The parts of `intervals` inside `[w0, w1]`."""
    return [(max(s, w0), min(e, w1), n) for s, e, n in intervals
            if e > w0 and s < w1]


def merge(intervals):
    """Sorted, disjoint `(start, end)` runs covering the same time."""
    runs = []
    for s, e, _ in sorted(intervals):
        if runs and s <= runs[-1][1]:
            runs[-1][1] = max(runs[-1][1], e)
        else:
            runs.append([s, e])
    return [(s, e) for s, e in runs]


def busy(intervals):
    """Time in which at least one interval runs."""
    return sum(e - s for s, e in merge(intervals))


def gaps(intervals, w0, w1):
    """The idle stretches `(start, end)` of `[w0, w1]`."""
    out, at = [], w0
    for s, e in merge(clip(intervals, w0, w1)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if w1 > at:
        out.append((at, w1))
    return out


def exposed(these, others):
    """Time in which one of `these` runs and none of `others` does: a
    collective that no compute hides. One pass over both merged lists (a
    four-chip capture holds hundreds of thousands of operations)."""
    hidden = merge(others)
    total, at = 0, 0
    for s, e in merge(these):
        while at < len(hidden) and hidden[at][1] <= s:
            at += 1
        total += e - s
        j = at
        while j < len(hidden) and hidden[j][0] < e:
            total -= min(e, hidden[j][1]) - max(s, hidden[j][0])
            j += 1
    return total


def category(name):
    """`%fusion.123 = ...`, `fusion.123` and `%copy.31.remat2 = ...` fold to
    `fusion` and `copy`: the operation's name up to its first dot."""
    return name.lstrip("%").split(" ", 1)[0].split(".", 1)[0]


def seconds_by(intervals, key=category, ns=1e-9):
    """`{key(name): seconds}`, largest first."""
    out = {}
    for s, e, n in intervals:
        k = key(n)
        out[k] = out.get(k, 0.0) + (e - s) * ns
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))
