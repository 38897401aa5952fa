"""The traced window reduced to what the next reader of the ledger needs: the
device operations that took most time, and the device's idle time by what the
host was doing meanwhile. Seconds are averaged over the chips used."""
from __future__ import annotations

from . import intervals as iv

TOP = 10


def traced_ops(run):
    """`{plane: [(start_ns, end_ns, name)]}` clipped to the traced window."""
    return run.capture.windowed()


def has_device_trace(run):
    return run.capture is not None and bool(run.capture.devices)


def busy_and_window_s(run):
    """Seconds in which an operation ran on the device, averaged over the
    chips used, and the length of the traced window."""
    w0, w1 = run.capture.window()
    planes = traced_ops(run)
    busy = sum(iv.busy(ops) for ops in planes.values()) / len(planes)
    return busy * 1e-9, (w1 - w0) * 1e-9


def idle_share(run):
    """1 - (time in which an operation ran on the device) / traced window, in
    percent; None without a device capture."""
    if not has_device_trace(run):
        return None
    busy, window = busy_and_window_s(run)
    return 100.0 * (1.0 - busy / window)


def device_ops(run):
    planes = traced_ops(run)
    total = {}
    for ops in planes.values():
        for name, s in iv.seconds_by(ops).items():
            total[name] = total.get(name, 0.0) + s / len(planes)
    return sorted(total.items(), key=lambda kv: -kv[1])[:TOP]


def host_spans(run):
    """What the host was doing, on the capture's clock: `(start_ns, end_ns,
    name)`, disjoint. Serving: the program's step phases (plan, build,
    dispatch, sync, emit). Training: this harness's own annotations around
    the input iterator and the step."""
    if run.engine_steps is not None:
        return sorted((run.to_capture_ns(s), run.to_capture_ns(e), name)
                      for st in run.engine_steps
                      for name, (s, e) in st["phases"].items())
    return sorted((s, e, name.split(" ")[0][len("bench."):])
                  for s, e, name in run.capture.annotations
                  if name.startswith(("bench.input", "bench.step")))


def idle_gaps(run, outside):
    """Idle seconds of the device by the host span they fall in; time in no
    span goes under `outside`."""
    w0, w1 = run.capture.window()
    spans = host_spans(run)
    planes = traced_ops(run)
    total = {}
    for ops in planes.values():
        at = 0
        for gs, ge in iv.gaps(ops, w0, w1):
            left = ge - gs
            while at < len(spans) and spans[at][1] <= gs:
                at += 1
            j = at
            while j < len(spans) and spans[j][0] < ge:
                s, e, name = spans[j]
                cut = min(ge, e) - max(gs, s)
                if cut > 0:
                    total[name] = total.get(name, 0.0) + cut
                    left -= cut
                j += 1
            total[outside] = total.get(outside, 0.0) + left
    return sorted(((n, s * 1e-9 / len(planes)) for n, s in total.items()),
                  key=lambda kv: -kv[1])[:TOP]


def raw_names(run, top=40):
    """`(name, seconds, events)` by full operation name, first chip, for
    looking at a capture by hand."""
    ops = next(iter(traced_ops(run).values()))
    total = {}
    for s, e, name in ops:
        t = total.setdefault(name, [0.0, 0])
        t[0] += (e - s) * 1e-9
        t[1] += 1
    return [(n, t[0], t[1]) for n, t in
            sorted(total.items(), key=lambda kv: -kv[1][0])[:top]]
