"""Read a profiler capture (`*.xplane.pb`) with nothing but jax and reduce it
to what the metrics need: per device the intervals of the operations that ran
on it, and the host's annotations on the same clock.

What a `TPU v5 lite` capture looks like (looked at by hand, PR 22): one plane
a chip, `/device:TPU:<n>`, whose line `XLA Ops` holds one event an executed
HLO operation, named by the operation's whole HLO text (the lines `XLA
Modules` and `Steps` cover the same time again and would count it twice;
`Async XLA Ops` holds the spans of asynchronous copies and collectives, whose
start and done are on `XLA Ops` already), and the plane `/host:CPU`, whose
lines hold the `TraceAnnotation`s. Host and device stamps agree to about a
millisecond (`benchmark/tests/test_xplane.py`), which is enough to put a step
of hundreds of milliseconds on the device's timeline and not enough to order
two events a few hundred microseconds apart."""
from __future__ import annotations

import glob
import os
import re

from .intervals import category, clip

OP_LINE = "XLA Ops"
COLLECTIVE_RE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|collective-broadcast", re.IGNORECASE)


def newest_capture(logdir):
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {logdir}")
    return max(paths, key=os.path.getmtime)


class Capture:
    """`devices`: `{plane name: [(start_ns, end_ns, op name)]}` from each
    device plane's operation line; `annotations`: `[(start_ns, end_ns,
    name)]` of host events whose name starts with one of `prefixes`."""

    def __init__(self, path, prefixes=("bench.", "paddle_tpu.step ")):
        from jax.profiler import ProfileData

        data = ProfileData.from_file(path)
        self.path = path
        self.devices = {}
        self.annotations = []
        self.layout = {}     # plane -> {line: events read}, for looking by hand
        for plane in data.planes:
            is_device = plane.name.startswith("/device:")
            lines = self.layout.setdefault(plane.name, {})
            for line in plane.lines:
                if is_device and line.name != OP_LINE:
                    lines[line.name] = None         # there, and not read
                    continue
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
                lines[line.name] = lines.get(line.name, 0) + len(events)
                if is_device:
                    self.devices.setdefault(plane.name, []).extend(events)
                else:
                    self.annotations.extend(
                        e for e in events if e[2].startswith(prefixes))
        for ops in self.devices.values():
            ops.sort()
        self.annotations.sort()
        self._windowed = None

    def windowed(self):
        """`{plane: operations}` clipped to the traced window, worked out
        once: every reader of the capture starts from it."""
        if self._windowed is None:
            w0, w1 = self.window()
            self._windowed = {p: clip(ops, w0, w1)
                              for p, ops in self.devices.items()}
        return self._windowed

    def named(self, prefix):
        return [a for a in self.annotations if a[2].startswith(prefix)]

    def window(self, name="bench.window"):
        """`(start_ns, end_ns)` of the annotation that marks the traced
        window; without one, the extent of the device operations."""
        marks = self.named(name)
        if marks:
            return marks[0][0], marks[0][1]
        ops = [o for v in self.devices.values() for o in v]
        return min(o[0] for o in ops), max(o[1] for o in ops)


def is_collective(name):
    """By the operation's own name, not its text: the text also names the
    operands, and a fusion that consumes `%all-gather.5` is no collective."""
    return bool(COLLECTIVE_RE.match(category(name)))
