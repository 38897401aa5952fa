"""The profiler around the traced part of a window. The capture is written
under `benchmark/out/` in the checkout and read back by `reduce/xplane.py`.
Python-level tracing is off: it slows the host it is meant to observe."""
from __future__ import annotations

import shutil
import time

from . import spec
from .reduce.xplane import Capture, newest_capture

WINDOW = "bench.window"


class DeviceTracer:
    def __init__(self, workload):
        self.dir = spec.HERE / "out" / "trace" / workload
        self.t0 = self.t1 = None
        self._mark = None

    def due(self, now, t0, cap, trace_s, done=0, want=None):
        """Whether to start now: the traced part is the window's last
        `trace_s`, by the cap, or (a window that closes on `want` units of
        work, `done` of them so far) by the work still due at the pace so
        far."""
        if self.t0 is not None:
            return False
        return now >= cap - trace_s or bool(
            want is not None and done
            and (want - done) * (now - t0) <= trace_s * done)

    def start_profiler(self):
        """The slow part of starting (it may run in a worker thread)."""
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        self.dir.mkdir(parents=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=options)

    def mark_start(self):
        import jax

        self._mark = jax.profiler.TraceAnnotation(WINDOW)
        self._mark.__enter__()
        self.t0 = time.monotonic()

    def stop(self):
        import jax

        self.t1 = time.monotonic()
        self._mark.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def load_into(self, run):
        """Read the capture back and put the two clocks on one scale: the
        window's annotation began at `self.t0` on the host's clock."""
        run.capture = Capture(newest_capture(str(self.dir)))
        run.traced = (self.t0, self.t1)
        run.trace_clock_ns = run.capture.window(WINDOW)[0] - self.t0 * 1e9
