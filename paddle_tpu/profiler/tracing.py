"""Shared ring-buffered Chrome/Perfetto trace-event recorder.

The recorder `serving/trace.py` proved out for the serving stack,
generalized so the TRAINING stack (hapi `Model.fit`, the SPMD/pipeline
compiled train steps) records the same kind of timeline:

- `Tracer` is the substrate: a bounded ring of trace events behind a lock
  (any thread may export mid-run), a monotonic epoch, span/instant
  emitters, step-id allocation, and the Perfetto-loadable
  `chrome_trace()`/`dump()` export. It knows nothing about requests or
  batches — producers subclass it and name their own tracks.
- `TrainTracer` records **one ``train_step`` span per training step** with
  phase children ``data`` (loader fetch), ``shard`` (host state gather +
  batch placement), ``dispatch`` (compiled-program launch), ``sync`` (host
  sync on the loss) and ``callback`` (metrics/log/callback work) — the
  training analogue of the serving step timeline's
  plan/build/dispatch/sync/emit.
- `serving.trace.EngineTracer` subclasses `Tracer`, keeping its whole
  public API (request lanes, lifecycle spans, the serving step timeline).

**Device-capture join**: every traced dispatch runs under a
`jax.profiler.TraceAnnotation` named ``paddle_tpu.step <id>``
(`STEP_ANNOTATION_PREFIX`) carrying the SAME id as the host span, so
`profiler.xplane.engine_step_spans` / `join_engine_steps` line device
captures up against host ``step[kind]`` AND ``train_step`` spans alike.
`Tracer.launch` is the one place that enters it (the engine's step,
`train_dispatch_span` and `hapi.Model.train_batch` all launch through it): it
stamps ``time.monotonic_ns()`` immediately before (the span's ``launch_ns``), so
every step is a pair (this tracer's clock, the capture's host clock) and
the join fits ONE offset over all of them; the export carries the epoch
(``otherData.epoch_ns``), so a dumped trace is enough to redo the fit.

**Off by default, free when off**: training code asks `train_tracer()`
for the process-wide tracer and gets None unless `enable_train_tracing()`
was called; every hook site is a single ``if tr is not None`` pointer
test, so the untraced step is byte-identical to the pre-trace code path.
Its ``capacity`` bounds the ring (default 65536 events), as the engine's
``trace_buffer`` does for serving.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from collections import deque

# The xplane join key: host step spans and the TraceAnnotation wrapping the
# matching device dispatch share "paddle_tpu.step <id>".
STEP_ANNOTATION_PREFIX = "paddle_tpu.step "


class Tracer:
    """Bounded trace-event recorder: the generic core.

    All timestamps come from ``time.monotonic()`` — one clock per process,
    so spans from different producers (and the metrics built on the same
    clock) agree by construction. The producing thread is the only writer;
    `chrome_trace()` may be called from any thread mid-run — a lock covers
    the ring append and the export snapshot, because iterating a deque
    that another thread is appending to raises RuntimeError.

    Memory is bounded by the ring (`capacity` events): a long-running
    producer overwrites its oldest events instead of growing. Track
    metadata (`self._meta`, filled by subclasses) lives OUTSIDE the ring
    so track names survive after the events that created them wrapped.
    """

    producer = "paddle_tpu.profiler.tracing"

    def __init__(self, capacity=65536, sample=1.0):
        self.capacity = int(capacity)
        self.sample = float(sample)
        self.events = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self.epoch = time.monotonic()
        self.dropped = 0          # events overwritten by the ring
        self._step_id = 0
        self._meta = []           # subclass-provided track metadata events

    # -- low-level event plumbing -----------------------------------------

    @staticmethod
    def _meta_ev(name, pid, tid, args):
        return {"name": name, "ph": "M", "pid": pid, "tid": tid,
                "ts": 0, "args": args}

    def ts(self, t):
        """monotonic seconds -> trace microseconds."""
        return (t - self.epoch) * 1e6

    def _push(self, ev):
        with self._lock:
            if len(self.events) == self.capacity:
                self.dropped += 1
            self.events.append(ev)

    def complete(self, name, pid, tid, start, end, args=None):
        """One 'X' (complete) span from monotonic `start` to `end`."""
        ev = {"name": name, "ph": "X", "pid": pid, "tid": tid,
              "ts": round(self.ts(start), 3),
              "dur": round(max(end - start, 0.0) * 1e6, 3)}
        if args:
            ev["args"] = args
        self._push(ev)

    def instant(self, name, pid, tid, t=None, args=None):
        ev = {"name": name, "ph": "i", "s": "t", "pid": pid, "tid": tid,
              "ts": round(self.ts(time.monotonic() if t is None else t), 3)}
        if args:
            ev["args"] = args
        self._push(ev)

    # -- step ids + phased spans -------------------------------------------

    def next_step_id(self):
        sid = self._step_id
        self._step_id += 1
        return sid

    def step_annotation(self, step_id):
        """Name for the `jax.profiler.TraceAnnotation` wrapping this
        step's device dispatch — the join key between this host trace and
        an xplane device capture (profiler.xplane.engine_step_spans), and
        one half of the step's clock pair: the annotation's start on the
        capture's host clock against the ``launch_ns`` that `launch`
        stamps on this tracer's clock just before entering it."""
        return f"{STEP_ANNOTATION_PREFIX}{step_id}"

    @contextlib.contextmanager
    def launch(self, step_id, args):
        """Run a step's launch call under its annotation and write into
        `args` (the step span's) ``launch_ns``: ``time.monotonic_ns()``
        taken immediately before the annotation is entered.
        `profiler.xplane.join_engine_steps` fits this tracer's clock to a
        capture's from the pairs (``launch_ns``, annotation's start)."""
        import jax

        ann = jax.profiler.TraceAnnotation(self.step_annotation(step_id))
        args["launch_ns"] = time.monotonic_ns()
        with ann:
            yield

    def phased_span(self, name, pid, tid, step_id, phases, phase_order,
                    args=None):
        """Emit one parent span covering min(start)..max(end) of `phases`
        ({phase: (start, end)} in monotonic seconds) plus one child span
        per phase in `phase_order`; parent and children all carry the
        step id so a join/sort never depends on timestamps."""
        s0 = min(t0 for t0, _ in phases.values())
        s1 = max(t1 for _, t1 in phases.values())
        a = {"step": step_id}
        if args:
            a.update(args)
        self.complete(name, pid, tid, s0, s1, a)
        for ph in phase_order:
            if ph in phases:
                t0, t1 = phases[ph]
                self.complete(ph, pid, tid, t0, t1, {"step": step_id})

    # -- export -------------------------------------------------------------

    def chrome_trace(self):
        """The trace as a Chrome/Perfetto trace-event JSON object. Track
        metadata is kept outside the ring, so lane names survive even
        after the ring has overwritten the events that created them.
        The meta snapshot shares the ring's lock: producers append lane
        metadata mid-run (EngineTracer._lane) while any thread exports."""
        with self._lock:
            ring = list(self.events)
            meta = list(self._meta)
        return {
            "traceEvents": meta + ring,
            "displayTimeUnit": "ms",
            "otherData": {
                "producer": self.producer,
                "sample": self.sample,
                "capacity": self.capacity,
                "dropped_events": self.dropped,
                # `ts` counts from here on time.monotonic(): with it a
                # span and its `launch_ns` are on one clock
                "epoch_ns": round(self.epoch * 1e9),
            },
        }

    def dump(self, path):
        """Write the Perfetto-loadable JSON to `path`; returns the event
        count written."""
        trace = self.chrome_trace()
        with open(path, "w") as f:
            json.dump(trace, f)
        return len(trace["traceEvents"])


class TrainTracer(Tracer):
    """Training-step timeline recorder.

    One ``train_step`` span per step on the ``paddle-tpu-train`` track,
    with up to five phase children:

    - ``data``      — loader fetch (the reader clock `profiler.timer`'s
                      benchmark() also accumulates);
    - ``shard``     — host state gather + batch placement (device_put
                      onto the mesh when hapi trains sharded);
    - ``dispatch``  — compiled-program launch (async on real
                      accelerators; wrapped in the xplane join
                      annotation);
    - ``sync``      — host synchronization on the loss + state writeback;
    - ``callback``  — metric/log/callback work between steps.

    Producers that only see the dispatch window (`ShardedTrainStep`,
    the compiled pipeline steps) record a span with a single ``dispatch``
    phase via `train_dispatch_span`.
    """

    producer = "paddle_tpu.profiler.tracing.train"

    PID_TRAIN = 1
    TID_STEPS = 0
    PHASES = ("data", "shard", "dispatch", "sync", "callback")

    def __init__(self, capacity=65536):
        super().__init__(capacity=capacity, sample=1.0)
        self._meta = [
            self._meta_ev("process_name", self.PID_TRAIN, 0,
                          {"name": "paddle-tpu-train"}),
            self._meta_ev("thread_name", self.PID_TRAIN, self.TID_STEPS,
                          {"name": "train-step"}),
        ]

    def record_train_step(self, step_id, phases, args=None):
        """Emit the ``train_step`` span and its phase children. `phases`
        is {name: (start, end)} in monotonic seconds; the step span covers
        min(start)..max(end)."""
        self.phased_span("train_step", self.PID_TRAIN, self.TID_STEPS,
                         step_id, phases, self.PHASES, args)


@contextlib.contextmanager
def train_dispatch_span(tracer, args=None):
    """Wrap ONE compiled train-step dispatch: allocates a step id, runs
    the body under the xplane join annotation, and records a ``train_step``
    span whose only phase is ``dispatch``. For producers (ShardedTrainStep,
    the pipelined GPT step) that hand back device arrays and never see the
    caller's host sync; the span carries ``launch_ns`` as a serving
    step's does (`Tracer.launch`). Yields the step id."""
    sid = tracer.next_step_id()
    args = dict(args or ())
    t0 = time.monotonic()
    try:
        with tracer.launch(sid, args):
            yield sid
    finally:
        tracer.record_train_step(sid, {"dispatch": (t0, time.monotonic())},
                                 args)


class InstrumentedStep:
    """Callable wrapper adding one `train_dispatch_span` per call when the
    process train tracer is on (a single pointer test when off). Every
    OTHER attribute — `jax.jit`'s ``.lower``/``.trace``/``.eval_shape`` —
    delegates to the wrapped callable, so AOT workflows and memory
    analysis see the compiled function unchanged."""

    def __init__(self, fn, args=None):
        self._fn = fn
        self._span_args = args

    def __call__(self, *args, **kwargs):
        tr = train_tracer()
        if tr is None:
            return self._fn(*args, **kwargs)
        with train_dispatch_span(tr, self._span_args):
            return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


# -- process-wide training tracer ------------------------------------------
#
# Training has no engine object to hang a tracer on (Model, ShardedTrainStep
# and the pipeline steps are independent), so the training tracer is a
# process singleton: every producer asks `train_tracer()` per step and gets
# None (one pointer test, nothing else) unless tracing is on.

_tracer = None          # set by enable_/disable_train_tracing


def train_tracer():
    """The process-wide `TrainTracer`, or None when training tracing is
    off (`enable_train_tracing` turns it on)."""
    return _tracer


def enable_train_tracing(capacity=65536):
    """Turn training tracing on with a fresh ring of ``capacity`` events;
    returns the tracer."""
    global _tracer
    _tracer = TrainTracer(capacity=max(16, int(capacity)))
    return _tracer


def disable_train_tracing():
    """Turn training tracing off."""
    global _tracer
    _tracer = None


def reset_train_tracing():
    """Back to off, dropping the tracer and what it recorded (tests; long
    processes that want to drop a recorded trace): the same as
    `disable_train_tracing`."""
    disable_train_tracing()
