"""Per-request lifecycle tracing and engine step timeline (Perfetto export).

The diagnostic substrate for the serving stack: when p95 TTFT spikes or
speculative acceptance drops, aggregate Prometheus counters
(serving/metrics.py) can say *that* it happened, but not *where request X
spent its time* or *what the engine did on step N*. `EngineTracer` records
exactly those two views as Chrome/Perfetto trace events:

- a **per-request lifecycle span tree** — one track per in-flight request
  carrying its ``enqueue`` instant, the ``queued`` span (arrival →
  admission, tagged with the prefix-cache match length), a ``requeued``
  span per preemption round-trip, one span per prefill chunk and per
  decode/verify step the request rode on, the ``ttft`` span (arrival →
  first token), block-pool instants (``alloc``, ``cow``,
  ``spec_reserve``/``spec_reclaim``, ``preempt``), and the closing
  ``request`` span (arrival → finish/abort) with the request's summary;
- an **engine step timeline** — one ``step`` span per device step,
  recorded when the step is read (the engine launches a step before it
  reads the one in flight, so consecutive spans overlap; ``ahead`` is 1 on
  a step so launched), with phase children ``plan`` (scheduling),
  ``build`` (host batch assembly), ``dispatch`` (device program launch),
  ``sync`` (host sync on the sampled tokens), ``emit`` (token emission),
  tagged with the batch
  composition (decode rows, prefill chunks, spec lanes), program kind
  (mixed/decode/verify), token counts, the ragged kernel's live and
  total grid steps (``grid_live``/``grid_total``), the blocks the
  append's writer copies back (``append_blocks``) and the hand-over to
  the device inside ``dispatch``: ``h2d_arrays`` (one: the step's packed
  host input), ``h2d_bytes`` (its size) and ``h2d_ms`` (that one
  transfer: ``dispatch``'s start to just before the launch). Then
  ``launch_ns`` (``time.monotonic_ns()`` taken immediately before the
  step's annotation is entered: the monotonic half of the step's clock
  pair, which `join_engine_steps` fits) and ``width`` (the width bucket of
  the program that ran, beside ``slots``: the join holds steps of one
  width to one program). A traced step runs the one ``np.asarray`` an
  untraced one does; what ``sync`` is made of is read from a device
  capture (device end -> the read's return), not from a second path. A model
  with expert layers and state a lane (models/nemotron_h.py) adds the
  counts its step program makes, which ride the step's one packed
  result: ``moe_pairs`` (picks of live tokens), ``moe_pairs_local``
  (those naming an expert held here, computed), ``moe_experts_hit``
  (held experts with a token), all three summed over the expert
  layers, ``moe_max_load`` (the largest load of one expert of one
  layer) and ``state_rows`` (lanes whose state moved). Pool
  evictions land as instants on a ``block-pool`` track.

The ring buffer, clocks, export, and the xplane join annotation are the
shared recorder in `paddle_tpu.profiler.tracing` (`Tracer`), which the
training stack's `TrainTracer` builds on too — this module adds only the
serving-specific tracks and span vocabulary. The engine's ``trace``
(on, or a request sampling fraction) and ``trace_buffer`` (the ring bound)
arguments set it up, and the one-pointer-test off-by-default discipline is
shared verbatim; see the base module's docstring.

Export: `chrome_trace()` returns the standard trace-event JSON object
(``{"traceEvents": [...]}``) — serve it from ``GET /debug/trace``
(serving/server.py), `dump()` it to a file, and open it at
https://ui.perfetto.dev. Device-side correlation: while tracing, every
device dispatch is wrapped in a ``jax.profiler.TraceAnnotation`` named
``paddle_tpu.step <id>`` carrying the SAME step id as the host ``step``
span (`Tracer.launch`). The annotation spans only the launch CALL; with
it `profiler.xplane.join_engine_steps` finds, in a `jax.profiler.trace`
capture, the step program's RUN on the device (the event of the device
plane's ``XLA Modules`` line that the step launched) and lays it on this
tracer's clock, fitted from every step's (``launch_ns``, annotation's
start): launch -> issue -> device start -> device end -> done -> the read
returns, so the device's idle time between two runs splits into waiting
to be heard (``readback``), for the host and to start (``launch``):
`profiler.xplane.split_idle`, `print_step_timeline`.
"""
from __future__ import annotations

import time

from ..profiler.tracing import (  # noqa: F401  (re-exported API)
    STEP_ANNOTATION_PREFIX,
    Tracer,
)

# process ids of the two fixed tracks groups
PID_ENGINE = 1
PID_REQUESTS = 2
# tids inside PID_ENGINE
TID_STEPS = 0
TID_POOL = 1
TID_SUPERVISOR = 2
# request lanes: tids PID_REQUESTS/[_LANE_BASE, _LANE_BASE + _NUM_LANES).
# Lanes are reused round-robin; concurrent requests can never collide as
# long as max_batch + max_waiting < _NUM_LANES (every event still carries
# its request_id in args, so even a collision is attributable).
_LANE_BASE = 10
_NUM_LANES = 256

_STEP_PHASES = ("plan", "build", "dispatch", "sync", "emit")


class EngineTracer(Tracer):
    """Bounded trace-event recorder for one `LLMEngine`.

    All timestamps come from ``time.monotonic()`` — the same clock
    `Request.arrival_time` and ServingMetrics use, so TTFT/queue-wait
    spans agree with the metric quantiles by construction. The engine
    thread is the only writer; `chrome_trace()` may be called from any
    thread (the HTTP event loop mid-serve) — the base class's lock covers
    the ring append and the export snapshot.
    """

    producer = "paddle_tpu.serving.trace"

    def __init__(self, capacity=65536, sample=1.0):
        super().__init__(capacity=capacity, sample=sample)
        self._acc = 0.0           # deterministic sampling accumulator
        self._lane_of = {}        # request_id -> tid (live requests only)
        self._next_lane = 0
        self._meta = [
            self._meta_ev("process_name", PID_ENGINE, 0,
                          {"name": "paddle-tpu-engine"}),
            self._meta_ev("thread_name", PID_ENGINE, TID_STEPS,
                          {"name": "engine-step"}),
            self._meta_ev("thread_name", PID_ENGINE, TID_POOL,
                          {"name": "block-pool"}),
            self._meta_ev("thread_name", PID_ENGINE, TID_SUPERVISOR,
                          {"name": "supervisor"}),
            self._meta_ev("process_name", PID_REQUESTS, 0,
                          {"name": "requests"}),
        ]
        self._named_lanes = set()

    # -- request lifecycle --------------------------------------------------

    def should_trace(self, req):
        """Decide once per request at `add`: the per-request ``trace``
        override wins; otherwise an error-diffusion accumulator admits
        exactly ``sample`` of the request stream (deterministic — tests
        and repeated captures see the same selection)."""
        if req.trace is not None:
            return bool(req.trace)
        self._acc += self.sample
        if self._acc >= 1.0:
            self._acc -= 1.0
            return True
        return False

    def _lane(self, req):
        tid = self._lane_of.get(req.request_id)
        if tid is None:
            tid = _LANE_BASE + (self._next_lane % _NUM_LANES)
            self._next_lane += 1
            self._lane_of[req.request_id] = tid
            if tid not in self._named_lanes:
                self._named_lanes.add(tid)
                # under the ring lock: chrome_trace() snapshots _meta
                # from the HTTP thread while this (engine) thread names
                # new lanes mid-serve
                with self._lock:
                    self._meta.append(self._meta_ev(
                        "thread_name", PID_REQUESTS, tid,
                        {"name": f"req-lane-{tid - _LANE_BASE:03d}"}))
        return tid

    def begin_request(self, req):
        self.instant("enqueue", PID_REQUESTS, self._lane(req),
                     t=req.arrival_time,
                     args={"request_id": req.request_id,
                           "prompt_tokens": len(req.prompt_ids),
                           "max_new_tokens": req.max_new_tokens})

    def request_admitted(self, req, now):
        """Close the wait span: ``queued`` for the first admission (from
        arrival), ``requeued`` for a post-preemption re-admission (from
        the preemption)."""
        first = req.wait_since == req.arrival_time and not req.preemptions
        self.complete("queued" if first else "requeued",
                      PID_REQUESTS, self._lane(req), req.wait_since, now,
                      args={"request_id": req.request_id,
                            "cached_tokens": req.num_cached,
                            "prefix_hit_tokens": req.prefix_hit_tokens,
                            "preemptions": req.preemptions})

    def request_instant(self, req, name, args=None):
        a = {"request_id": req.request_id}
        if args:
            a.update(args)
        self.instant(name, PID_REQUESTS, self._lane(req), args=a)

    def row_span(self, req, name, start, end, args=None):
        """One span for a step this request rode on (``prefill_chunk``,
        ``decode``, or ``verify``), covering the step's device window."""
        a = {"request_id": req.request_id}
        if args:
            a.update(args)
        self.complete(name, PID_REQUESTS, self._lane(req), start, end, a)

    def first_token(self, req, now):
        self.complete("ttft", PID_REQUESTS, self._lane(req),
                      req.arrival_time, now,
                      args={"request_id": req.request_id})

    def end_request(self, req, reason, now=None):
        """The closing ``request`` span (arrival -> finish/abort) with the
        whole lifecycle summary; frees the request's lane."""
        now = time.monotonic() if now is None else now
        self.complete(
            "request", PID_REQUESTS, self._lane(req), req.arrival_time, now,
            args={
                "request_id": req.request_id,
                "reason": reason,
                "prompt_tokens": len(req.prompt_ids),
                "output_tokens": len(req.output_ids),
                "prefix_hit_tokens": req.prefix_hit_tokens,
                "preemptions": req.preemptions,
                "spec_accepted_tokens": req.spec_accepted,
            })
        self._lane_of.pop(req.request_id, None)

    # -- engine step timeline ----------------------------------------------

    def record_step(self, step_id, kind, phases, args):
        """Emit the ``step`` span and its phase children on the engine
        track. `phases` is {name: (start, end)} in monotonic seconds; the
        step span covers min(start)..max(end)."""
        a = {"kind": kind}
        a.update(args)
        self.phased_span(f"step[{kind}]", PID_ENGINE, TID_STEPS, step_id,
                         phases, _STEP_PHASES, a)

    def pool_instant(self, name, args=None):
        self.instant(name, PID_ENGINE, TID_POOL, args=args)

    def supervisor_instant(self, name, args=None):
        """Fault-injection fires, poison-bisection probes/verdicts, and
        watchdog trips land on the ``supervisor`` track — a chaos run's
        injected failures and the engine's recovery decisions line up
        against the step timeline in one Perfetto view."""
        self.instant(name, PID_ENGINE, TID_SUPERVISOR, args=args)
