"""Lifecycle tracing + engine step timeline (serving/trace.py).

Acceptance criteria from the observability issue:

- the exported JSON is valid Chrome/Perfetto trace-event format and a
  known scenario produces the expected span names (the schema canary —
  drift fails CI, not a user's Perfetto import);
- spans nest and close for every interleaving of preempt/abort/COW
  (churn harness reused from tests/test_prefix_cache.py): every traced
  request that terminates gets exactly ONE closing ``request`` span,
  phase children sit inside their ``step`` parent;
- the ring buffer never grows past its bound;
- tracing disabled is byte-identical output to the untraced path (and
  `engine.tracer` is None — the hook sites are pointer tests, nothing
  else);
- TTFT/queue-wait spans agree with ServingMetrics quantiles;
- satellites: the per-request JSON summary log line, and the Prometheus
  exposition's `# HELP`/`_count`/`_sum` contract.
"""
import json
import logging

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.trace import (PID_ENGINE, PID_REQUESTS, TID_STEPS,
                                      EngineTracer)

_PH = {"X", "i", "M"}


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(model, **kw)


def _events(engine, name=None, ph=None):
    evs = engine.tracer.chrome_trace()["traceEvents"]
    if name is not None:
        evs = [e for e in evs if e["name"] == name]
    if ph is not None:
        evs = [e for e in evs if e["ph"] == ph]
    return evs


def _validate_trace_event_json(trace):
    """Every structural property a Perfetto import depends on."""
    json.loads(json.dumps(trace))  # JSON-serializable end to end
    assert isinstance(trace["traceEvents"], list)
    for ev in trace["traceEvents"]:
        assert ev["ph"] in _PH, ev
        assert isinstance(ev["name"], str) and ev["name"], ev
        assert isinstance(ev["pid"], int) and isinstance(ev["tid"], int), ev
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0, ev
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0, ev
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name"), ev
            assert ev["args"]["name"], ev


# -- schema canary (CI gate against trace-format drift) ---------------------

def test_trace_schema_canary(model):
    """A known scenario (two requests, one multi-chunk prefill, greedy
    decode) must export valid trace-event JSON containing exactly the
    span vocabulary the docs and the Perfetto workflow rely on."""
    engine = _engine(model, prefill_chunk=8, trace=1.0)
    engine.generate(_prompts((20, 7), seed=1), max_new_tokens=4)
    trace = engine.tracer.chrome_trace()
    _validate_trace_event_json(trace)

    names = {e["name"] for e in trace["traceEvents"]}
    # engine step timeline: step spans + all five phase children
    assert {"step[mixed]", "step[decode]"} <= names
    assert {"plan", "build", "dispatch", "sync", "emit"} <= names
    # request lifecycle span tree
    assert {"enqueue", "queued", "prefill_chunk", "decode", "ttft",
            "request"} <= names
    # track metadata survives export
    assert {"process_name", "thread_name"} <= names

    # the lifecycle spans live on request lanes, the timeline on engine 0
    for e in trace["traceEvents"]:
        if e["name"] in ("queued", "request", "ttft", "decode",
                         "prefill_chunk", "enqueue"):
            assert e["pid"] == PID_REQUESTS
            assert e["args"]["request_id"] is not None
        if e["name"].startswith("step[") or e["name"] in (
                "plan", "build", "dispatch", "sync", "emit"):
            assert e["pid"] == PID_ENGINE and e["tid"] == TID_STEPS
    # step spans carry the batch composition the issue asks for
    step = next(e for e in trace["traceEvents"]
                if e["name"] == "step[mixed]")
    for key in ("step", "kind", "decode_rows", "prefill_rows",
                "spec_lanes", "fed_tokens", "emitted_tokens"):
        assert key in step["args"], step["args"]
    assert trace["otherData"]["dropped_events"] == 0


def test_phases_nest_inside_their_step(model):
    engine = _engine(model, prefill_chunk=8, trace=1.0)
    engine.generate(_prompts((20, 9), seed=2), max_new_tokens=4)
    steps = {e["args"]["step"]: e for e in _events(engine, ph="X")
             if e["name"].startswith("step[")}
    phases = [e for e in _events(engine, ph="X")
              if e["name"] in ("plan", "build", "dispatch", "sync", "emit")]
    assert steps and phases
    eps = 1e-3  # ts/dur are rounded to 3 decimals (ns resolution)
    for ph in phases:
        parent = steps[ph["args"]["step"]]
        assert ph["ts"] >= parent["ts"] - eps, (ph, parent)
        assert (ph["ts"] + ph["dur"]
                <= parent["ts"] + parent["dur"] + eps), (ph, parent)


# -- spans close under churn (preempt/abort/COW interleavings) --------------

def test_spans_close_under_churn(model):
    """The prefix-cache churn harness with tracing on: shared prefixes
    through a tiny pool force hits, COW, preemptions, and aborts; every
    traced request must still close with exactly one ``request`` span
    whose reason matches how it terminated."""
    rs = np.random.RandomState(0)
    engine = LLMEngine(model, block_size=4, num_blocks=10, max_batch=3,
                       max_seq_len=64, prefill_chunk=8, trace=1.0)
    prefixes = [rs.randint(0, 128, (8,)).tolist() for _ in range(3)]
    all_rids, aborted = [], set()
    for rnd in range(4):
        reqs = []
        for _ in range(rs.randint(2, 5)):
            p = (prefixes[rs.randint(len(prefixes))]
                 + rs.randint(0, 128, (rs.randint(0, 9),)).tolist())
            reqs.append(engine.add_request(
                p, max_new_tokens=int(rs.randint(2, 8))))
        doomed = set(rs.choice(reqs, size=len(reqs) // 3, replace=False)
                     .tolist()) if len(reqs) >= 3 else set()
        steps = 0
        while engine.has_unfinished():
            engine.step()
            steps += 1
            if steps == 2:
                for rid in doomed:
                    if engine.abort(rid):   # may already have finished
                        aborted.add(rid)
        all_rids.extend(reqs)
        for rid in reqs:
            if rid not in aborted:
                engine.release(rid)

    closes = {}
    for e in _events(engine, name="request"):
        rid = e["args"]["request_id"]
        assert rid not in closes, f"request {rid} closed twice"
        closes[rid] = e
    assert set(closes) == set(all_rids)  # every request closed exactly once
    for rid, e in closes.items():
        want = "aborted" if rid in aborted else "finished"
        assert e["args"]["reason"] == want, (rid, e["args"])
        # the span tree is consistent: outputs in the summary match reality
        assert e["args"]["output_tokens"] >= (0 if rid in aborted else 1)
    # the churn actually exercised the mechanisms it claims to
    names = {e["name"] for e in _events(engine)}
    assert "cow" in names, "no COW instant recorded"
    c = engine.metrics.counters
    assert c.get("requests_aborted", 0) > 0
    # preemptions happened iff preempt instants were recorded
    assert ("preempt" in names) == (c.get("preemptions", 0) > 0)
    _validate_trace_event_json(engine.tracer.chrome_trace())


def test_ring_buffer_never_grows_past_bound(model):
    engine = _engine(model, trace=1.0, trace_buffer=64)
    for wave in range(3):
        engine.generate(_prompts((12, 9, 7), seed=wave), max_new_tokens=8)
    tr = engine.tracer
    assert len(tr.events) == 64          # full, not past capacity
    assert tr.dropped > 0                # the ring actually wrapped
    assert tr.capacity == 64
    # export still valid after wrap (metadata lives outside the ring)
    trace = tr.chrome_trace()
    _validate_trace_event_json(trace)
    assert any(e["ph"] == "M" for e in trace["traceEvents"])


# -- disabled tracing is free ----------------------------------------------

def test_disabled_tracing_is_byte_identical_and_absent(model):
    prompts = _prompts((13, 6, 20), seed=3)
    off = _engine(model, prefill_chunk=8)
    assert off.tracer is None            # default: no tracer object at all
    out_off = off.generate(prompts, max_new_tokens=6)
    on = _engine(model, prefill_chunk=8, trace=1.0)
    out_on = on.generate(prompts, max_new_tokens=6)
    assert out_on == out_off             # tracing never changes tokens
    assert len(on.tracer.events) > 0


class _ResultSpy:
    """Stands in for the step's device result: counts the host's reads of
    it (`np.asarray` goes through `__array__`) and its waits."""

    def __init__(self, array, calls):
        self._array, self._calls = array, calls

    def copy_to_host_async(self):
        self._calls["copy_to_host_async"] += 1
        return self._array.copy_to_host_async()

    def block_until_ready(self):
        self._calls["block_until_ready"] += 1
        return self._array.block_until_ready()

    def __array__(self, dtype=None, copy=None):
        self._calls["asarray"] += 1
        return np.asarray(self._array, dtype=dtype)


def _spy_on_result(engine):
    calls = {"steps": 0, "asarray": 0, "block_until_ready": 0,
             "copy_to_host_async": 0}
    run_step = engine._run_step

    def spied(*args, **kwargs):
        calls["steps"] += 1
        return _ResultSpy(run_step(*args, **kwargs), calls)

    engine._run_step = spied
    return calls


@pytest.mark.parametrize("trace", [None, 1.0], ids=["untraced", "traced"])
def test_step_reads_its_result_once_and_never_waits(model, trace):
    """A step's one host sync is one `np.asarray` of the packed result and
    nothing calls `block_until_ready`, with tracing off AND on: a traced
    step times the `sync` production runs, not a second path; what `sync`
    is made of is read from a device capture."""
    prompts = _prompts((13, 6, 20), seed=3)
    want = _engine(model, prefill_chunk=8).generate(prompts, max_new_tokens=6)
    engine = _engine(model, prefill_chunk=8, trace=trace)
    calls = _spy_on_result(engine)
    assert engine.generate(prompts, max_new_tokens=6) == want
    assert calls["steps"] > 0
    assert calls["asarray"] == calls["steps"]
    assert calls["block_until_ready"] == calls["copy_to_host_async"] == 0


def test_step_span_says_when_it_launched_and_which_program_ran(model):
    """The args the device-timeline join reads (`profiler/xplane.py
    join_engine_steps`): `launch_ns`, the monotonic half of the step's
    clock pair, stamped inside `dispatch` after the transfer and just
    before the annotation; `width` beside `slots`, which names the program
    that ran. Every arg of the hand-over has a reader: none times `sync`'s
    parts, which a traced step does not split."""
    engine = _engine(model, prefill_chunk=8, trace=1.0)
    engine.generate(_prompts((13, 6), seed=4), max_new_tokens=5)
    trace = engine.tracer.chrome_trace()
    epoch_ns = trace["otherData"]["epoch_ns"]
    steps = [e for e in trace["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("step[")]
    assert {e["args"]["kind"] for e in steps} == {"mixed", "decode"}
    phases = {(e["args"]["step"], e["name"]): e
              for e in trace["traceEvents"]
              if e["ph"] == "X" and e["pid"] == PID_ENGINE
              and e["tid"] == TID_STEPS and not e["name"].startswith("step[")}
    slack = 1.0     # us: the spans' stamps are rounded to the nanosecond
    for e in steps:
        a, sid = e["args"], e["args"]["step"]
        for key in ("launch_ns", "h2d_ms", "width", "slots"):
            assert key in a, (key, a)
        assert not {"launch_ms", "wait_ms", "d2h_ms", "d2h_bytes"} & set(a)
        dispatch = phases[sid, "dispatch"]
        at_us = (a["launch_ns"] - epoch_ns) / 1e3
        assert dispatch["ts"] + a["h2d_ms"] * 1e3 - slack <= at_us
        assert at_us <= dispatch["ts"] + dispatch["dur"] + slack
        assert a["width"] in engine.width_buckets
        assert a["slots"] == sum(n * w for n, w in
                                 engine.step_groups(a["width"]))


def test_sampling_fraction_and_per_request_override(model):
    engine = _engine(model, trace=0.25)
    prompts = _prompts((5,) * 8, seed=4)
    rids = [engine.add_request(p, max_new_tokens=2) for p in prompts]
    traced = [r for r in rids if engine.get_request(r).traced]
    assert len(traced) == 2              # deterministic: every 4th request
    while engine.has_unfinished():
        engine.step()
    # per-request override beats the sampler in both directions
    forced = engine.add_request(_prompts((5,), seed=5)[0],
                                max_new_tokens=2, trace=True)
    assert engine.get_request(forced).traced
    denied_ids = [engine.add_request(p, max_new_tokens=2, trace=False)
                  for p in _prompts((4,) * 8, seed=6)]
    assert not any(engine.get_request(r).traced for r in denied_ids)
    while engine.has_unfinished():
        engine.step()
    closed = {e["args"]["request_id"] for e in _events(engine,
                                                       name="request")}
    assert forced in closed
    assert closed.isdisjoint(denied_ids)


# -- agreement with ServingMetrics -----------------------------------------

def test_ttft_and_queue_wait_spans_agree_with_metrics(model):
    """The acceptance criterion: the trace's TTFT spans are the SAME
    measurements ServingMetrics aggregates into its quantiles — same
    clock, same anchors — so span durations must reproduce the metric
    summary to float precision, and queue waits must be consistent with
    admission (inside the request span, before its first token)."""
    engine = _engine(model, trace=1.0, max_batch=2)
    engine.generate(_prompts((9, 14, 6, 11), seed=7), max_new_tokens=5)
    ttft_spans = sorted(e["dur"] / 1e6 for e in _events(engine, name="ttft"))
    lat = engine.metrics.latency_summary()["ttft"]
    assert len(ttft_spans) == lat["count"] == 4
    assert ttft_spans[-1] == pytest.approx(lat["max_ms"] / 1e3, abs=2e-6)
    assert sum(ttft_spans) == pytest.approx(
        lat["total_ms"] / 1e3, abs=1e-5)
    p95 = lat["p95_ms"] / 1e3
    assert any(abs(s - p95) < 2e-6 for s in ttft_spans)
    # queue-wait spans: start at arrival (request span start), end before
    # the request's first token lands
    reqs = {e["args"]["request_id"]: e for e in _events(engine,
                                                        name="request")}
    ttfts = {e["args"]["request_id"]: e for e in _events(engine,
                                                         name="ttft")}
    queued = [e for e in _events(engine, name="queued")]
    assert len(queued) == 4
    for q in queued:
        rid = q["args"]["request_id"]
        assert q["ts"] == pytest.approx(reqs[rid]["ts"], abs=1e-3)
        assert q["ts"] + q["dur"] <= ttfts[rid]["ts"] + ttfts[rid]["dur"] \
            + 1e-3


# -- satellite: per-request summary log ------------------------------------

def test_request_log_lines(model, caplog):
    engine = _engine(model, request_log=True, prefill_chunk=8)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving.request"):
        rids = [engine.add_request(p, max_new_tokens=3)
                for p in _prompts((18, 5), seed=8)]
        victim = engine.add_request(_prompts((6,), seed=9)[0],
                                    max_new_tokens=3)
        engine.step()
        engine.abort(victim)
        while engine.has_unfinished():
            engine.step()
    recs = [json.loads(r.message) for r in caplog.records
            if r.name == "paddle_tpu.serving.request"]
    assert len(recs) == 3                # one line per finish/abort, ever
    by_id = {r["request_id"]: r for r in recs}
    for rid in rids:
        r = by_id[str(rid)]
        assert r["reason"] == "finished"
        assert r["output_tokens"] == 3
        assert r["ttft_ms"] > 0 and r["queue_wait_ms"] >= 0
        assert r["ttft_ms"] <= r["total_ms"]
    assert by_id[str(victim)]["reason"] == "aborted"
    from paddle_tpu.serving import slo as slo_mod

    phase_keys = {f"phase_{p}_ms" for p in slo_mod.PHASES}
    for r in recs:                       # the full greppable schema
        assert {"event", "request_id", "reason", "prompt_tokens",
                "output_tokens", "prefix_hit_tokens",
                "spec_accepted_tokens", "preemptions", "queue_wait_ms",
                "ttft_ms", "tpot_ms", "total_ms", "tenant", "priority",
                "deadline_s", "deadline"} <= set(r)
        # the line's phase fields are derived from the ledger's phase
        # vocabulary (slo.PHASES) — line and ledger cannot drift — and
        # the decomposition sums to the line's own total_ms
        assert phase_keys <= set(r)
        assert sum(r[k] for k in phase_keys) == pytest.approx(
            r["total_ms"], abs=0.05)


def test_request_log_off_by_default(model, caplog):
    engine = _engine(model)
    with caplog.at_level(logging.INFO, logger="paddle_tpu.serving.request"):
        engine.generate(_prompts((5,), seed=10), max_new_tokens=2)
    assert not [r for r in caplog.records
                if r.name == "paddle_tpu.serving.request"]


# -- satellite: Prometheus exposition contract ------------------------------

def test_prometheus_help_type_and_count_sum(model):
    engine = _engine(model)
    engine.generate(_prompts((9, 5), seed=11), max_new_tokens=4)
    text = engine.metrics.prometheus_text()
    lines = text.splitlines()
    # every TYPE line is preceded by its HELP line, for every family
    for i, ln in enumerate(lines):
        if ln.startswith("# TYPE "):
            metric = ln.split()[2]
            assert lines[i - 1].startswith(f"# HELP {metric} "), ln
    # latency families expose _count/_sum so scrapers can build true rates
    for fam in ("ttft_seconds", "decode_step_seconds"):
        assert f"# HELP paddle_tpu_serving_{fam} " in text
        assert f"paddle_tpu_serving_{fam}_count " in text
        assert f"paddle_tpu_serving_{fam}_sum " in text
    # the bounded-window caveat is documented in the exposition itself
    assert "most recent 4096 observations" in text
    # counters keep their HELP too
    assert "# HELP paddle_tpu_serving_generated_tokens_total " in text


# -- tracer unit: lanes recycle, ids stay attributable ----------------------

def test_request_lanes_recycle_bounded_metadata():
    tracer = EngineTracer(capacity=1 << 14, sample=1.0)

    class _Req:
        def __init__(self, rid):
            self.request_id = rid
            self.prompt_ids = [1]
            self.max_new_tokens = 1
            self.output_ids = []
            self.arrival_time = tracer.epoch
            self.prefix_hit_tokens = 0
            self.preemptions = 0
            self.spec_accepted = 0

    for i in range(600):                 # > the 256-lane pool
        r = _Req(f"r{i}")
        tracer.begin_request(r)
        tracer.end_request(r, "finished")
    assert not tracer._lane_of           # every lane returned
    meta = [e for e in tracer.chrome_trace()["traceEvents"]
            if e["ph"] == "M"]
    assert len(meta) <= 256 + 8          # O(lanes), not O(requests)
    spans = [e for e in tracer.chrome_trace()["traceEvents"]
             if e["name"] == "request"]
    assert {e["args"]["request_id"] for e in spans} >= {"r599"}
