"""SLO ledger (serving/slo.py) + fault flight recorder (serving/postmortem.py).

Acceptance criteria from the observability issue:

- the ledger invariant: per-request phase durations sum to end-to-end
  wall time (float tolerance) across preempt/abort/fault interleavings,
  including preempted and fault-recovered requests (chaos harness
  reused from tests/test_serving_chaos.py);
- per-class rollups (p95 TTFT, TPOT, deadline attainment) exposed on
  /debug/slo and /metrics agree on the same traffic;
- exposition-spec conformance for the labeled histograms: ordered `le`
  buckets ending +Inf, `_count`/`_sum` consistent, label values escaped
  — locked by a /metrics parse test;
- each PR 9 fault class (poison isolation, watchdog trip, nonfinite
  row, thread death) produces exactly ONE valid postmortem bundle
  (valid JSON + Perfetto-loadable trace) and bundles prune to the cap;
- everything off by default: no ledger, no recorder, no slo_* series.

Fast deterministic variants run in tier-1; the randomized soak is
``slow``.
"""
import asyncio
import json
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import (
    AsyncLLMEngine,
    LLMEngine,
    ServingServer,
    faults,
)
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.slo import PHASES


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


@pytest.fixture(autouse=True)
def _disarm():
    yield
    plan = faults.active()
    if plan is not None:
        plan.release_hangs()
    faults.clear()


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(model, **kw)


def _assert_sums(req, abs_ms=0.05):
    """THE ledger invariant: the phase decomposition sums to e2e."""
    s = req.slo_summary
    assert s is not None, req.request_id
    assert set(s["phases_ms"]) == set(PHASES)
    assert sum(s["phases_ms"].values()) == pytest.approx(
        s["e2e_s"] * 1e3, abs=abs_ms), (req.request_id, s)
    return s


async def _http(port, method, path, obj=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(obj).encode() if obj is not None else b""
    writer.write(
        (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
         f"Content-Type: application/json\r\n"
         f"Content-Length: {len(data)}\r\n\r\n").encode() + data
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, OSError):
        pass
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.decode("latin1").split("\r\n")[0].split(" ")[1]), body


# -- Prometheus exposition parsing (the conformance lock) --------------------

_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$")
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _unescape(s):
    out, i = [], 0
    while i < len(s):
        if s[i] == "\\" and i + 1 < len(s):
            out.append({"n": "\n", '"': '"', "\\": "\\"}
                       .get(s[i + 1], "\\" + s[i + 1]))
            i += 2
        else:
            out.append(s[i])
            i += 1
    return "".join(out)


def _parse_prom(text):
    """(types, samples): every non-comment line must parse — an escaping
    bug anywhere invalidates the whole scrape, which is the point."""
    types, samples = {}, []
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            types[name] = kind
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m is not None, f"unparseable sample line: {line!r}"
        labels = {}
        if m.group(2):
            body = m.group(2)[1:-1]
            # the label body must be fully consumed by valid pairs
            rebuilt = ",".join(f'{k}="{v}"'
                               for k, v in _LABEL_RE.findall(body))
            assert rebuilt == body, f"bad label body: {body!r}"
            labels = {k: _unescape(v) for k, v in _LABEL_RE.findall(body)}
        samples.append((m.group(1), labels, float(m.group(3))))
    return types, samples


def _histogram_series(samples, family):
    """{labelkey: {"buckets": [(le, cum)], "sum": x, "count": n}} for one
    histogram family, le rows in exposition order."""
    out = {}
    for name, labels, value in samples:
        if not name.startswith(family):
            continue
        key = tuple(sorted((k, v) for k, v in labels.items() if k != "le"))
        s = out.setdefault(key, {"buckets": [], "sum": None, "count": None})
        if name == family + "_bucket":
            s["buckets"].append((labels["le"], value))
        elif name == family + "_sum":
            s["sum"] = value
        elif name == family + "_count":
            s["count"] = value
    return out


def _check_histogram_conformance(types, samples, family):
    assert types[family] == "histogram"
    series = _histogram_series(samples, family)
    assert series, family
    for key, s in series.items():
        les = [le for le, _ in s["buckets"]]
        assert les[-1] == "+Inf", (family, key, les)
        bounds = [float(le) for le in les[:-1]]
        assert bounds == sorted(bounds), (family, key)
        cums = [v for _, v in s["buckets"]]
        assert cums == sorted(cums), (family, key)   # cumulative
        assert s["count"] == cums[-1], (family, key)
        assert s["sum"] is not None
    return series


# -- off by default -----------------------------------------------------------


def test_everything_off_by_default(model):
    engine = _engine(model)
    assert engine.slo is None and engine.recorder is None
    assert engine.scheduler.slo is None
    engine.generate(_prompts((5,), seed=1), max_new_tokens=2)
    req_probe = engine.add_request(_prompts((4,), seed=2)[0],
                                   max_new_tokens=1)
    assert engine.get_request(req_probe).phase is None   # clock never ran
    assert "slo_" not in engine.metrics.prometheus_text()
    assert "postmortem" not in engine.metrics.prometheus_text()


def test_label_values_truncated():
    from paddle_tpu.serving.scheduler import Request

    req = Request([1, 2, 3], tenant="t" * 500, priority="p" * 500)
    assert req.tenant == "t" * 64          # multi-MB tenant strings must
    assert req.priority == "p" * 64        # not become metrics state


# -- decomposition invariant: happy path + preemption churn ------------------


def test_decomposition_sums_and_preemption_attribution(model):
    # pool sized so the younger of two long requests must be preempted
    # (a chunk row each, so that both prefill in the first step)
    engine = _engine(model, max_batch=2, num_blocks=5, slo=True,
                     token_budget=2 * 64)
    rids = [
        engine.add_request(_prompts((24,), seed=3)[0], max_new_tokens=8,
                           tenant="acme", priority="hi", deadline_s=60.0),
        engine.add_request(_prompts((24,), seed=4)[0], max_new_tokens=8,
                           tenant="free", priority="lo"),
    ]
    reqs = [engine.get_request(r) for r in rids]
    while engine.has_unfinished():
        engine.step()
    for req in reqs:
        s = _assert_sums(req)
        assert s["reason"] == "finished"
        assert all(v >= 0.0 for v in s["phases_ms"].values())
        assert s["phases_ms"]["decode_compute"] > 0.0
        assert s["ttft_s"] > 0.0 and s["tpot_s"] > 0.0
    assert reqs[1].preemptions >= 1
    assert reqs[1].slo_summary["phases_ms"]["preempted"] > 0.0
    assert reqs[0].slo_summary["deadline"] == "met"
    assert reqs[1].slo_summary["deadline"] is None     # no deadline set
    roll = engine.slo.rollup()
    by_class = {(c["tenant"], c["priority"]): c for c in roll["classes"]}
    acme = by_class[("acme", "hi")]
    assert acme["requests"] == 1 and acme["deadline"]["attainment"] == 1.0
    free = by_class[("free", "lo")]
    assert free["preemptions"] >= 1 and free["preemption_share"] > 0.0
    assert roll["total"]["requests"] == 2
    # rollup phase totals are the per-request decompositions, summed
    assert roll["total"]["phases_ms"]["preempted"] == pytest.approx(
        sum(r.slo_summary["phases_ms"]["preempted"] for r in reqs),
        abs=0.01)


def test_abort_and_queued_only_requests_close_cleanly(model):
    engine = _engine(model, max_batch=1, slo=True)
    run = engine.add_request(_prompts((6,), seed=5)[0], max_new_tokens=4)
    parked = engine.add_request(_prompts((6,), seed=6)[0], max_new_tokens=4,
                                deadline_s=30.0)
    run_req, parked_req = engine.get_request(run), engine.get_request(parked)
    engine.step()
    engine.abort(parked)                   # dies waiting: queued only
    while engine.has_unfinished():
        engine.step()
    s = _assert_sums(parked_req)
    assert s["reason"] == "aborted" and s["deadline"] == "aborted"
    assert s["phases_ms"]["queued"] > 0.0
    assert s["phases_ms"]["decode_compute"] == 0.0
    _assert_sums(run_req)


# -- /debug/slo vs /metrics on the same traffic ------------------------------


def test_debug_slo_and_metrics_agree_and_conform(model):
    engine = _engine(model, slo=True)
    weird = 'we"ird\\ten\nant'             # must survive label escaping

    async def main():
        server = await ServingServer(engine, port=0, max_waiting=8).start()
        jobs = []
        for i, (tenant, prio) in enumerate(
                [("acme", "hi")] * 3 + [("free", "lo")] * 2 + [(weird, "x")]):
            jobs.append(_http(
                server.port, "POST", "/v1/completions",
                {"prompt": _prompts((5 + i,), seed=7 + i)[0],
                 "max_tokens": 4, "tenant": tenant, "priority": prio,
                 "timeout_s": 30.0}))
        results = await asyncio.gather(*jobs)
        s1, slo_body = await _http(server.port, "GET", "/debug/slo")
        s2, met_body = await _http(server.port, "GET", "/metrics")
        s3, _ = await _http(server.port, "GET", "/debug/postmortem")
        await server.shutdown(drain=True)
        return results, (s1, slo_body), (s2, met_body), s3

    results, (s1, slo_body), (s2, met_body), s3 = asyncio.run(main())
    assert all(status == 200 for status, _ in results)
    assert s1 == 200 and s2 == 200
    assert s3 == 404                       # recorder off on this engine
    roll = json.loads(slo_body)
    by_class = {(c["tenant"], c["priority"]): c for c in roll["classes"]}
    assert by_class[("acme", "hi")]["requests"] == 3
    assert by_class[(weird, "x")]["requests"] == 1
    types, samples = _parse_prom(met_body.decode())
    pre = "paddle_tpu_serving_"
    for fam in ("slo_e2e_seconds", "slo_ttft_seconds", "slo_tpot_seconds"):
        series = _check_histogram_conformance(types, samples, pre + fam)
        if fam == "slo_e2e_seconds":
            e2e_series = series
    # per-class agreement between the JSON rollup and the scrape
    for (tenant, prio), entry in by_class.items():
        key = tuple(sorted({"tenant": tenant, "priority": prio}.items()))
        s = e2e_series[key]
        n = entry["e2e_ms"]["count"]
        assert s["count"] == n == entry["requests"]
        # nearest-rank p95 must land in a bucket consistent with the
        # histogram's cumulative counts: strictly fewer than `rank`
        # observations below its bucket, at least `rank` at/above it
        p95_s = entry["e2e_ms"]["p95"] / 1e3
        rank = -(-95 * n // 100)
        below = 0.0
        for le, cum in s["buckets"]:
            if le != "+Inf" and float(le) < p95_s:
                below = cum
        assert below < rank
        at_or_above = [cum for le, cum in s["buckets"]
                       if le == "+Inf" or float(le) >= p95_s]
        assert at_or_above and at_or_above[0] >= rank
    # labeled counters agree too (all six finished within deadline)
    met = {tuple(sorted(lbl.items())): v for name, lbl, v in samples
           if name == pre + "slo_deadline_met_total"}
    for (tenant, prio), entry in by_class.items():
        key = tuple(sorted({"tenant": tenant, "priority": prio}.items()))
        assert met[key] == entry["deadline"]["met"] == entry["requests"]
        assert entry["deadline"]["attainment"] == 1.0
    # the weird tenant's label value round-trips exactly
    assert any(lbl.get("tenant") == weird for _, lbl, _ in samples)


# -- deadline verdicts through the frontend ----------------------------------


def test_frontend_timeout_is_missed_deadline(model):
    faults.install(FaultPlan([{"point": "slow_step_ms", "ms": 30}]))
    engine = _engine(model, slo=True)

    async def main():
        fe = await AsyncLLMEngine(engine, max_waiting=8).start()
        slow = fe.submit(_prompts((5,), seed=20)[0], max_new_tokens=48,
                         temperature=0.0, timeout_s=0.15, tenant="t")
        ok = fe.submit(_prompts((5,), seed=21)[0], max_new_tokens=3,
                       temperature=0.0, timeout_s=30.0, tenant="t")
        r_slow = await asyncio.wait_for(slow.collect(), 30.0)
        r_ok = await asyncio.wait_for(ok.collect(), 30.0)
        await fe.shutdown(drain=True, timeout_s=10.0)
        return (slow.req, r_slow), (ok.req, r_ok)

    (req_slow, (_, reason_slow)), (req_ok, (_, reason_ok)) = asyncio.run(
        main())
    assert reason_slow == "timeout" and reason_ok == "length"
    assert _assert_sums(req_slow)["deadline"] == "missed"
    assert _assert_sums(req_ok)["deadline"] == "met"
    roll = engine.slo.rollup()["total"]
    assert roll["deadline"]["met"] == 1
    assert roll["deadline"]["missed"] == 1
    assert roll["deadline"]["attainment"] == 0.5


# -- chaos: invariant + one bundle per fault class ---------------------------


def test_poison_isolation_ledger_and_bundle(model, tmp_path):
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "poison", "exc": "DeviceBoom"},
    ]))
    engine = _engine(model, postmortem_dir=str(tmp_path))
    assert engine.slo is not None          # the recorder implies a ledger

    async def main():
        fe = await AsyncLLMEngine(engine, max_waiting=8).start()
        streams = []
        for i, p in enumerate(_prompts((5, 9, 13), seed=22)):
            rid = "poison" if i == 1 else f"r{i}"
            streams.append(fe.submit(p, max_new_tokens=6, temperature=0.0,
                                     request_id=rid))
        results = await asyncio.wait_for(
            asyncio.gather(*(s.collect() for s in streams)), 30.0)
        await fe.shutdown(drain=True, timeout_s=10.0)
        return streams, results

    streams, results = asyncio.run(main())
    assert results[1][1] == "error"
    assert results[0][1] == results[2][1] == "length"
    for st in streams:
        _assert_sums(st.req)
    # the culprit's decomposition shows failure-boundary time
    assert streams[1].req.slo_summary["phases_ms"]["stalled"] > 0.0
    bundles = engine.recorder.list_bundles()
    assert [b["event"] for b in bundles] == ["poison_isolated"]
    assert bundles[0]["victim"] == "poison"
    bd = json.load(open(os.path.join(str(tmp_path), bundles[0]["name"],
                                     "bundle.json")))
    assert bd["victim"]["request_id"] == "poison"
    assert bd["fault_plan"]["fired"]       # the chaos run self-describes
    assert set(bd["victim"]["phases_ms"]) == set(PHASES)
    assert bd["metrics"]["counters"]["poison_requests_isolated"] == 1


def test_nonfinite_row_bundle_exactly_once(model, tmp_path):
    faults.install(FaultPlan([
        {"point": "step_nonfinite_logits", "request_id": "poison",
         "times": 1},
    ]))
    engine = _engine(model, postmortem_dir=str(tmp_path))
    engine.add_request(_prompts((5,), seed=23)[0], max_new_tokens=4,
                       request_id="poison")
    engine.add_request(_prompts((7,), seed=24)[0], max_new_tokens=4,
                       request_id="ok")
    # grab refs now: the abort releases the poison's engine record
    poison, ok = engine.get_request("poison"), engine.get_request("ok")
    while engine.has_unfinished():
        engine.step()
    assert [b["event"] for b in engine.recorder.list_bundles()] \
        == ["nonfinite_row"]
    assert ok.slo_summary["reason"] == "finished"
    _assert_sums(poison)


def test_watchdog_trip_stalled_and_bundle(model, tmp_path):
    plan = faults.install(FaultPlan([
        {"point": "step_hang", "at_step": 1, "timeout_s": 60.0},
    ]))
    # (a chunk row each: both requests are in the step that hangs)
    engine = _engine(model, trace=True, postmortem_dir=str(tmp_path),
                     token_budget=2 * 64)

    async def main():
        fe = await AsyncLLMEngine(
            engine, max_waiting=8,
            watchdog_step_timeout_s=0.2, watchdog_poll_s=0.05,
        ).start()
        streams = [fe.submit(p, max_new_tokens=4, temperature=0.0,
                             request_id=f"r{i}")
                   for i, p in enumerate(_prompts((5, 9), seed=25))]
        results = await asyncio.wait_for(
            asyncio.gather(*(s.collect() for s in streams)), 15.0)
        plan.release_hangs()
        await fe.shutdown(drain=True, timeout_s=10.0)
        return streams, results

    streams, results = asyncio.run(main())
    for _, reason in results:
        assert reason == "error"
    bundles = engine.recorder.list_bundles()
    assert [b["event"] for b in bundles] == ["watchdog_trip"]
    name = bundles[0]["name"]
    bd = json.load(open(os.path.join(str(tmp_path), name, "bundle.json")))
    assert bd["health"]["reason"] == "step_stuck"
    # Perfetto-loadable trace rode along (tracing was on)
    tr = json.load(open(os.path.join(str(tmp_path), name, "trace.json")))
    assert isinstance(tr["traceEvents"], list) and tr["traceEvents"]
    # the hung step's victims: wall time attributed to `stalled`, and
    # the invariant survives the watchdog/abort interleaving
    for st in streams:
        s = _assert_sums(st.req)
        assert s["phases_ms"]["stalled"] > 0.0


def test_thread_death_bundle(model, tmp_path):
    engine = _engine(model, postmortem_dir=str(tmp_path))

    async def main():
        fe = await AsyncLLMEngine(engine, max_waiting=8).start()
        streams = [fe.submit(p, max_new_tokens=40, temperature=0.0,
                             request_id=f"r{i}")
                   for i, p in enumerate(_prompts((5, 9), seed=26))]
        await asyncio.sleep(0.05)
        faults.install(FaultPlan([{"point": "thread_die"}]))
        results = await asyncio.wait_for(
            asyncio.gather(*(s.collect() for s in streams)), 10.0)
        await asyncio.wait_for(fe.shutdown(drain=False), 10.0)
        return streams, results

    streams, results = asyncio.run(main())
    for _, reason in results:
        assert reason == "error"
    bundles = engine.recorder.list_bundles()
    assert [b["event"] for b in bundles] == ["engine_thread_died"]
    for st in streams:                     # aborted by the crash epilogue
        _assert_sums(st.req)


# -- pruning + manifests -----------------------------------------------------


def test_bundles_prune_to_cap(model, tmp_path):
    engine = _engine(model, postmortem_dir=str(tmp_path), postmortem_keep=3)
    for i in range(5):
        path = engine.recorder.record("watchdog_trip", detail=f"drill {i}")
        assert path is not None
    bundles = engine.recorder.list_bundles()
    assert len(bundles) == 3
    assert [b["seq"] for b in bundles] == [2, 3, 4]   # oldest pruned
    assert engine.metrics.counters["postmortem_bundles"] == 5
    for b in bundles:
        assert "bundle.json" in b["files"]


# -- randomized soak ---------------------------------------------------------


@pytest.mark.slow
def test_chaos_soak_ledger_invariant(model):
    """Seeded random faults over a mixed multi-tenant wave: every
    request's decomposition sums to its e2e whatever interleaving ran,
    and class request counts add up."""
    rs = np.random.RandomState(41)
    prompts = [rs.randint(0, 128, (int(n),)).tolist()
               for n in rs.randint(3, 40, size=24)]
    faults.install(FaultPlan([
        {"point": "step_raise", "probability": 0.05, "seed": 1},
        {"point": "alloc_fail", "probability": 0.05, "seed": 2},
        {"point": "step_nonfinite_logits", "probability": 0.01, "seed": 3},
        {"point": "slow_step_ms", "probability": 0.1, "seed": 4, "ms": 2},
    ]))
    engine = _engine(model, slo=True)

    async def main():
        fe = await AsyncLLMEngine(engine, max_waiting=32,
                                  max_step_retries=4).start()
        streams = [fe.submit(p, max_new_tokens=int(rs.randint(1, 12)),
                             temperature=0.0, request_id=f"s{i}",
                             tenant=f"t{i % 3}", priority=str(i % 2),
                             timeout_s=60.0)
                   for i, p in enumerate(prompts)]
        await asyncio.wait_for(
            asyncio.gather(*(s.collect() for s in streams)), 120.0)
        await fe.shutdown(drain=True, timeout_s=30.0)
        return streams

    streams = asyncio.run(main())
    for st in streams:
        _assert_sums(st.req)
    roll = engine.slo.rollup()
    assert roll["total"]["requests"] == len(prompts)
    assert sum(c["requests"] for c in roll["classes"]) == len(prompts)
