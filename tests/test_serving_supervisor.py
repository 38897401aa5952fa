"""EngineSupervisor + StepWatchdog against a bare LLMEngine (synchronous).

The poison-isolation contract, driven by injected faults
(serving/faults.py): a step_raise pinned to one request aborts exactly
that request while every other in-flight request completes with output
token-identical to a no-fault run; transient faults attribute nobody;
only max_step_retries consecutive unattributable failures abort
everything. Plus non-finite containment, alloc_fail pressure, the
watchdog, and the standing invariants — after ANY injected fault
sequence, every refcount is zero and num_free equals idle capacity.

The async/HTTP layers of the same machinery are
tests/test_serving_chaos.py.
"""
import math
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import (
    EngineSupervisor,
    LLMEngine,
    StepWatchdog,
    faults,
)
from paddle_tpu.serving.faults import FaultPlan


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


@pytest.fixture(scope="module")
def ref_engine(model):
    """One shared no-fault engine for reference outputs — compiling a
    fresh pair of step programs per reference run is the dominant cost
    of this file (warm-vs-cold parity is PR 4's tested guarantee, so
    reuse cannot change the reference tokens)."""
    return LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _idle(engine):
    assert engine.pool._refcount == {}
    return engine.pool.num_free == engine.pool.num_blocks - 1


def _engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(model, **kw)


def _run(sup, max_steps=300):
    """Drive the supervised engine to completion; returns (outs, failures)
    accumulated across steps."""
    outs, failures = [], []
    steps = 0
    while sup.engine.has_unfinished():
        o, f = sup.step()
        outs += o
        failures += f
        steps += 1
        assert steps < max_steps, "supervised serve did not converge"
    return outs, failures


def _reference(ref_engine, prompts, n=6):
    return ref_engine.generate(prompts, max_new_tokens=n, temperature=0.0)


def _submit_all(eng, prompts, poison_index=None, n=6):
    """Add every prompt; the poisoned one gets request id 'poison'.
    Returns the request ids in order."""
    rids = []
    for i, p in enumerate(prompts):
        rid = "poison" if i == poison_index else f"r{i}"
        eng.add_request(p, max_new_tokens=n, temperature=0.0, request_id=rid)
        rids.append(rid)
    return rids


def test_poison_step_isolated_others_token_identical(model, ref_engine):
    """THE acceptance criterion: a step_raise pinned to one request in a
    full mixed batch aborts exactly that request with an error carrying
    the exception class; every other request completes token-identical
    to a no-fault run; pool drains to idle."""
    prompts = _prompts((5, 9, 13, 7), seed=0)
    refs = _reference(ref_engine, prompts)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "poison", "exc": "DeviceBoom"},
    ]))
    rids = _submit_all(eng, prompts, poison_index=2)
    _, failures = _run(sup)
    assert [rid for rid, _ in failures] == ["poison"]
    assert "FaultInjected" in failures[0][1]       # the exception class
    assert "DeviceBoom" in failures[0][1]
    for i, rid in enumerate(rids):
        if rid == "poison":
            assert rid not in eng._requests        # aborted + dropped
            continue
        assert list(eng._requests[rid].output_ids) == refs[i]
    assert eng.metrics.counters["poison_requests_isolated"] == 1
    assert eng.metrics.counters["engine_step_errors"] >= 1
    assert _idle(eng)


def test_bisection_probe_bound_is_logarithmic(model):
    """Isolating one poisoned request out of B costs O(log B) probe
    steps per failed step — never a per-request scan."""
    prompts = _prompts((5, 9, 13, 7), seed=1)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "poison"},
    ]))
    _submit_all(eng, prompts, poison_index=1)
    _run(sup)
    errors = eng.metrics.counters["engine_step_errors"]
    probes = eng.metrics.counters["engine_step_retries"]
    bound = errors * (math.ceil(math.log2(len(prompts))) + 1)
    assert probes <= bound, f"{probes} probes for {errors} failures"
    assert _idle(eng)


def test_transient_fault_attributes_nobody(model, ref_engine):
    """A fault that does not reproduce under probing (one-shot nth_call)
    aborts NO request: everyone recomputes and completes with the exact
    no-fault outputs."""
    prompts = _prompts((5, 9, 7), seed=2)
    refs = _reference(ref_engine, prompts)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_raise", "nth_call": 1},
    ]))
    rids = _submit_all(eng, prompts)
    _, failures = _run(sup)
    assert failures == []
    assert eng.metrics.counters.get("poison_requests_isolated", 0) == 0
    for i, rid in enumerate(rids):
        assert list(eng._requests[rid].output_ids) == refs[i]
    assert _idle(eng)


def test_abort_everything_after_max_consecutive_unattributable(model):
    """Unattributable failures (raise on the main step, clean on every
    probe) fall back to the pre-supervisor abort-everything behavior —
    but only after max_step_retries CONSECUTIVE ones."""
    prompts = _prompts((5,), seed=3)
    eng = _engine(model)
    sup = EngineSupervisor(eng, max_step_retries=3)
    # a single request: main steps and verify probes alternate, so odd
    # match() calls are main steps — three one-shot faults on calls
    # 1/3/5 raise three main steps in a row while every probe is clean
    faults.install(FaultPlan([
        {"point": "step_raise", "nth_call": 1},
        {"point": "step_raise", "nth_call": 3},
        {"point": "step_raise", "nth_call": 5},
    ]))
    eng.add_request(prompts[0], max_new_tokens=6, temperature=0.0,
                    request_id="solo")
    _, failures = _run(sup)
    assert [rid for rid, _ in failures] == ["solo"]
    assert "unattributable" in failures[0][1]
    assert eng.metrics.counters.get("poison_requests_isolated", 0) == 0
    assert _idle(eng)


@pytest.mark.slow
def test_clean_step_resets_unattributable_counter(model, ref_engine):
    """Two unattributable failures separated by a clean step never reach
    a max_step_retries=2 fallback — the counter is consecutive."""
    prompts = _prompts((5,), seed=4)
    refs = _reference(ref_engine, prompts)
    eng = _engine(model)
    sup = EngineSupervisor(eng, max_step_retries=2)
    # calls: 1 = main (raise) / 2 = probe (clean) / 3 = main (clean,
    # resets) / 4 = main (raise) / 5 = probe (clean) -> counter 1 < 2
    faults.install(FaultPlan([
        {"point": "step_raise", "nth_call": 1},
        {"point": "step_raise", "nth_call": 4},
    ]))
    eng.add_request(prompts[0], max_new_tokens=6, temperature=0.0,
                    request_id="solo")
    _, failures = _run(sup)
    assert failures == []
    assert list(eng._requests["solo"].output_ids) == refs[0]
    assert _idle(eng)


def test_nonfinite_fault_aborts_only_that_row(model, ref_engine):
    """step_nonfinite_logits drives the per-row NaN/Inf containment:
    the matched row ends error:nonfinite_logits, everyone else is
    token-identical to the no-fault run."""
    prompts = _prompts((5, 9, 7), seed=5)
    refs = _reference(ref_engine, prompts)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_nonfinite_logits", "request_id": "poison",
         "times": 1},
    ]))
    rids = _submit_all(eng, prompts, poison_index=1)
    _, failures = _run(sup)
    assert failures == [("poison", "nonfinite_logits")]
    assert eng.metrics.counters["nonfinite_rows"] == 1
    for i, rid in enumerate(rids):
        if rid != "poison":
            assert list(eng._requests[rid].output_ids) == refs[i]
    assert _idle(eng)


def test_real_nan_forward_is_contained_and_never_cached(model):
    """A genuinely NaN forward (poisoned weights, no fault plan) trips
    the same containment: the row aborts instead of emitting a garbage
    token, and none of its written blocks is published to the prefix
    cache (NaN KV must never serve a later request)."""
    import jax

    (p,) = _prompts((17,), seed=6)
    eng = _engine(model)
    eng._params = jax.tree_util.tree_map(
        lambda x: x * float("nan"), eng._params)
    eng.add_request(p, max_new_tokens=4, temperature=0.0, request_id="bad")
    outs, contained = [], []
    while eng.has_unfinished():
        outs += eng.step()
        contained += eng.step_faults
    assert outs == []                              # no token ever emitted
    assert contained == [("bad", "nonfinite_logits")]
    assert eng.pool._hash_index == {}              # nothing published
    assert _idle(eng)


@pytest.mark.slow
def test_alloc_fail_pressure_is_absorbed(model, ref_engine):
    """Phantom allocation failures defer/preempt exactly like real block
    pressure; the serve completes with the no-fault outputs."""
    prompts = _prompts((5, 9, 13), seed=7)
    refs = _reference(ref_engine, prompts)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "alloc_fail", "nth_call": 2},
        {"point": "alloc_fail", "nth_call": 5},
    ]))
    rids = _submit_all(eng, prompts)
    _, failures = _run(sup)
    assert failures == []
    for i, rid in enumerate(rids):
        assert list(eng._requests[rid].output_ids) == refs[i]
    assert _idle(eng)


def test_watchdog_trips_on_hung_step(model):
    """A step_hang wedges the (here: side) engine thread; the watchdog
    flips health to step_stuck within timeout + one poll interval and
    records the trip; after release the step completes and the pool
    drains."""
    (p,) = _prompts((5,), seed=8)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    plan = faults.install(FaultPlan([
        {"point": "step_hang", "at_step": 1, "timeout_s": 30.0},
    ]))
    eng.add_request(p, max_new_tokens=3, temperature=0.0, request_id="hung")
    wd = StepWatchdog(sup, timeout_s=0.15, poll_s=0.02).start()
    t = threading.Thread(target=_run, args=(sup,), daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while sup.health.healthy and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not sup.health.healthy
    snap = sup.health.snapshot()
    assert snap["reason"] == "step_stuck"
    assert snap["stuck_for_s"] >= 0.15
    assert eng.metrics.counters["watchdog_trips"] == 1
    assert eng.metrics.gauges["engine_unhealthy"] == 1.0
    assert wd.tripped
    plan.release_hangs()
    t.join(10.0)
    assert not t.is_alive()
    assert _idle(eng)
    wd.stop()


@pytest.mark.slow
def test_watchdog_quiet_on_healthy_serve(model):
    """No trip, no health flip, and a clean watchdog stop when steps
    finish inside the timeout."""
    (p,) = _prompts((5,), seed=9)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    wd = StepWatchdog(sup, timeout_s=30.0, poll_s=0.01).start()
    eng.add_request(p, max_new_tokens=4, temperature=0.0)
    _run(sup)
    wd.stop()
    assert not wd.tripped
    assert sup.health.healthy
    assert eng.metrics.counters.get("watchdog_trips", 0) == 0


def test_requeue_semantics(model):
    """requeue: running -> preempted to the waiting queue with blocks
    released; waiting -> True (already queued); unknown/finished ->
    False."""
    prompts = _prompts((5, 9), seed=10)
    eng = _engine(model)
    r0 = eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0)
    r1 = eng.add_request(prompts[1], max_new_tokens=4, temperature=0.0)
    assert eng.requeue(r0) is True                 # waiting: no-op True
    eng.step()                                     # admits + first chunk
    req0 = eng._requests[r0]
    assert req0.state == "running" and req0.blocks
    assert eng.requeue(r0) is True
    assert req0.state == "waiting" and not req0.blocks
    assert eng.requeue("nope") is False
    while eng.has_unfinished():
        eng.step()
    assert eng.requeue(r0) is False                # finished
    assert eng.requeue(r1) is False
    assert _idle(eng)


def test_schedule_only_restricts_planning_and_admission(model):
    """step(only=ids) plans rows ONLY for those requests — everyone else
    holds exactly still (num_cached, outputs, blocks unchanged)."""
    prompts = _prompts((5, 9), seed=11)
    eng = _engine(model)
    ra = eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0)
    rb = eng.add_request(prompts[1], max_new_tokens=4, temperature=0.0)
    outs = eng.step(only={ra})
    assert {o.request_id for o in outs} <= {ra}
    reqb = eng._requests[rb]
    assert reqb.state == "waiting" and reqb.num_cached == 0
    assert not reqb.output_ids
    while eng.has_unfinished():
        eng.step()
    assert len(eng._requests[rb].output_ids) == 4
    assert _idle(eng)


def test_contained_rows_survive_a_same_step_raise(model):
    """A step that poisons row A (non-finite containment) and THEN
    raises while emitting row B must still report A's failure — the
    containment abort already happened engine-side, and dropping it
    would leave A's consumer waiting forever."""
    prompts = _prompts((5, 9), seed=15)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_nonfinite_logits", "request_id": "A", "times": 1},
    ]))
    orig_emit = eng._emit
    state = {"armed": True}

    def bomb(req, token):
        out = orig_emit(req, token)
        if state["armed"] and req.request_id == "B":
            state["armed"] = False
            raise RuntimeError("emit-path bug")
        return out

    eng._emit = bomb
    eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0,
                    request_id="A")
    eng.add_request(prompts[1], max_new_tokens=4, temperature=0.0,
                    request_id="B")
    _, failures = _run(sup)
    assert ("A", "nonfinite_logits") in failures
    assert [rid for rid, _ in failures if rid == "B"] == []  # B recovered
    assert len(eng._requests["B"].output_ids) == 4
    assert _idle(eng)


def test_scheduler_raise_never_blames_the_previous_plan(model):
    """schedule() itself raising (here: phantom allocation pressure that
    starves even the oldest request) recovers against an EMPTY plan —
    unattributable, falling back to abort-everything after
    max_step_retries — instead of re-queueing and bisecting whatever the
    previous step happened to plan."""
    (p,) = _prompts((5,), seed=16)
    eng = _engine(model)
    sup = EngineSupervisor(eng, max_step_retries=3)
    faults.install(FaultPlan([{"point": "alloc_fail"}]))  # every allocate
    eng.add_request(p, max_new_tokens=4, temperature=0.0,
                    request_id="solo")
    _, failures = _run(sup)
    assert [rid for rid, _ in failures] == ["solo"]
    assert "unattributable" in failures[0][1]
    assert eng.metrics.counters.get("engine_step_retries", 0) == 0
    assert _idle(eng)


def test_probe_exonerates_only_stepped_ids(model):
    """A clean probe clears exactly the ids the scheduler planned: an id
    it could not step (deferred/unknown) learned nothing and must stay
    suspect — and a probe that stepped nothing is fully inconclusive."""
    (p,) = _prompts((5,), seed=13)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    eng.add_request(p, max_new_tokens=4, temperature=0.0, request_id="r0")
    raised, stepped, outs, step_faults = sup._probe(["ghost"])
    assert raised is False and stepped == []
    assert outs == [] and step_faults == []
    raised, stepped, outs, _ = sup._probe(["ghost", "r0"])
    assert raised is False
    assert stepped == ["r0"]              # the deferred id stays suspect
    assert outs                           # stepped: real chunk progress
    while eng.has_unfinished():
        eng.step()
    assert _idle(eng)


def test_bisect_keeps_unstepped_half_suspect(model):
    """An inconclusive half probe must not eliminate that half: with the
    first half unsteppable, bisection probes the other half instead and
    still attributes the reproducible culprit there; symmetrically, a
    clean other half keeps the unstepped half suspect without ever
    attributing an unprobed request."""
    prompts = _prompts((5, 9), seed=14)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "poison"},
    ]))
    eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0,
                    request_id="poison")
    culprit, _, _ = sup._bisect(["ghost", "poison"])
    assert culprit == "poison"
    eng.abort("poison")
    faults.clear()
    # no fault armed: other half clean, unstepped half stays suspect but
    # (being unsteppable) can never be positively attributed
    eng.add_request(prompts[1], max_new_tokens=4, temperature=0.0,
                    request_id="innocent")
    culprit, _, _ = sup._bisect(["ghost", "innocent"])
    assert culprit is None
    while eng.has_unfinished():
        eng.step()
    assert _idle(eng)


def test_supervisor_events_reach_the_trace(model):
    """Chaos runs are Perfetto-inspectable: fault fires, bisection
    probes, and the isolation verdict all land on the supervisor
    track."""
    prompts = _prompts((5, 9, 7), seed=12)
    eng = _engine(model, trace=True)
    sup = EngineSupervisor(eng)
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "poison"},
    ]))
    _submit_all(eng, prompts, poison_index=0)
    _run(sup)
    names = {e["name"] for e in eng.tracer.chrome_trace()["traceEvents"]}
    assert {"fault[step_raise]", "step_failed", "bisect_probe",
            "poison_isolated"} <= names
    assert _idle(eng)


def test_poison_window_counts_distinct_sources(model):
    """The sliding poison-isolation window (the fleet router's sick-chip
    signal): every bisection attribution records its request SOURCE —
    the tenant, "-" when untenanted — and `poison_stats` reports both
    the isolation count and the DISTINCT source count. Serial poison
    from one tenant (or one untenanted client minting request ids) stays
    ONE source; isolations across tenants accumulate sources."""
    prompts = _prompts((5, 7, 9, 6), seed=20)
    eng = _engine(model)
    sup = EngineSupervisor(eng)
    assert sup.poison_stats() == {"window_s": 60.0,
                                  "isolated_in_window": 0,
                                  "distinct_sources": 0}
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": f"p{i}"} for i in range(3)]))
    # two isolations from tenant "mallory", one untenanted, one "acme":
    # 3 distinct sources total (mallory, -, acme) over 4 isolations
    plan = [("mallory", "p0"), ("mallory", "p1"), (None, "p2")]
    for i, (tenant, rid) in enumerate(plan):
        eng.add_request(prompts[i], max_new_tokens=4, temperature=0.0,
                        request_id=rid, tenant=tenant)
        _run(sup)
        stats = sup.poison_stats()
        assert stats["isolated_in_window"] == i + 1
    assert sup.poison_stats()["distinct_sources"] == 2   # mallory + "-"
    faults.clear()
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "p3"}]))
    eng.add_request(prompts[3], max_new_tokens=4, temperature=0.0,
                    request_id="p3", tenant="acme")
    _run(sup)
    stats = sup.poison_stats()
    assert stats == {"window_s": 60.0, "isolated_in_window": 4,
                     "distinct_sources": 3}
    # the gauges track the stats (refreshed by poison_stats itself)
    assert eng.metrics.gauges["poison_isolated_in_window"] == 4
    assert eng.metrics.gauges["poison_distinct_sources"] == 3
    assert _idle(eng)


def test_poison_window_slides(model):
    """Events age out of the window: with a tiny window, earlier
    isolations stop counting and the gauges decay on the next read."""
    prompts = _prompts((5, 7), seed=21)
    eng = _engine(model)
    sup = EngineSupervisor(eng, poison_window_s=0.2)
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": f"p{i}"} for i in range(2)]))
    eng.add_request(prompts[0], max_new_tokens=4, temperature=0.0,
                    request_id="p0", tenant="a")
    _run(sup)
    assert sup.poison_stats()["isolated_in_window"] == 1
    time.sleep(0.25)
    stats = sup.poison_stats()
    assert stats["isolated_in_window"] == 0
    assert stats["distinct_sources"] == 0
    assert eng.metrics.gauges["poison_distinct_sources"] == 0
    eng.add_request(prompts[1], max_new_tokens=4, temperature=0.0,
                    request_id="p1", tenant="b")
    _run(sup)
    stats = sup.poison_stats()
    assert stats == {"window_s": 0.2, "isolated_in_window": 1,
                     "distinct_sources": 1}
    assert _idle(eng)
