"""One step in flight ahead of the host (serving/engine.py `LLMEngine.step`,
`_run_rows`, `_finish`; scheduler.py `Request.unread`, `MustDrain`): a call
plans the next step as if the step in flight had emitted, launches it, and
only then reads the step in flight and emits its tokens. A decode row whose
token the step in flight samples names that step's result row (`src`) and
the program takes the token there.

- the overlapped loop and the synchronous one (the same engine steered to
  read every step before it plans the next, `_sync`) plan the same steps and
  give the same tokens: greedy for every model the engine serves here (GPT,
  the looped decoder, the hybrid with state a lane, the latent-attention
  decoder), and seeded sampling, where the two loops plan the same steps;
- what a step planned ahead may assume: a request whose last token is in
  flight is not planned again; an eos, an abort, a deadline or a non-finite
  row found at the read drops the token the step behind made for it; a plan
  that needs a block the pool does not have drains first;
- where the engine may not launch ahead (drafts, `only=`, a fault plan, a
  host KV tier) it never does: `steps_launched_ahead` stays 0;
- the step span says `ahead`, and `step.ahead_share` reads it.
"""
import functools

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import LLMEngine, faults
from paddle_tpu.serving.faults import FaultPlan

import _dots3_common as dots3
import _looped_common as looped
import _nemotron_common as hybrid
from _serving_common import prompts_of, spy_plans

# (engine options, vocabulary) a model kind
KINDS = {
    "gpt": (dict(block_size=8, max_batch=3, max_seq_len=64,
                 prefill_chunk=8), 128),
    "looped": (dict(block_size=8, max_batch=2, max_seq_len=64,
                    prefill_chunk=16), looped.VOCAB),
    "hybrid": (dict(block_size=4, max_batch=3, max_seq_len=96,
                    prefill_chunk=8), hybrid.VOCAB),
    "dots3": (dict(block_size=4, max_batch=3, max_seq_len=96,
                   prefill_chunk=8), dots3.VOCAB),
}
SEEDED = dict(temperature=0.8, top_k=20, top_p=0.95)


@functools.lru_cache(maxsize=None)
def _model(kind):
    if kind == "gpt":
        paddle.seed(0)
        return GPT(GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                             num_heads=2, max_seq_len=64, attn_impl="xla",
                             dropout=0.0)).eval()
    if kind == "looped":
        return looped.build(2)
    if kind == "hybrid":
        return hybrid.build(experts_held=4, experts_from=4)
    return dots3.build(experts_held=4, experts_from=4)


def _engine(kind="gpt", **kw):
    options, _ = KINDS[kind]
    return LLMEngine(_model(kind), seed=3, **{**options, **kw})


def _sync(engine):
    """`engine`, steered to read every step before it plans the next: the
    loop as it ran before a step could be in flight ahead of the read."""
    engine._may_launch_ahead = lambda: False
    return engine


def _prompts(kind, lengths, seed=0):
    return prompts_of(lengths, KINDS[kind][1], seed)


def _serve(engine, prompts, tokens, each=None, **knobs):
    """Serve `prompts` (`tokens[i]` tokens each, `each[i]` knobs of its own)
    to the end, one `step()` a call. Returns `(streams, plans, calls)`: the
    tokens of each request as the calls returned them, `spy_plans`' list
    with a request named by its place, and each call's StepOutputs."""
    plans = spy_plans(engine)
    rids = [engine.add_request(p, max_new_tokens=n,
                               **{**knobs, **(each or {}).get(i, {})})
            for i, (p, n) in enumerate(zip(prompts, tokens))]
    streams, calls = {r: [] for r in rids}, []
    while engine.has_unfinished():
        calls.append(engine.step())
        for out in calls[-1]:
            streams[out.request_id].append(out.token)
        assert len(calls) < 500, "the serve does not end"
    index = {r: i for i, r in enumerate(rids)}
    return ([streams[r] for r in rids],
            [(W, [(index[r], *row) for r, *row in rows])
             for W, rows in plans], calls)


def _fed(plans, i):
    """Tokens request `i` was fed over the serve."""
    return sum(count for _, rows in plans for r, _, count, _ in rows
               if r == i)


def _idle(engine):
    assert not engine.has_unfinished() and engine._in_flight is None
    assert engine.pool.num_free == engine.pool.num_blocks - 1


@pytest.mark.parametrize("knobs", [{}, SEEDED], ids=["greedy", "seeded"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_overlapped_loop_plans_and_samples_what_the_synchronous_one_does(
        kind, knobs):
    """As many requests as lanes, so that no lane is handed on and the two
    loops plan the same steps: the tokens, greedy and sampled, are the
    synchronous loop's, and a request whose last token is in flight is not
    planned again (each is fed its prompt and every token but its last)."""
    lanes = KINDS[kind][0]["max_batch"]
    prompts = _prompts(kind, (13, 5, 9)[:lanes], seed=2)
    tokens = (6, 4, 7)[:lanes]
    ahead = _engine(kind)
    got, plans, calls = _serve(ahead, prompts, tokens, **knobs)
    sync = _sync(_engine(kind))
    want, sync_plans, _ = _serve(sync, prompts, tokens, **knobs)
    assert got == want and plans == sync_plans
    assert [len(s) for s in got] == list(tokens)
    for i, (p, n) in enumerate(zip(prompts, tokens)):
        assert _fed(plans, i) == len(p) + n - 1
    c = ahead.metrics.counters
    # every step but the first was launched before its predecessor's read,
    # and a call returned one step's tokens
    assert c["steps_launched_ahead"] == len(plans) - 1 > 0
    assert "steps_launched_ahead" not in sync.metrics.counters
    assert c["host_syncs"] == len(plans) == len(calls)
    assert c["jit_traces"] == len(ahead._step_fns)
    assert ahead.metrics.gauges["jit_retraces"] == 0
    _idle(ahead)


def test_an_eos_found_at_the_read_drops_the_token_the_step_behind_made():
    """Request 0 ends at an eos, a token it makes mid-answer: the step
    launched before that token was read fed request 0 once more, and its
    sample is never emitted; the streams are the synchronous loop's."""
    prompts = _prompts("gpt", (13, 5, 9), seed=2)
    tokens = (8, 8, 8)
    plain, _, _ = _serve(_engine(), prompts, tokens)
    k = next(k for k in range(2, 7) if plain[0][k] not in plain[0][:k])
    each = {0: dict(eos_token_id=plain[0][k])}
    ahead = _engine()
    got, plans, _ = _serve(ahead, prompts, tokens, each)
    want, sync_plans, _ = _serve(_sync(_engine()), prompts, tokens, each)
    assert got == want
    assert got[0] == plain[0][:k + 1] and got[1:] == plain[1:]
    assert _fed(plans, 0) == _fed(sync_plans, 0) + 1
    _idle(ahead)


@pytest.mark.parametrize("reason", ["aborted", "timeout"])
def test_an_abort_or_a_deadline_drops_the_row_in_flight(reason):
    """The frontend aborts between two calls (a client gone, or its
    deadline: reason "timeout"). A step planned on the request is in flight
    then: its row is dropped at the read and nothing more is emitted for
    the request; the others' tokens are the synchronous loop's."""
    prompts = _prompts("gpt", (13, 5, 9), seed=2)

    def serve(engine):
        rids = [engine.add_request(p, max_new_tokens=8) for p in prompts]
        streams = {r: [] for r in rids}
        for n in range(500):
            if not engine.has_unfinished():
                break
            if n == 4:
                if engine._in_flight is not None:
                    assert rids[1] in {r.req.request_id
                                       for r in engine._in_flight.rows}
                assert engine.abort(rids[1], reason=reason)
            for out in engine.step():
                streams[out.request_id].append(out.token)
        return [streams[r] for r in rids]

    ahead = _engine(trace=1.0)
    got = serve(ahead)
    assert got == serve(_sync(_engine()))
    assert 0 < len(got[1]) < 8 and [len(s) for s in (got[0], got[2])] == [8, 8]
    ends = [e["args"]["reason"] for e in ahead.tracer.chrome_trace()[
        "traceEvents"] if e["name"] == "request"]
    assert sorted(ends) == sorted(["finished", "finished", reason])
    _idle(ahead)


class _NonFinite:
    """A step's device result whose row `row` reads non-finite."""

    def __init__(self, out, row):
        self.out, self.row = out, row

    def __array__(self, dtype=None, copy=None):
        a = np.array(self.out)
        a[self.row, -1] = 0
        return a


def _poison_at(engine, step, i):
    """Make request `i`'s row of the `step`-th launched step non-finite."""
    run_rows, launched = engine._run_rows, []

    def poisoned(rows, W, step_id=0):
        s = run_rows(rows, W, step_id)
        if len(launched) == step:
            req = sorted({r.req for r in rows},
                         key=lambda q: q.arrival_seq)[i]
            s.out = _NonFinite(s.out, s.result_row[req])
        launched.append(s)
        return s

    engine._run_rows = poisoned


def test_a_nonfinite_row_found_at_the_read_drops_its_row_in_flight():
    """Request 1's row of step 4 reads non-finite: it is aborted at that
    read, while the step behind, launched before it, holds a row for it
    that is dropped; the others' tokens are the synchronous loop's."""
    prompts = _prompts("gpt", (13, 5, 9), seed=2)
    tokens = (8, 8, 8)
    runs = []
    for engine in (_engine(), _sync(_engine())):
        _poison_at(engine, 4, 1)
        runs.append((engine, *_serve(engine, prompts, tokens)))
    (ahead, got, plans, calls), (sync, want, sync_plans, _) = runs
    assert got == want
    assert len(got[1]) < 8 and [len(s) for s in (got[0], got[2])] == [8, 8]
    assert 1 in {r for r, *_ in plans[5][1]}
    assert 1 not in {r for r, *_ in sync_plans[5][1]}
    for engine in (ahead, sync):
        assert engine.metrics.counters["nonfinite_rows"] == 1
    _idle(ahead)


def test_a_plan_that_needs_a_block_the_pool_lacks_drains_first():
    """Eight blocks of 8 for three lanes that grow to eleven: a plan made
    ahead that needs a block the pool does not have is not made (a plan on
    read tokens preempts), the call reads the step in flight and runs its
    own synchronously, and the tokens are the synchronous loop's."""
    prompts = _prompts("gpt", (20, 7, 11), seed=1)
    tokens = (16, 16, 16)
    ahead = _engine(num_blocks=9)
    got, _, calls = _serve(ahead, prompts, tokens)
    want, _, _ = _serve(_sync(_engine(num_blocks=9)), prompts, tokens)
    assert got == want
    assert got == _serve(_sync(_engine()), prompts, tokens)[0]
    c = ahead.metrics.counters
    assert c["preemptions"] >= 1
    assert 0 < c["steps_launched_ahead"] < c["host_syncs"]
    # a call that drained read two steps, its own after the one in flight
    assert c["host_syncs"] > len(calls)
    _idle(ahead)


@pytest.mark.parametrize("case", ["drafts", "only", "fault_plan", "tier"])
def test_where_the_engine_may_not_launch_ahead_it_never_does(case):
    """A drafter proposes from tokens on the host, a bisection probe steps a
    set of requests, a fault plan matches steps the host has read, a host KV
    tier orders its saves against the plan: each engine reads every step
    before it plans the next, and serves the greedy tokens."""
    prompts = _prompts("gpt", (13, 5, 9), seed=2) + [[5, 6, 7] * 4]
    want = _sync(_engine(max_batch=4)).generate(prompts, max_new_tokens=6)
    kw = dict(max_batch=4)
    if case == "drafts":
        kw["spec_decoding"] = True
    elif case == "tier":
        kw["host_kv_blocks"] = 8
    engine = _engine(**kw)
    if case == "fault_plan":
        faults.install(FaultPlan([{"point": "step_raise",
                                   "at_step": 10 ** 6}]))
    try:
        rids = [engine.add_request(p, max_new_tokens=6) for p in prompts]
        while engine.has_unfinished():
            engine.step(only=set(rids) if case == "only" else None)
    finally:
        faults.clear()
    assert [engine.get_request(r).output_ids for r in rids] == want
    c = engine.metrics.counters
    assert "steps_launched_ahead" not in c and c["host_syncs"] > 0
    if case == "drafts":
        assert c["spec_accepted_tokens"] > 0
    _idle(engine)


def test_a_step_span_says_whether_it_was_launched_ahead():
    """`ahead` is 1 on a step launched while its predecessor was unread,
    whose span then starts (its plan) before the predecessor's read; the
    first step of a serve and a step of the synchronous loop read 0.
    `step.ahead_share` is their share, and None on spans without it."""
    from benchmark import spec
    from benchmark.record import Run

    read = spec.load_reader("per_layer", "step.ahead_share").read
    prompts = _prompts("gpt", (13, 5, 9), seed=2)
    shares, steps = [], []
    for engine in (_engine(trace=1.0), _sync(_engine(trace=1.0))):
        engine.generate(prompts, max_new_tokens=6)
        events = engine.tracer.chrome_trace()["traceEvents"]
        spans = {e["args"]["step"]: e for e in events
                 if e["ph"] == "X" and e["name"].startswith("step[")}
        phase = {(e["args"]["step"], e["name"]): e for e in events
                 if e["ph"] == "X" and e["name"] in ("plan", "sync")}
        ahead = [spans[i]["args"]["ahead"] for i in sorted(spans)]
        assert ahead[0] == 0
        assert sum(ahead) == engine.metrics.counters.get(
            "steps_launched_ahead", 0)
        for i in sorted(spans)[1:]:
            assert (phase[i, "plan"]["ts"] < phase[i - 1, "sync"]["ts"]) \
                == bool(ahead[i])
        run = Run({}, {"name": "cell"}, {}, {}, None, {"platform": "cpu"},
                  None, 0.0)
        run.engine_steps = [{"start": 0.0, "end": 1.0,
                             "args": spans[i]["args"]} for i in spans]
        run.t0, run.t1 = 0.0, 1.0
        shares.append(read(run))
        steps.append(len(ahead))
    assert shares[0] == (100.0 * (steps[0] - 1) / steps[0], steps[0])
    assert shares[1] == (0.0, steps[1])
    run.engine_steps = [{"start": 0.0, "end": 1.0, "args": {"rows": 1}}]
    assert read(run) is None
