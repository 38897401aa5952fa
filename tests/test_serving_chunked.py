"""Chunked-prefill scheduler/engine edge cases.

Satellites from the ragged-paged-attention issue: admission exactly at the
token budget lives in test_serving_engine.py; here: preemption of a
half-prefilled / half-decoded request (recompute must replay already-emitted
chunks WITHOUT re-emitting their tokens), zero-waiting-queue mixed steps,
and chunk accounting across replays.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import BlockPool, LLMEngine
from paddle_tpu.serving.scheduler import Request, Scheduler

from _serving_common import (WAVE, check_wave_plans, rectangle, serve,
                             wave_of)


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


def _reference(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n, temperature=0.0)
    return out.numpy()[0, len(prompt):].tolist()


def test_replay_of_preempted_request_does_not_reemit():
    """A preempted request with emitted tokens replays prompt+outputs in
    chunks: every replay row is emit=False until the chunk that reaches the
    last pending position — which samples the NEXT token, not a repeat."""
    pool = BlockPool(num_blocks=64, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)
    sched = Scheduler(pool, max_batch=2, token_budget=4, prefill_chunk=4)
    req = Request([1] * 9, max_new_tokens=8)
    sched.add(req)
    # prefill 9 tokens in chunks of 4: emit only on the last
    emits = []
    for _ in range(3):
        (row,) = sched.schedule()
        emits.append(row.emit)
        req.num_cached += row.count
    assert emits == [False, False, True]
    req.output_ids.extend([5, 6])  # two tokens emitted (engine would do it)
    req.num_cached = req.num_tokens - 1  # decode steady state
    sched._preempt(req)
    assert req.num_cached == 0 and not req.blocks
    # replay: 9 + 2 = 11 pending tokens -> chunks 4, 4, 3; only the chunk
    # reaching position 10 (the last emitted token, fed back in) emits — and
    # what it samples is output token #3, never a re-emission of 5 or 6
    emits, counts = [], []
    while req.num_pending > 1:
        (row,) = sched.schedule()
        assert row.start == req.num_cached
        emits.append(row.emit)
        counts.append(row.count)
        req.num_cached += row.count
    assert counts == [4, 4, 3]
    assert emits == [False, False, True]
    assert req.preemptions == 1 and req.output_ids == [5, 6]


def test_engine_preempts_mid_serve_token_streams_exact(model):
    """Step-by-step streams under preemption pressure: every request's
    emitted token sequence equals its final output_ids equals the
    sequential reference — replays never duplicate or drop a token."""
    prompts = _prompts((6, 7, 9), seed=1)
    engine = LLMEngine(model, block_size=4, num_blocks=10, max_batch=4,
                       max_seq_len=64, prefill_chunk=4)
    rids = [engine.add_request(p, max_new_tokens=10, temperature=0.0)
            for p in prompts]
    streams = {rid: [] for rid in rids}
    while engine.has_unfinished():
        for out in engine.step():
            streams[out.request_id].append(out.token)
    assert engine.metrics.counters["preemptions"] >= 1
    for rid, p in zip(rids, prompts):
        ref = _reference(model, p, 10)
        assert streams[rid] == ref
        assert engine.get_request(rid).output_ids == ref
    assert engine.pool.num_free == engine.pool.num_blocks - 1


def test_zero_waiting_queue_mixed_steps(model):
    """With the waiting queue empty, a long prompt keeps chunking WHILE the
    other lane decodes — mixed steps with num_waiting == 0, and the decode
    lane emits a token in every one of them."""
    p_short, p_long = _prompts((4, 40), seed=2)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       prefill_chunk=8)
    r1 = engine.add_request(p_short, max_new_tokens=12, temperature=0.0)
    engine.step()  # admit + prefill r1 (emits its first token)
    r2 = engine.add_request(p_long, max_new_tokens=4, temperature=0.0)
    mixed_with_empty_queue = 0
    decode_progress = []
    while engine.get_request(r2).num_pending > 1 or not engine.get_request(
            r2).output_ids:
        n1 = len(engine.get_request(r1).output_ids)
        engine.step()
        if (engine.metrics.gauges["num_waiting"] == 0
                and len(engine.get_request(r1).output_ids) == n1 + 1):
            mixed_with_empty_queue += 1
            decode_progress.append(True)
    # 40-token prompt at chunk 8 -> 5 chunk steps, all riding with r1's
    # decode rows after admission emptied the queue
    assert mixed_with_empty_queue >= 4
    while engine.has_unfinished():
        engine.step()
    assert engine.get_request(r1).output_ids == _reference(model, p_short, 12)
    assert engine.get_request(r2).output_ids == _reference(model, p_long, 4)


def test_preemption_priority_is_arrival_order_not_list_position():
    """A preempted-and-readmitted request sits at the END of the running
    list but keeps its arrival age: an arrival-younger sequence must defer
    rather than victimize it, while the arrival-oldest may still reclaim
    from the true youngest."""
    pool = BlockPool(num_blocks=5, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)  # 4 usable
    sched = Scheduler(pool, max_batch=3, token_budget=12, prefill_chunk=4)
    r1, r2, r3 = (Request([1] * 4, max_new_tokens=8) for _ in range(3))
    for r in (r1, r2, r3):
        sched.add(r)
    rows = sched.schedule()  # one block each, 1 free
    assert [w.req for w in rows] == [r1, r2, r3]
    for w in rows:
        w.req.num_cached += w.count
    # simulate r2 having been preempted + re-admitted: list-youngest now,
    # but still arrival-older than r3
    sched.running.remove(r2)
    sched.running.append(r2)
    # r3 wants 3 blocks: takes the free one, then the pool is dry — r2 (the
    # list-tail) is NOT fair game, and r3 has no arrival-younger victim
    assert sched._grow(r3, 3) is False
    assert r2.blocks and r2.preemptions == 0
    # the arrival-oldest r1 reclaims from the arrival-youngest holder (r3)
    assert sched._grow(r1, 3) is True
    assert r3.preemptions == 1 and r3.state == "waiting"
    assert r2.preemptions == 0


def test_scheduler_defers_younger_prefill_when_pool_dry():
    """FCFS block priority: when the pool is dry, a younger mid-prefill row
    defers (no self-thrash) while an older sequence keeps its blocks and
    advances."""
    pool = BlockPool(num_blocks=5, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)  # 4 usable blocks
    sched = Scheduler(pool, max_batch=2, token_budget=32, prefill_chunk=8)
    r1 = Request([1] * 12, max_new_tokens=8)   # 3 blocks at 12 tokens
    r2 = Request([1] * 8, max_new_tokens=8)
    sched.add(r1)
    sched.add(r2)
    rows = sched.schedule()  # r1 chunk 8 (2 blocks) + r2 chunk 8 (2 blocks)
    assert [(w.req, w.count) for w in rows] == [(r1, 8), (r2, 8)]
    for w in rows:
        w.req.num_cached += w.count
    # r1's last chunk needs a 3rd block; pool is dry -> r2 (younger, holds
    # blocks) is preempted, r1 proceeds, r2 replays later
    rows = sched.schedule()
    assert [(w.req, w.count, w.emit) for w in rows] == [(r1, 4, True)]
    assert r2.state == "waiting" and r2.num_cached == 0
    assert r2.preemptions == 1


# -- the shape of a step that carries a chunk (PR 37) ------------------------
# A step's tokens lie on one flat axis: the decode group [max_batch, 1 + K]
# and, where a row feeds more, the chunk group [C, width] behind it
# (`LLMEngine.step_groups`). The reference is the same engine steered back to
# the ONE rectangle [max_batch, width] a step used to be
# (`_serving_common.rectangle`): same plans, same tokens, same arena.

# a budget of two chunks: two chunk rows a step (the default is one)
GROUPED = dict(block_size=8, max_batch=4, max_seq_len=64, prefill_chunk=8,
               token_budget=16, seed=11)


def _same_arena(a, b, atol=1e-5):
    """Every block but the null block (which padding writes) holds the
    same keys and values in both engines' arenas."""
    for name in ("k", "v"):
        x, y = (np.asarray(getattr(e.pool, name), np.float32)[:, :, 1:]
                for e in (a, b))
        assert np.abs(x - y).max() <= atol, name


@pytest.mark.parametrize("knobs", [
    dict(temperature=0.0), dict(temperature=0.8, top_k=20, top_p=0.95)],
    ids=["greedy", "seeded"])
def test_a_grouped_step_is_the_rectangles_step(model, knobs):
    """Decode rows beside one and beside two chunk rows, a prompt of three
    chunks, last chunks of one token: the tokens, greedy and sampled, and
    the arena are what the one rectangle gave."""
    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=4))
    grouped = LLMEngine(model, **GROUPED)
    parent = rectangle(LLMEngine(model, **GROUPED))
    assert grouped.step_groups(1) == ((4, 1),)
    assert grouped.step_groups(8) == ((4, 1), (2, 8))
    assert parent.step_groups(8) == ((4, 8),)
    got, plans = serve(grouped, wave, **knobs)
    want, parents_plans = serve(parent, wave, **knobs)
    check_wave_plans(plans)
    assert plans == parents_plans
    assert got == want
    if not knobs["temperature"]:
        assert got == [_reference(model, p, n) for _, p, n in wave]
    _same_arena(grouped, parent)
    for engine in (grouped, parent):
        m = engine.metrics
        assert m.counters["jit_traces"] == engine.expected_program_count() == 2
        assert m.gauges["jit_retraces"] == 0
        assert engine.pool.num_free == engine.pool.num_blocks - 1


def test_a_third_chunk_row_waits_a_step_and_decode_rows_never_do(model):
    """A budget of two chunks is two chunk rows a step: of three prompts
    beside decoding requests the third waits a step; the decode rows ride
    every step. The default budget is ONE chunk, one chunk row."""
    default = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                        prefill_chunk=8)
    assert default.scheduler.token_budget == 8
    assert default.scheduler.chunk_rows == 1
    assert default.step_groups(8) == ((4, 1), (1, 8))
    engine = LLMEngine(model, **GROUPED)
    assert engine.scheduler.token_budget == 16
    assert engine.scheduler.chunk_rows == 2
    prompts = _prompts((6, 7, 12, 11, 10), seed=5)
    arrivals = [(0, prompts[0], 12), (0, prompts[1], 12)] + [
        (2, p, 3) for p in prompts[2:]]
    got, plans = serve(engine, arrivals, temperature=0.0)
    assert got == [_reference(model, p, n) for _, p, n in arrivals]
    rows = [[(rid, count) for rid, _, count, _ in r] for _, r in plans]
    # two lanes are free: the third prompt waits for a LANE, the second
    # chunk row is there for the second prompt
    assert rows[2] == [(0, 1), (1, 1), (2, 8), (3, 8)]
    assert rows[3] == [(0, 1), (1, 1), (2, 4), (3, 3)]
    for step in rows[1:12]:
        assert step[:2] == [(0, 1), (1, 1)]
    assert all(sum(c > 1 for _, c in step) <= 2 for step in rows)
    # max_batch 3 of 4 busy with chunks: the third chunk row waits
    engine = LLMEngine(model, **GROUPED)
    got, plans = serve(engine, [(0, p, 2) for p in prompts[2:]] + [
        (0, prompts[0], 2)], temperature=0.0)
    rows = [[(rid, count) for rid, _, count, _ in r] for _, r in plans]
    assert rows[0] == [(0, 8), (1, 8)]              # 2 and 3 wait
    assert rows[1] == [(0, 4), (1, 3)]
    assert rows[2] == [(0, 1), (1, 1), (2, 8), (3, 6)]


def test_a_budget_of_a_chunk_a_lane_serves_a_burst_in_as_few_steps(model):
    """`token_budget = max_batch x prefill_chunk` is a chunk group of
    `max_batch` rows: a burst prefills in one step, as under the old
    default; a budget of two chunks takes a step for every two prompts,
    the default of one a step a prompt."""
    prompts = _prompts((8, 5, 7, 6), seed=6)
    burst = [(0, p, 4) for p in prompts]
    wide = LLMEngine(model, **{**GROUPED, "token_budget": 4 * 8})
    assert wide.scheduler.chunk_rows == 4
    assert wide.step_groups(8) == ((4, 1), (4, 8))
    got, plans = serve(wide, burst, temperature=0.0)
    assert got == [_reference(model, p, 4) for p in prompts]
    assert [len(r) for _, r in plans] == [4, 4, 4, 4]       # 1 + 3 steps
    assert all(c > 1 for *_, c, _ in plans[0][1])
    narrow = LLMEngine(model, **GROUPED)
    got, plans = serve(narrow, burst, temperature=0.0)
    assert got == [_reference(model, p, 4) for p in prompts]
    assert len(plans) == 5 and [
        sum(c > 1 for *_, c, _ in r) for _, r in plans[:2]] == [2, 2]
    # a budget under a chunk narrows the chunk, and the group is one row
    default = LLMEngine(model, **{k: v for k, v in GROUPED.items()
                                   if k != "token_budget"})
    wants, plans = serve(default, burst, temperature=0.0)
    assert wants == got and len(plans) == 7 and [
        sum(c > 1 for *_, c, _ in r) for _, r in plans[:4]] == [1, 1, 1, 1]
    one = LLMEngine(model, **{**GROUPED, "token_budget": 4})
    assert one.prefill_chunk == 4 and one.scheduler.chunk_rows == 1
    assert one.step_groups(4) == ((4, 1), (1, 4))
    assert serve(one, burst, temperature=0.0)[0] == got


def _lora_engine(model, **kw):
    from paddle_tpu.models import lora as lora_mod

    engine = LLMEngine(model, lora_slots=2, lora_rank=4, **kw)
    engine.load_adapter("a", lora_mod.random_adapter(
        model.cfg, 4, lora_mod.LORA_TARGETS, seed=7, scale=0.5), alpha=8)
    return engine


@pytest.mark.parametrize("case", ["int8", "lora", "draft", "only", "preempt"])
def test_each_feature_rides_a_grouped_step(model, case):
    """The int8 arena's touched lists, a LoRA lane, a drafted row beside a
    chunk row, `step(only=...)` and preemption in the middle of a prefill,
    each once through the wave: the rectangle's tokens."""
    kw, knobs, only, build = dict(GROUPED), dict(temperature=0.0), None, \
        LLMEngine
    prompts = _prompts([n for _, n, _ in WAVE], seed=8)
    if case == "int8":
        kw["kv_dtype"] = "int8"
    elif case == "lora":
        build = _lora_engine
    elif case == "draft":
        kw["spec_decoding"] = True
        # a motif the n-gram drafter continues: B drafts while A prefills
        prompts[1] = [5, 6, 7, 5, 6]
    elif case == "only":
        # steps 3 and 6 hold B and D still (the call after a probe launches
        # two steps, its own and the next one ahead of the read)
        only = {3: (0, 2), 6: (0, 2)}
    elif case == "preempt":
        kw["num_blocks"] = 9        # 8 usable blocks of 8 for 4 lanes
    wave = wave_of(prompts)
    if case == "lora":
        wave[0] += (dict(adapter="a"),)
        wave[2] += (dict(adapter="a"),)
    grouped, parent = build(model, **kw), rectangle(build(model, **kw))
    got, plans = serve(grouped, wave, only=only, **knobs)
    want, parents_plans = serve(parent, wave, only=only, **knobs)
    assert plans == parents_plans
    assert got == want
    if case in ("only", "preempt", "draft"):
        assert got == [_reference(model, p, n) for _, p, n in wave]
    wide = [rows for W, rows in plans if W == 8]
    assert any(len({c > 1 for *_, c, _ in rows}) == 2 for rows in wide)
    if case == "int8":
        assert grouped.pool.quantized
        assert "touched" in grouped._step_layout(8)[0][1]
    elif case == "lora":
        base, _ = serve(LLMEngine(model, **GROUPED), wave_of(prompts),
                        **knobs)
        assert got[1] == base[1] and got[0] != base[0]
    elif case == "draft":
        assert grouped.step_groups(8) == ((4, 5), (2, 8))
        # a drafted row in the decode group of a step that carries a chunk
        assert any(k and any(c > 1 for *_, c, _ in rows)
                   for rows in wide for *_, k in rows)
        assert grouped.metrics.counters["spec_accepted_tokens"] > 0
    elif case == "only":
        assert [sorted({r for r, *_ in plans[n][1]}) for n in (3, 6)] == [
            [0, 2], [0, 2]]
    elif case == "preempt":
        assert grouped.metrics.counters["preemptions"] >= 1
        assert any(start == 0 and rid in {r for _, rows in plans[:n]
                                          for r, *_ in rows}
                   for n, (_, rows) in enumerate(plans)
                   for rid, start, *_ in rows if n)
    assert grouped.pool.num_free == grouped.pool.num_blocks - 1
    assert grouped.metrics.counters["jit_traces"] <= \
        grouped.expected_program_count()


@pytest.mark.parametrize("kw", [dict(), dict(kv_dtype="int8"),
                                dict(spec_decoding=True)],
                         ids=["plain", "int8", "drafting"])
def test_the_packed_input_round_trips_every_groups_sections(model, kw):
    """Every section of every group of every program: what the host writes
    through `_row_arrays`' views, the program's `unpack_step_input` reads,
    bit for bit; the groups' sections tile the one buffer."""
    import functools

    import jax

    from paddle_tpu.serving.engine import unpack_step_input

    engine = LLMEngine(model, **GROUPED, **kw)
    rng = np.random.default_rng(3)
    for W in engine.width_buckets:
        groups = engine.step_groups(W)
        layouts, words = engine._step_layout(W)
        packed, parts = engine._row_arrays(W)
        assert packed.shape == (words,) and len(parts) == len(groups) == \
            (1 if W <= engine.scheduler.decode_width else 2)
        end = 0
        for g, ((n, width), fields, a) in enumerate(zip(groups, layouts,
                                                        parts)):
            assert a["ids"].shape == (n, width)
            assert ("lanes" in a) == (g > 0)
            assert ("touched" in a) == engine.pool.quantized
            for name, (off, shape, dtype) in fields.items():
                assert off == end and a[name].shape == shape
                end += int(np.prod(shape))
                a[name][...] = rng.integers(1, 1 << 20, shape).astype(dtype)
        assert end == words
        for fields, a in zip(layouts, parts):
            got = jax.jit(functools.partial(unpack_step_input,
                                            fields=fields))(packed)
            for name in fields:
                assert got[name].dtype == a[name].dtype
                assert np.array_equal(np.asarray(got[name]), a[name]), name
