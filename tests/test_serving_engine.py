"""paddle_tpu.serving: continuous-batching engine over the paged KV cache.

Acceptance criteria from the serving issues: paged-cache generation matches
sequential `GPT.generate` greedy outputs token-for-token while serving
overlapping requests of different prompt lengths; requests admitted
mid-decode join the running batch; preemption under a tiny pool frees and
recomputes correctly; and the whole workload — any prompt lengths, chunked
prefill mixed with decode — compiles exactly TWO programs, watched by the
engine's `jit_traces` counter, which increments inside the traced step body
(trace time only). Chunked-prefill edge cases live in
test_serving_chunked.py; Pallas-kernel/fallback parity in
test_paged_attention_kernel.py.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import BlockPool, LLMEngine, faults
from paddle_tpu.serving.scheduler import Request, Scheduler


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


def test_paged_matches_generate_greedy_overlapping(model):
    """>= 3 overlapping requests with different prompt lengths produce
    greedy outputs identical to sequential GPT.generate, with at most one
    compile per (prefill bucket, decode) shape."""
    prompts = _prompts((5, 9, 13))
    engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
    outs = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 6)
    # all three prompts share the chunk-width bucket + the decode bucket
    # (the one-place program-count contract: engine.expected_program_count)
    assert engine.expected_program_count() == 2
    assert engine.metrics.counters["jit_traces"] == 2
    assert engine.pool.num_free == engine.pool.num_blocks - 1  # all freed


def test_mixed_lengths_compile_two_programs(model):
    """Chunked prefill retired the per-bucket programs: prompts of ANY
    length share one (max_batch, prefill_chunk) instance of the unified
    ragged step plus its (max_batch, 1) decode-width instance —
    re-serving different lengths adds zero traces."""
    engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
    prompts = _prompts((4, 20), seed=1)
    outs = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 4)
    assert (engine.metrics.counters["jit_traces"]
            == engine.expected_program_count() == 2)
    engine.generate(_prompts((7, 30, 44), seed=2), max_new_tokens=4,
                    temperature=0.0)
    assert engine.metrics.counters["jit_traces"] == 2  # no recompiles


def test_width_bucket_collision_dedups_programs(model):
    """The program table is keyed by (batch, width) only — when the spec
    width coincides with the chunk width, the old per-kind model's third
    program simply does not exist: FEWER compiled programs, same
    tokens."""
    prompts = _prompts((5, 9, 13), seed=6)
    base = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
    want = base.generate(prompts, max_new_tokens=8, temperature=0.0)
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                    prefill_chunk=4, spec_decoding=True, num_spec_tokens=3)
    assert eng.width_buckets == [1, 4]         # 1 + num_spec == chunk
    assert eng.expected_program_count() == 2   # was 3 kinds pre-unification
    got = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    assert got == want
    assert eng.metrics.counters["jit_traces"] <= 2


def test_width_buckets_knob(model):
    """`width_buckets` adds intermediate ragged widths: a short prefill
    rides the smallest covering bucket instead of full chunk width, tokens
    unchanged."""
    prompts = _prompts((5, 30), seed=8)
    base = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    want = base.generate(prompts, max_new_tokens=4, temperature=0.0)
    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                    width_buckets=[8])
    assert eng.width_buckets == [1, 8, 64]
    assert eng.expected_program_count() == 3
    (o1,) = eng.generate([prompts[0]], max_new_tokens=4, temperature=0.0)
    assert o1 == want[0]
    # the 5-token prefill fit the w8 bucket — chunk width never compiled
    assert set(eng._step_fns) == {(2, 1), (2, 8)}
    (o2,) = eng.generate([prompts[1]], max_new_tokens=4, temperature=0.0)
    assert o2 == want[1]
    assert set(eng._step_fns) == {(2, 1), (2, 8), (2, 64)}
    with pytest.raises(ValueError, match="width_buckets"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                  width_buckets=[0])


def _options(engine):
    """What the engine's options came to, read off its attributes."""
    return dict(
        tp=1 if engine._smesh is None else engine._smesh.tp_degree,
        kv_dtype=engine.kv_dtype,
        quant_collectives=engine.quant_collectives,
        prefix_cache=engine.prefix_cache,
        spec_decoding=engine.spec_decoding,
        width_buckets=engine.width_buckets,
        trace_capacity=(None if engine.tracer is None
                        else engine.tracer.capacity),
        request_log=engine.request_log,
        slo=engine.slo is not None,
        postmortem_keep=(None if engine.recorder is None
                         else engine.recorder.keep),
        tier=engine.tier is not None,
        fault_plan=faults._PLAN is not None,
    )


_DEFAULTS = dict(tp=1, kv_dtype=None, quant_collectives=frozenset(),
                 prefix_cache=True, spec_decoding=False, width_buckets=[1, 64],
                 trace_capacity=None, request_log=False, slo=False,
                 postmortem_keep=None, tier=False, fault_plan=False)


# (variable, the value that once turned its option on, the arguments it
# acted beside, what those arguments change of the defaults)
_ENV_CASES = [
    ("PADDLE_TPU_TP", "2", {}, {}),
    ("PADDLE_TPU_KV_DTYPE", "int8", {}, {}),
    ("PADDLE_TPU_QUANT_ALLREDUCE", "1", {"mesh": 2}, {"tp": 2}),
    ("PADDLE_TPU_PREFIX_CACHE", "0", {}, {}),
    ("PADDLE_TPU_SPEC_DECODE", "1", {}, {}),
    ("PADDLE_TPU_WIDTH_BUCKETS", "8,32", {}, {}),
    ("PADDLE_TPU_TRACE", "1", {}, {}),
    ("PADDLE_TPU_TRACE_BUF", "32", {"trace": 1.0},
     {"trace_capacity": 65536}),
    ("PADDLE_TPU_REQUEST_LOG", "1", {}, {}),
    ("PADDLE_TPU_POSTMORTEM_DIR", "{tmp}", {}, {}),
    ("PADDLE_TPU_POSTMORTEM_KEEP", "3", {"postmortem_dir": "{tmp}"},
     {"postmortem_keep": 16, "slo": True}),
    ("PADDLE_TPU_SLO", "1", {}, {}),
    ("PADDLE_TPU_HOST_KV_BLOCKS", "8", {}, {}),
    ("PADDLE_TPU_FAULTS", '[{"point": "slow_step_ms", "ms": 1}]', {}, {}),
]


@pytest.mark.parametrize("var, value, kwargs, differs",
                         [pytest.param(*c, id=c[0]) for c in _ENV_CASES])
def test_environment_sets_no_engine_option(model, monkeypatch, tmp_path,
                                           var, value, kwargs, differs):
    """The engine is what its arguments say: a variable that once set an
    option (or armed a fault plan) at construction sets nothing. The
    cases that pass an argument are the variables that acted only beside
    it, and they expect that argument's own defaults."""
    def tmp(v):
        return str(tmp_path) if v == "{tmp}" else v

    monkeypatch.setenv(var, tmp(value))
    try:
        engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                           **{k: tmp(v) for k, v in kwargs.items()})
        assert _options(engine) == {**_DEFAULTS, **differs}
    finally:
        faults.clear()


def test_one_host_sync_per_step(model):
    """THE host-sync contract: every step — mixed, decode, spec verify —
    reads back exactly ONE packed device array, so the `host_syncs`
    counter equals the step count after any wave."""
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                    prefill_chunk=8, spec_decoding=True, num_spec_tokens=3)
    eng.generate(_prompts((5, 21, 9), seed=9) + [[7, 3] * 8],
                 max_new_tokens=8, temperature=0.0)
    c = eng.metrics.counters
    steps = (c.get("mixed_steps", 0) + c.get("decode_steps", 0)
             + c.get("verify_steps", 0))
    assert steps > 0
    assert c["host_syncs"] == steps


def test_long_prompt_prefills_in_chunks(model):
    """A prompt longer than prefill_chunk streams into the arena a chunk at
    a time — several mixed steps before the first token — and still matches
    the sequential reference exactly (chunk boundaries change no math)."""
    (p,) = _prompts((29,), seed=7)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       prefill_chunk=8)
    (out,) = engine.generate([p], max_new_tokens=5, temperature=0.0)
    assert out == _reference(model, p, 5)
    # 29 tokens at chunk 8 -> 4 mixed steps (the last emits token 1)
    assert engine.metrics.counters["mixed_steps"] == 4
    assert engine.metrics.counters["jit_traces"] == 2


def test_staggered_add_request_mid_decode(model):
    """A request added while another is mid-decode joins the running batch
    (continuous batching) and both finish with exact greedy outputs."""
    p1, p2 = _prompts((6, 11), seed=3)
    engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
    r1 = engine.add_request(p1, max_new_tokens=8, temperature=0.0)
    # run prefill + a few decode steps for r1 alone
    for _ in range(4):
        engine.step()
    assert len(engine.get_request(r1).output_ids) == 4
    r2 = engine.add_request(p2, max_new_tokens=8, temperature=0.0)
    saw_joint_decode = False
    while engine.has_unfinished():
        engine.step()
        if engine.metrics.gauges.get("num_running", 0) >= 2:
            saw_joint_decode = True
    assert saw_joint_decode  # r2 decoded alongside r1, not after it
    assert engine.get_request(r1).output_ids == _reference(model, p1, 8)
    assert engine.get_request(r2).output_ids == _reference(model, p2, 8)


def test_preemption_frees_and_recomputes(model):
    """A pool too small for three full sequences preempts by recompute:
    blocks are freed, the victim re-prefills prompt+generated, and greedy
    outputs still match the sequential reference exactly."""
    prompts = _prompts((6, 7, 9), seed=1)
    engine = LLMEngine(model, block_size=4, num_blocks=10, max_batch=4,
                       max_seq_len=64)
    outs = engine.generate(prompts, max_new_tokens=10, temperature=0.0)
    assert engine.metrics.counters["preemptions"] >= 1
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 10)
    assert engine.pool.num_free == engine.pool.num_blocks - 1


def test_stream_yields_tokens_incrementally(model):
    (p,) = _prompts((8,), seed=4)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    toks = []
    for out in engine.stream(p, max_new_tokens=5, temperature=0.0):
        toks.append(out.token)
        last_finished = out.finished
    assert toks == _reference(model, p, 5)
    assert last_finished


def test_eos_and_temperature_sampling(model):
    (p,) = _prompts((6,), seed=5)
    ref = _reference(model, p, 8)
    eos = ref[2]
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    (out,) = engine.generate([p], max_new_tokens=8, temperature=0.0,
                             eos_token_id=eos)
    # stops right after the FIRST occurrence of eos (tiny models repeat)
    assert out == ref[: ref.index(eos) + 1]
    # sampled path: legal tokens, full length, engine survives temp > 0
    (sampled,) = engine.generate([p], max_new_tokens=8, temperature=0.8)
    assert len(sampled) == 8
    assert all(0 <= t < 128 for t in sampled)


def test_request_validation(model):
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.add_request(list(range(60)), max_new_tokens=10)
    with pytest.raises(ValueError, match="empty"):
        engine.add_request([], max_new_tokens=4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.add_request([1, 2], max_new_tokens=0)
    # a request whose worst-case KV need exceeds the whole pool is rejected
    # at ADMISSION — otherwise it becomes the oldest running sequence and
    # the scheduler's no-livelock error kills the whole serve mid-flight
    small = LLMEngine(model, block_size=4, num_blocks=4, max_batch=2,
                      max_seq_len=64)
    with pytest.raises(ValueError, match="KV blocks"):
        small.add_request(list(range(1, 20)), max_new_tokens=4)
    small.add_request([1, 2, 3], max_new_tokens=4)  # fits: 2 of 3 blocks
    with pytest.raises(ValueError, match="token_budget"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                  token_budget=0)
    # chunking removed the bucketed engine's token-budget admission limit:
    # a prompt (or post-preempt recompute) larger than the budget streams
    # through in chunks instead of being rejected
    tight = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                      token_budget=8)
    p = _prompts((20,), seed=11)[0]
    (out,) = tight.generate([p], max_new_tokens=6, temperature=0.0)
    assert out == _reference(model, p, 6)
    assert tight.metrics.counters["mixed_steps"] >= 3  # 20 tokens / chunk 8


def test_generate_and_stream_release_requests(model):
    """generate/stream evict finished requests from the engine's registry —
    a long-running engine must not retain every prompt forever."""
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    engine.generate(_prompts((5, 9), seed=8), max_new_tokens=3)
    for _ in engine.stream(_prompts((6,), seed=9)[0], max_new_tokens=3):
        pass
    assert engine._requests == {}
    # manually-driven requests stay until released; unfinished can't release
    rid = engine.add_request(_prompts((5,), seed=10)[0], max_new_tokens=4)
    with pytest.raises(ValueError, match="release"):
        engine.release(rid)
    while engine.has_unfinished():
        engine.step()
    engine.release(rid)
    assert engine._requests == {}


def test_metrics_schedule_view_and_snapshot(model):
    """Metrics export in the shape xplane.print_schedule_analysis consumes
    and as a flat JSON snapshot."""
    import io
    import json

    from paddle_tpu.profiler import xplane

    (p,) = _prompts((6,), seed=6)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    engine.generate([p], max_new_tokens=4, temperature=0.0)
    snap = engine.metrics.snapshot()
    json.dumps(snap)  # JSON-able end to end
    assert snap["counters"]["generated_tokens"] == 4
    assert "decode_step" in snap["latency"]
    assert "ttft" in snap["latency"]  # time-to-first-token
    assert snap["latency"]["ttft"]["p95_ms"] >= snap["latency"]["ttft"]["p50_ms"]
    view = engine.metrics.schedule_view()
    st = view["serving-engine"]
    assert st["span_ms"] > 0 and 0 < st["utilization"] <= 1.0
    assert st["n_ops"] == snap["counters"]["mixed_steps"] + snap[
        "counters"]["decode_steps"]
    buf = io.StringIO()
    xplane.print_schedule_analysis(view, file=buf)
    assert "util" in buf.getvalue()


def test_block_pool_alloc_free_copy():
    import jax.numpy as jnp

    pool = BlockPool(num_blocks=6, num_layers=2, block_size=4, num_heads=2,
                     head_dim=8)
    # head-major arena: [layers, heads, blocks, block_size, head_dim]
    assert pool.k.shape == (2, 2, 6, 4, 8)
    assert pool.num_free == 5  # block 0 reserved as null
    a = pool.allocate(3)
    assert a is not None and 0 not in a
    assert pool.allocate(3) is None  # only 2 left
    pool.k = pool.k.at[:, :, a[0]].set(1.0)
    b = pool.allocate(1)
    pool.copy_blocks([a[0]], [b[0]])
    assert float(jnp.sum(pool.k[:, :, b[0]])) == float(
        jnp.sum(pool.k[:, :, a[0]]))
    pool.free(a + b)
    assert pool.num_free == 5
    with pytest.raises(ValueError, match="null"):
        pool.free([0])


@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("kind", ["width1", "chunk", "int8-chunk"])
def test_append_writes_what_the_windowed_scatter_wrote(kind, tp):
    """`block_pool._append` (the head an index of the scatter) against the
    expression it replaced (the head in the scatter's window), written out
    here: the same values at the same addresses. Width 1 with an idle lane,
    and a chunk that crosses a block boundary beside a shorter, padded row;
    the int8 case feeds the quantized token write's integers. Under tp=2
    the helper runs per shard on a 2-device mesh. The null block is
    scratch (padded tokens collide there, in no stated order), so it is
    compared nowhere."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import build_serving_mesh
    from paddle_tpu.serving.block_pool import _append

    pool = BlockPool(num_blocks=8, num_layers=3, block_size=4, num_heads=4,
                     head_dim=8)
    if kind == "width1":
        # (blocks, first position, live tokens) a row; a lane without a
        # request feeds nothing
        rows, width = [([3], 2, 1), ([5, 1], 7, 1), ([], 0, 0)], 1
    else:
        rows, width = [([2, 6, 4], 2, 6), ([7], 0, 3)], 6
    slots, offs = map(np.stack, zip(*(
        pool.positions_to_slots(blocks or [0], start, count, width)
        for blocks, start, count in rows)))
    assert (slots == 0).any() and (slots != 0).any()
    dtype = jnp.int8 if kind.startswith("int8") else jnp.float32
    rs = np.random.RandomState(0)
    draw = lambda shape: jnp.asarray(  # noqa: E731
        rs.randint(-127, 128, shape), dtype)
    arena = draw(pool.k.shape)
    new = draw((len(rows), width, 4, 8))
    layer = 1
    want = arena.at[layer, :, slots, offs].set(new)

    mesh = None
    if tp > 1:
        smesh = build_serving_mesh(tp)
        mesh = smesh.mesh
        arena = jax.device_put(arena, smesh.arena_sharding())
    got, = jax.jit(_append, static_argnums=(1, 5))(
        (arena,), layer, (new,), jnp.asarray(slots), jnp.asarray(offs), mesh)
    assert got.dtype == want.dtype
    assert np.array_equal(np.asarray(got)[:, :, 1:],
                          np.asarray(want)[:, :, 1:])
    # the other layers whole, their null blocks too
    assert np.array_equal(np.asarray(got)[[0, 2]], np.asarray(arena)[[0, 2]])


def test_scheduler_fcfs_mixed_rows_and_token_budget():
    """One mixed plan per step: FCFS lane admission, decode rows always
    ride, prefill chunks split under the per-step token budget."""
    pool = BlockPool(num_blocks=64, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)
    sched = Scheduler(pool, max_batch=2, token_budget=6, prefill_chunk=6)
    r1 = Request([1] * 10, max_new_tokens=4)
    r2 = Request([1] * 4, max_new_tokens=4)
    r3 = Request([1] * 4, max_new_tokens=4)
    for r in (r1, r2, r3):
        sched.add(r)
    # max_batch=2 lanes: r1 gets a full 6-token chunk, r2 (FCFS next) gets
    # nothing this step (budget spent); r3 waits for a lane
    rows = sched.schedule()
    assert [(w.req, w.start, w.count, w.emit) for w in rows] == [
        (r1, 0, 6, False)
    ]
    assert r2.state == "running" and r3.state == "waiting"
    r1.num_cached += 6
    # next step: r1's last 4 prompt tokens (emits). The budget has 2 left,
    # but a budget of one chunk is a chunk group of ONE row (chunk_rows =
    # token_budget // prefill_chunk) and r1 has it: r2 waits a step more
    assert sched.chunk_rows == 1
    rows = sched.schedule()
    assert [(w.req, w.count, w.emit) for w in rows] == [(r1, 4, True)]
    for w in rows:
        w.req.num_cached += w.count
    r1.output_ids.append(7)  # r1's first token emitted -> decode row next
    # mixed step: r1 decodes (never gated) while r2 prefills in the one row
    rows = sched.schedule()
    assert [(w.req, w.count, w.emit) for w in rows] == [
        (r1, 1, True), (r2, 4, True)
    ]
    for w in rows:
        w.req.num_cached += w.count
    sched.finish(r1)
    sched.finish(r2)
    # freed lanes: r3 admitted FCFS, prompt fits one chunk
    rows = sched.schedule()
    assert [(w.req, w.count, w.emit) for w in rows] == [(r3, 4, True)]


def test_scheduler_admission_exactly_at_token_budget():
    """Chunk packing never overshoots the budget, and plans no more chunk
    rows than whole chunks fit it (`chunk_rows`, the step program's chunk
    group): the third prefill waits a step though 2 tokens of budget are
    left, where a step of one rectangle gave it those 2."""
    pool = BlockPool(num_blocks=64, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)
    sched = Scheduler(pool, max_batch=4, token_budget=12, prefill_chunk=5)
    reqs = [Request([1] * n, max_new_tokens=2) for n in (5, 5, 9, 8)]
    for r in reqs:
        sched.add(r)
    assert sched.chunk_rows == 2
    rows = sched.schedule()
    assert [(w.req, w.count) for w in rows] == [(reqs[0], 5), (reqs[1], 5)]
    for w in rows:
        w.req.num_cached += w.count
        if w.emit:
            w.req.output_ids.append(3)
    # next step: the two finished-prefill rows decode (not budget-gated)
    # while the mid-prompt rows take chunk-capped budget shares
    rows = sched.schedule()
    assert [(w.req, w.count, w.emit) for w in rows] == [
        (reqs[0], 1, True), (reqs[1], 1, True),
        (reqs[2], 5, False), (reqs[3], 5, False),
    ]


def test_scheduler_pool_too_small_fails_loudly():
    """The oldest sequence failing to grow with no younger victims is a
    config error, not a livelock."""
    pool = BlockPool(num_blocks=3, num_layers=1, block_size=4, num_heads=1,
                     head_dim=4)
    sched = Scheduler(pool, max_batch=2, token_budget=64, prefill_chunk=64)
    sched.add(Request([1] * 12, max_new_tokens=1))  # needs 3 blocks, pool has 2
    with pytest.raises(ValueError, match="KV blocks"):
        sched.schedule()


def test_recompile_sentinel_zero_retraces_steady_state(model):
    """The program-count contract, locked from the sentinel's side via
    the one shared helper: the compiled table never exceeds
    `expected_program_count()` (one program per ragged width bucket),
    and after a warmup wave an arbitrary steady-state serve (varied
    prompt lengths, sampling knobs, cache hits) runs with ZERO further
    XLA traces — `jit_traces` stays equal to the compiled-program count,
    the `jit_retraces` gauge stays 0, and the sentinel never warns."""
    import warnings

    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       spec_decoding=True, num_spec_tokens=3)
    # the default spec engine buckets: decode, 1 + num_spec, chunk
    assert engine.expected_program_count() == 3
    with warnings.catch_warnings():
        warnings.simplefilter("error")       # any sentinel warning fails
        # warmup: a repetitive prompt drives mixed, decode, AND (via the
        # pure-decode width gate) spec-bucket steps
        engine.generate([[7] * 24], max_new_tokens=12)
        assert len(engine._step_fns) <= engine.expected_program_count()
        warm = engine.metrics.counters["jit_traces"]
        assert warm == len(engine._step_fns)  # one trace per program, ever
        rs = np.random.RandomState(1)
        for round_ in range(3):
            prompts = [rs.randint(0, 128, (n,)).tolist()
                       for n in (5, 17, 9)]
            engine.generate(prompts[:2], max_new_tokens=8)
            engine.generate([prompts[2]], max_new_tokens=4,
                            temperature=0.8, top_k=5)
    assert len(engine._step_fns) <= engine.expected_program_count()
    assert (engine.metrics.counters["jit_traces"]
            == len(engine._step_fns))        # 0 retraces, ever
    assert engine.metrics.gauges["jit_retraces"] == 0


def test_recompile_sentinel_warns_on_surplus_trace(model):
    """A trace beyond one-per-program is exactly what the sentinel must
    catch: simulate one (the counter is the engine's own trace-time
    signal) and the next step warns once, sets the gauge, and never
    spams."""
    import warnings

    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    engine.generate(_prompts((9,)), max_new_tokens=2)
    engine.metrics.inc("jit_traces")         # a phantom re-trace
    with pytest.warns(RuntimeWarning, match="recompile sentinel"):
        engine.generate(_prompts((7,), seed=1), max_new_tokens=2)
    assert engine.metrics.gauges["jit_retraces"] == 1
    with warnings.catch_warnings():          # warns once, never spams
        warnings.simplefilter("error")
        engine.generate(_prompts((5,), seed=2), max_new_tokens=2)
