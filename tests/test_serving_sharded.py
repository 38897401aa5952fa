"""Tensor-parallel serving (serving/sharded.py) on the 8-fake-device CPU
mesh: the acceptance bar is TOKEN parity — a tp-sharded engine serving a
mixed wave (chunked prefill + decode + speculative drafts + prefix-cache
hits) emits greedy output token-for-token identical to the single-chip
engine, still compiles at most one program per ragged width bucket
(`expected_program_count`) with 0 steady-state retraces,
and keeps every host-side invariant (refcounts drain, pool returns to
idle). Always-on: the tp=2 smoke plus unit/capacity/topology-surface
checks; the tp=4/8 sweep, preemption interleaving, and the shard_map'd
Pallas-interpret kernel path are ``-m slow``.
"""
import asyncio
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import (
    EngineSupervisor,
    LLMEngine,
    ServingMesh,
    ServingServer,
    as_serving_mesh,
    build_serving_mesh,
    faults,
    kv_capacity_blocks,
    serving_param_specs,
)
from paddle_tpu.serving.faults import FaultPlan
from paddle_tpu.serving.scheduler import Request


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2, num_heads=4,
                    max_seq_len=96, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _wave_prompts(seed=0):
    """The acceptance-criterion mixed wave: two prompts sharing a cached
    prefix, one prompt longer than the prefill chunk, one with a
    repetitive suffix the n-gram drafter hits."""
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, 128, (24,)).tolist()
    motif = [7, 11, 13]
    return shared, [
        shared + rs.randint(0, 128, (4,)).tolist(),
        shared + rs.randint(0, 128, (6,)).tolist(),
        rs.randint(0, 128, (40,)).tolist(),             # > prefill_chunk
        rs.randint(0, 128, (5,)).tolist() + motif * 4,  # drafter fodder
    ]


def _serve_wave(model, mesh, **kw):
    """Warm the prefix cache with the shared prefix, then serve the wave
    with speculative decoding on; returns (engine, outputs)."""
    shared, prompts = _wave_prompts()
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, mesh=mesh, spec_decoding=True,
                    num_spec_tokens=3, **kw)
    eng.generate([shared], max_new_tokens=2, temperature=0.0)
    outs = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
    return eng, outs


@pytest.fixture(scope="module")
def ref_wave(model):
    """Single-chip reference serve of the mixed wave (the parity anchor
    for every sharded run in this file)."""
    eng, outs = _serve_wave(model, mesh=1)
    return eng, outs


def _idle(engine):
    assert engine.pool._refcount == {}
    return engine.pool.num_free == engine.pool.num_blocks - 1


# ---------------------------------------------------------------------------
# units: mesh construction, param specs, capacity formula
# ---------------------------------------------------------------------------

def test_build_serving_mesh_validation():
    import jax

    with pytest.raises(ValueError, match="tp_degree >= 2"):
        build_serving_mesh(1)
    with pytest.raises(ValueError, match="devices"):
        build_serving_mesh(4096)
    sm = build_serving_mesh(2)
    assert sm.tp_degree == 2 and sm.device_count == 2
    assert sm.backend == jax.devices()[0].platform
    # coercions: int, Mesh, ServingMesh, None
    assert as_serving_mesh(None) is None
    assert as_serving_mesh(1) is None
    assert as_serving_mesh(sm) is sm
    assert as_serving_mesh(2).tp_degree == 2
    assert as_serving_mesh(sm.mesh).tp_degree == 2
    from jax.sharding import Mesh

    # a degree-1 mesh is an explicit single-chip request in every form,
    # not a sharded engine that disabled donation for nothing
    one = Mesh(np.asarray(jax.devices()[:1]), ("tp",))
    assert as_serving_mesh(one) is None
    assert as_serving_mesh(ServingMesh(one)) is None
    with pytest.raises(ValueError, match="'tp' axis"):
        as_serving_mesh(Mesh(np.asarray(jax.devices()[:2]), ("dp",)))


def test_serving_param_specs_layout(model):
    """The documented tp layout: attention heads / FFN columns / vocab
    rows on 'tp' (the model's own mp sharding_axes renamed), norms and
    position embeddings replicated."""
    from jax.sharding import PartitionSpec as P

    sm = build_serving_mesh(2)
    specs = serving_param_specs(model, sm)
    assert specs["wte.weight"] == P("tp", None)
    assert specs["blocks.0.attn.qkv.weight"] == P(None, "tp")
    assert specs["blocks.0.attn.qkv.bias"] == P("tp")
    assert specs["blocks.0.attn.proj.weight"] == P("tp", None)
    assert specs["blocks.0.fc1.weight"] == P(None, "tp")
    assert specs["blocks.0.fc2.weight"] == P("tp", None)
    assert specs["blocks.0.ln1.weight"] == P()
    assert specs["wpe.weight"] == P()
    # RowParallel bias is the post-psum add — replicated
    assert specs["blocks.0.attn.proj.bias"] == P()


def test_validate_model_divisibility(model):
    # heads=4: tp=8 cannot shard them — one loud error at construction
    with pytest.raises(ValueError, match="num_heads"):
        LLMEngine(model, mesh=8)


def test_kv_capacity_blocks_per_shard():
    """Same per-chip byte budget buys tp x the blocks of the naive
    logical-head-count formula: under tp each shard stores heads/tp per
    block (the satellite fix — admission bounds must speak per-shard)."""
    kw = dict(kv_bytes=1 << 20, num_layers=2, num_heads=8, block_size=16,
              head_dim=32, dtype_itemsize=4)
    one = kv_capacity_blocks(**kw, tp_degree=1)
    four = kv_capacity_blocks(**kw, tp_degree=4)
    assert four == 4 * one
    assert one == (1 << 20) // (2 * 2 * 8 * 16 * 32 * 4)


def test_kv_hbm_bytes_admission_per_shard(model):
    """The same per-chip byte budget serves at tp=4 what tp=1 cannot
    hold: the single-chip engine fails LOUDLY at construction (budget
    named, not per-request 4xxes), the tp=4 engine gets 4x the blocks
    and admits a max-length request; num_blocks + kv_hbm_bytes together
    is a loud config error."""
    per_block = 2 * model.cfg.num_layers * model.cfg.num_heads * 8 * 16 * 4
    # a max-len (96-token) sequence worst-cases at blocks_for(95) = 12
    # blocks; 12-block budget is one short of 12 + the null block
    budget = 12 * per_block
    with pytest.raises(ValueError, match="kv_hbm_bytes"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                  kv_hbm_bytes=budget)
    # the gate mirrors validate EXACTLY: 13 blocks (12 usable) admits a
    # max-length request, so construction must accept it too
    e13 = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                    kv_hbm_bytes=13 * per_block)
    assert e13.validate(Request([1] * 46, max_new_tokens=50)) == 12
    e4 = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                   mesh=4, kv_hbm_bytes=budget)
    assert e4.pool.num_blocks == 4 * 12       # same budget, 4x the blocks
    long_req = Request([1] * 40, max_new_tokens=50)       # 90 tokens
    assert e4.validate(long_req) == e4.pool.blocks_for(89)
    with pytest.raises(ValueError, match="not both"):
        LLMEngine(model, block_size=8, num_blocks=64,
                  kv_hbm_bytes=budget)


# ---------------------------------------------------------------------------
# the acceptance test: tp=2 mixed-wave token parity
# ---------------------------------------------------------------------------

def test_tp2_mixed_wave_token_parity(model, ref_wave):
    """tp=2 serve of the full mixed wave (prefill chunks + decode + spec
    drafts + prefix-cache hits) is greedy token-identical to single-chip,
    compiles at most one mesh-aware program per ragged width bucket
    (`expected_program_count`, the one-place program contract) with 0
    steady-state retraces, and drains the pool to idle."""
    ref_eng, ref_outs = ref_wave
    eng, outs = _serve_wave(model, mesh=2)
    assert outs == ref_outs
    # program-count contract + recompile sentinel: the table is keyed by
    # (batch, width) only, never outgrows the bucket set, and every
    # compiled program traced exactly once
    assert set(eng._step_fns) <= {(eng.max_batch, w)
                                  for w in eng.width_buckets}
    assert len(eng._step_fns) <= eng.expected_program_count()
    assert (int(eng.metrics.counters["jit_traces"])
            == len(eng._step_fns))
    assert eng.metrics.gauges.get("jit_retraces", 0) == 0
    # the wave really exercised cache + spec on BOTH engines identically
    for m in (eng.metrics, ref_eng.metrics):
        assert m.counters.get("prefix_cache_hit_tokens", 0) > 0
        assert m.counters.get("spec_accepted_tokens", 0) > 0
    assert (eng.metrics.counters["prefix_cache_hit_tokens"]
            == ref_eng.metrics.counters["prefix_cache_hit_tokens"])
    assert (eng.metrics.counters["spec_accepted_tokens"]
            == ref_eng.metrics.counters["spec_accepted_tokens"])
    assert _idle(eng)


def test_tp2_temperature_sampling_bit_identical(model):
    """The PR 10 known limit, closed: with sampling compiled into the
    step on rows pinned REPLICATED at the program boundary, a tp=2
    temperature>0 serve draws the same tokens as single-chip from the
    same PRNG key — bit-identical, not merely same-distribution. The
    per-step key sequence is host-side and scheduling is deterministic,
    so every categorical/rejection draw sees the same (replicated) rows
    and the same key on both engines."""
    rs = np.random.RandomState(11)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (6, 11, 17)]
    kw = dict(block_size=8, max_batch=3, max_seq_len=96, prefill_chunk=8,
              seed=123)
    ref = LLMEngine(model, mesh=1, **kw)
    want = ref.generate(prompts, max_new_tokens=12, temperature=0.9,
                        top_k=20, top_p=0.95)
    eng = LLMEngine(model, mesh=2, **kw)
    got = eng.generate(prompts, max_new_tokens=12, temperature=0.9,
                       top_k=20, top_p=0.95)
    assert got == want
    assert _idle(eng) and _idle(ref)


def test_tp2_arena_and_param_placement(model, ref_wave):
    """The sharded engine's device state carries the documented layout:
    arenas head-sharded over tp, column/row-parallel weights on their
    axes (checked on the placed jax.Arrays, not just the spec table)."""
    from jax.sharding import PartitionSpec as P

    eng, _ = _serve_wave(model, mesh=2)
    assert eng.pool.k.sharding.spec == P(None, "tp")
    assert eng.pool.v.sharding.spec == P(None, "tp")
    assert eng._params["blocks.0.attn.qkv.weight"].sharding.spec == P(None, "tp")
    assert eng._params["blocks.1.fc2.weight"].sharding.spec == P("tp", None)
    # per-shard bytes: each of the 2 chips holds half the arena
    shard = next(iter(eng.pool.k.addressable_shards))
    assert shard.data.shape[1] == model.cfg.num_heads // 2
    assert eng.mesh_info() == {"tp_degree": 2, "device_count": 2,
                               "backend": "cpu", "kv_dtype": "float32"}


# ---------------------------------------------------------------------------
# supervision / fault injection keep working against the sharded engine
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _disarm():
    yield
    plan = faults.active()
    if plan is not None:
        plan.release_hangs()
    faults.clear()


def test_supervisor_poison_isolation_tp2(model, ref_wave):
    """PR 9's bisection isolation, unchanged against a tp=2 engine: a
    step_raise pinned to one request aborts exactly that request; every
    other request's tokens match the no-fault sharded (== single-chip)
    reference; pool drains to idle."""
    _, ref_outs = ref_wave
    _, prompts = _wave_prompts()
    by_ref = {}
    for i, o in enumerate(ref_outs):
        by_ref[f"r{i}"] = o
    shared, _ = _wave_prompts()
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, mesh=2, spec_decoding=True,
                    num_spec_tokens=3)
    eng.generate([shared], max_new_tokens=2, temperature=0.0)
    for i, p in enumerate(prompts):
        eng.add_request(p, max_new_tokens=10, request_id=f"r{i}")
    faults.install(FaultPlan([
        {"point": "step_raise", "request_id": "r2", "exc": "ShardBoom"},
    ]))
    sup = EngineSupervisor(eng)
    outs, failures = [], []
    steps = 0
    while eng.has_unfinished():
        o, f = sup.step()
        outs += o
        failures += f
        steps += 1
        assert steps < 300, "supervised sharded serve did not converge"
    assert [rid for rid, _ in failures] == ["r2"]
    assert "ShardBoom" in failures[0][1]
    got = {}
    for o in outs:
        got.setdefault(o.request_id, []).append(o.token)
    for rid in ("r0", "r1", "r3"):
        assert got[rid] == by_ref[rid]
    assert _idle(eng)


# ---------------------------------------------------------------------------
# /healthz and /metrics expose the mesh topology, and they agree
# ---------------------------------------------------------------------------

def _prom_gauge(text, name):
    for line in text.splitlines():
        if line.startswith(f"paddle_tpu_serving_{name} "):
            return float(line.split()[-1])
    raise AssertionError(f"gauge {name} not in /metrics")


def test_mesh_gauges_healthz_metrics_agree(model):
    """mesh_tp_degree / mesh_device_count gauges and the mesh_info
    backend label on /metrics must agree with /healthz's mesh object —
    a sharded replica's shape is visible on both surfaces."""
    async def main():
        engine = LLMEngine(model, block_size=8, max_batch=2,
                           max_seq_len=96, mesh=2)
        server = ServingServer(engine, host="127.0.0.1", port=0)
        await server.start()
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        writer.write(b"GET /healthz HTTP/1.1\r\nHost: t\r\n\r\n")
        await writer.drain()
        raw = await reader.read()
        writer.close()
        health = json.loads(raw.partition(b"\r\n\r\n")[2])
        reader, writer = await asyncio.open_connection("127.0.0.1",
                                                       server.port)
        writer.write(b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
        await writer.drain()
        raw = await reader.read()
        writer.close()
        metrics_text = raw.partition(b"\r\n\r\n")[2].decode()
        await server.shutdown(drain=True)
        return health, metrics_text

    health, text = asyncio.run(main())
    mesh = health["mesh"]
    assert mesh["tp_degree"] == 2 and mesh["device_count"] == 2
    assert mesh["backend"] == "cpu"
    assert _prom_gauge(text, "mesh_tp_degree") == mesh["tp_degree"]
    assert _prom_gauge(text, "mesh_device_count") == mesh["device_count"]
    assert (f'paddle_tpu_serving_mesh_info{{backend="{mesh["backend"]}"}} 1'
            in text)


def test_single_chip_reports_degree_one(model, ref_wave):
    ref_eng, _ = ref_wave
    info = ref_eng.mesh_info()
    assert info["tp_degree"] == 1 and info["device_count"] == 1
    assert ref_eng.metrics.gauges["mesh_tp_degree"] == 1


# ---------------------------------------------------------------------------
# slow: tp=4/8 sweep, preemption interleaving, shard_map'd Pallas kernel
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tp4_tp8_parity_sweep():
    """Wider meshes: an 8-head model served at tp=4 and tp=8 stays token-
    identical to its single-chip serve."""
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                    num_heads=8, max_seq_len=64, attn_impl="xla",
                    dropout=0.0)
    m = GPT(cfg)
    m.eval()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (5, 17, 9)]
    ref = LLMEngine(m, block_size=8, max_batch=4, max_seq_len=64,
                    prefill_chunk=8)
    ref_outs = ref.generate(prompts, max_new_tokens=8, temperature=0.0)
    for tp in (4, 8):
        eng = LLMEngine(m, block_size=8, max_batch=4, max_seq_len=64,
                        prefill_chunk=8, mesh=tp)
        outs = eng.generate(prompts, max_new_tokens=8, temperature=0.0)
        assert outs == ref_outs, f"tp={tp} diverged"
        assert eng.mesh_info()["tp_degree"] == tp
        assert _idle(eng)


@pytest.mark.slow
def test_tp2_preemption_interleave_parity(model):
    """A pool small enough to force preemption-by-recompute, with prefix
    caching and spec decoding live: any interleaving of admissions,
    preemptions, cache hits, and verify steps stays token-identical to
    the single-chip engine under the same pressure, and refcounts drain."""
    shared, prompts = _wave_prompts(seed=5)
    kw = dict(block_size=8, max_batch=3, max_seq_len=96, prefill_chunk=8,
              num_blocks=30, spec_decoding=True, num_spec_tokens=3)
    ref = LLMEngine(model, **kw)
    ref.generate([shared], max_new_tokens=2, temperature=0.0)
    ref_outs = ref.generate(prompts, max_new_tokens=10, temperature=0.0)
    eng = LLMEngine(model, mesh=2, **kw)
    eng.generate([shared], max_new_tokens=2, temperature=0.0)
    outs = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
    assert outs == ref_outs
    assert (eng.metrics.counters.get("preemptions", 0)
            == ref.metrics.counters.get("preemptions", 0))
    assert _idle(eng) and _idle(ref)


@pytest.mark.slow
def test_shard_map_pallas_interpret_parity(model, monkeypatch):
    """The per-shard Pallas dispatch (shard_map over the head axis):
    forced interpret mode exercises the kernel path on CPU; a tp=2 serve
    through it matches the XLA-fallback single-chip serve token-for-
    token. (On a real TPU the same dispatch runs the compiled kernel.)"""
    _, prompts = _wave_prompts(seed=9)
    ref = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8)
    ref_outs = ref.generate(prompts[:2], max_new_tokens=6, temperature=0.0)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, mesh=2)
    outs = eng.generate(prompts[:2], max_new_tokens=6, temperature=0.0)
    assert outs == ref_outs
    assert _idle(eng)
