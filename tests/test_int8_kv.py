"""Int8 end-to-end serving: quantized KV arena + AdaRound weights +
EQuARX quantized all-reduce, behind QUALITY GATES.

The contract this file enforces (README "Quantization"): int8 is only
shippable because these gates pass — a greedy serve on the quantized
arena must be near-token-identical to the f32 serve on the SAME mixed
wave (chunked prefill + decode + speculative drafts + prefix-cache
hits), single-chip and tp=2 with the quantized collectives on; AdaRound
weight quantization must hold the held-out NLL delta; and the capacity
claim (same ``kv_hbm_bytes`` admits ~4x the f32 blocks) must be real.
Around the anchor: churn-sweep scale-sidecar invariants, the tier's
scale-carrying export/import, the /healthz+/metrics kv_dtype surfaces,
and the PR 16 async-vs-sync drive race guard.
"""
import asyncio

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import AsyncLLMEngine, LLMEngine, kv_capacity_blocks

VOCAB = 128

# quality gates, deliberately stated once: at least this fraction of
# greedy tokens must match f32 exactly (int8 KV rounds logits ~0.1%, so
# runs match until a near-tie flips — on the tiny config they match
# token-for-token, but the gate is what we promise, not bitwiseness)
PARITY_RATE = 0.9
# AdaRound held-out mean-NLL may exceed f32 by at most this (nats/token)
NLL_DELTA = 0.05


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=VOCAB, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=96, attn_impl="xla",
                    dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _wave_prompts(seed=0):
    """The acceptance mixed wave: two prompts sharing a cached prefix,
    one longer than the prefill chunk, one with drafter fodder."""
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, VOCAB, (24,)).tolist()
    motif = [7, 11, 13]
    return shared, [
        shared + rs.randint(0, VOCAB, (4,)).tolist(),
        shared + rs.randint(0, VOCAB, (6,)).tolist(),
        rs.randint(0, VOCAB, (40,)).tolist(),              # > prefill_chunk
        rs.randint(0, VOCAB, (5,)).tolist() + motif * 4,   # drafter fodder
    ]


def _serve_wave(model, **kw):
    """Warm the prefix cache, then serve the wave with spec decoding on;
    returns (engine, outputs)."""
    shared, prompts = _wave_prompts()
    kw.setdefault("mesh", 1)
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, spec_decoding=True, num_spec_tokens=3,
                    **kw)
    eng.generate([shared], max_new_tokens=2, temperature=0.0)
    outs = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
    return eng, outs


@pytest.fixture(scope="module")
def ref_wave(model):
    """The f32 single-chip reference serve every gate compares against."""
    eng, outs = _serve_wave(model)
    return eng, outs


def _parity_rate(outs, ref):
    toks = [t for row in outs for t in row]
    want = [t for row in ref for t in row]
    assert len(toks) == len(want)
    return np.mean([a == b for a, b in zip(toks, want)])


# -- the tentpole gates: greedy parity on the mixed wave ----------------------


def test_int8_kv_greedy_parity_mixed_wave(model, ref_wave):
    _, ref = ref_wave
    eng, outs = _serve_wave(model, kv_dtype="int8")
    rate = _parity_rate(outs, ref)
    assert rate >= PARITY_RATE, (rate, outs, ref)
    # the dtype switch is visible on every observability surface
    assert eng.pool.kv_dtype == "int8"
    assert eng.pool_stats()["kv_dtype"] == "int8"
    assert eng.mesh_info()["kv_dtype"] == "int8"
    assert eng.metrics.infos["kv"] == {"dtype": "int8"}
    # one program per width bucket still holds — quantization must not
    # fork the program table
    assert eng.metrics.counters["jit_traces"] <= eng.expected_program_count()


def test_int8_kv_tp2_parity_with_quantized_allreduce(model, ref_wave):
    """tp=2 with BOTH int8 stories on: quantized arena + EQuARX
    RowParallel all-reduces. The gate is against the single-chip f32
    reference, so the collective quantization is inside the gate too."""
    _, ref = ref_wave
    eng, outs = _serve_wave(model, mesh=2, kv_dtype="int8",
                            quant_allreduce=True)
    rate = _parity_rate(outs, ref)
    assert rate >= PARITY_RATE, (rate, outs, ref)
    assert eng.quant_collectives == {"attn_proj", "ffn_fc2"}
    assert eng.mesh_info()["tp_degree"] == 2


def test_int8_kv_spec_and_prefix_determinism(model):
    """Speculative accept/rollback and prefix-cache hits must be
    requantization-safe: the same wave served twice (second run all
    prefix hits) is token-identical — rollback leaves accepted tokens'
    scales intact, and a cached block's payload is never re-scattered."""
    eng, first = _serve_wave(model, kv_dtype="int8")
    shared, prompts = _wave_prompts()
    again = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
    assert first == again
    assert eng.metrics.counters.get("prefix_cache_hit_tokens", 0) > 0


# -- capacity: the reason to ship int8 ----------------------------------------


def test_int8_capacity_vs_f32_at_same_budget(model):
    """Same kv_hbm_bytes must admit ~4x the f32 blocks (minus the scale
    sidecar overhead) — checked both on the sizing formula and on live
    engines, whose bytes-per-block gauge must agree with the formula."""
    cfg = model.cfg
    budget = 1 << 20
    kw = dict(block_size=8, max_batch=4, max_seq_len=96,
              kv_hbm_bytes=budget)
    eng_f = LLMEngine(model, **kw)
    eng_q = LLMEngine(model, kv_dtype="int8", **kw)
    assert eng_q.pool.num_blocks >= 2 * eng_f.pool.num_blocks
    assert eng_q.pool.bytes_per_block() < eng_f.pool.bytes_per_block() / 2
    # formula twin (serving/sharded.py): scales cost 2*L*H*4 per block
    blocks = kv_capacity_blocks(budget, cfg.num_layers, cfg.num_heads, 8,
                                cfg.hidden_size // cfg.num_heads, 1,
                                scale_itemsize=4)
    assert eng_q.pool.num_blocks == blocks
    # and the arena really is int8 + f32 sidecars
    assert eng_q.pool.k.dtype == np.int8
    assert eng_q.pool.k_scale.shape == eng_q.pool.k.shape[:3]
    assert eng_q.pool.k_scale.dtype == np.float32


def test_int8_overcapacity_wave_parity(model):
    """An over-capacity wave (device pool smaller than the wave's block
    need, preempt-by-recompute churn) must still pass the parity gate:
    freed-and-reallocated blocks restart their scales via the fresh-
    write reset, so churn cannot ratchet scales upward forever."""
    shared, prompts = _wave_prompts()
    outs, engs = [], []
    for kv_dtype in (None, "int8"):
        eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                        prefill_chunk=8, num_blocks=18, mesh=1,
                        kv_dtype=kv_dtype)
        outs.append(eng.generate(prompts, max_new_tokens=8,
                                 temperature=0.0))
        engs.append(eng)
    rate = _parity_rate(outs[1], outs[0])
    assert rate >= PARITY_RATE, (rate, outs)
    # pool drained back to idle in both dtypes
    for eng in engs:
        assert eng.pool._refcount == {}


# -- churn sweep: scale-sidecar invariants ------------------------------------


def test_churn_sweep_scale_sidecar_invariants(model):
    """Distinct-prefix over-capacity churn with the host tier on: after
    every round the sidecars hold finite non-negative scales, blocks the
    pool currently owns have strictly positive scales on both K and V,
    and a fresh serve still passes the parity gate (requantize-on-grow
    plus fresh-reset keep old payloads decodable)."""
    eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, num_blocks=18, host_kv_blocks=16,
                    mesh=1, kv_dtype="int8")
    ref = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, num_blocks=18, mesh=1)
    rs = np.random.RandomState(11)
    for r in range(3):
        prompts = [rs.randint(0, VOCAB, (n,)).tolist()
                   for n in (17, 25, 19)]
        got = eng.generate(prompts, max_new_tokens=4, temperature=0.0)
        want = ref.generate(prompts, max_new_tokens=4, temperature=0.0)
        assert _parity_rate(got, want) >= PARITY_RATE, (r, got, want)
        for sc in (np.asarray(eng.pool.k_scale),
                   np.asarray(eng.pool.v_scale)):
            assert np.isfinite(sc).all()
            assert (sc >= 0.0).all()
        owned = [b for b in range(1, eng.pool.num_blocks)
                 if eng.pool.refcount(b) > 0]
        for b in owned:
            assert (np.asarray(eng.pool.k_scale)[:, :, b] > 0).all()
            assert (np.asarray(eng.pool.v_scale)[:, :, b] > 0).all()
    eng.close()


# -- tier: scales ride swap + migration ---------------------------------------


def test_tier_export_import_carries_scales(model):
    """A drained int8 replica's export carries (hash, k, v, k_scale,
    v_scale) entries; an importing int8 replica serves the wave host-warm
    and token-identical to its own cold serve. An f32 replica must REJECT
    the int8 payload (dtype is part of the tier geometry)."""
    src, cold = _serve_wave(model, kv_dtype="int8", host_kv_blocks=24)
    payload = src.export_kv_tier(demote=True)
    assert payload["dtype"] == "int8"
    entry = payload["entries"][0]
    assert len(entry) == 5
    L, H = model.cfg.num_layers, model.cfg.num_heads
    assert entry[3].shape == (L, H) and entry[3].dtype == np.float32
    assert entry[1].dtype == np.int8

    dst = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    prefill_chunk=8, spec_decoding=True, num_spec_tokens=3,
                    mesh=1, kv_dtype="int8", host_kv_blocks=24)
    assert dst.import_kv_tier(payload) > 0
    _, prompts = _wave_prompts()
    warm = dst.generate(prompts, max_new_tokens=10, temperature=0.0)
    assert warm == cold
    assert dst.metrics.counters.get("swap_ins", 0) > 0

    f32 = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=96,
                    mesh=1, host_kv_blocks=24)
    with pytest.raises(ValueError, match="geometry"):
        f32.import_kv_tier(payload)
    for e in (src, dst, f32):
        e.close()


# -- AdaRound weights: the perplexity gate ------------------------------------


def _mean_nll(model, seqs):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor

    tot, n = 0.0, 0
    for seq in seqs:
        ids = np.asarray(seq, np.int32)[None, :]
        logits = model(Tensor(jnp.asarray(ids)))._array[0]  # [s, vocab]
        lse = jax.nn.logsumexp(logits[:-1].astype(jnp.float32), axis=-1)
        ll = logits[np.arange(len(seq) - 1), ids[0, 1:]] - lse
        tot += float(-ll.sum())
        n += len(seq) - 1
    return tot / n


@pytest.mark.slow  # tier-1 headroom (PR 19): heaviest always-on case; tier-2 covers it
def test_adaround_nll_gate_and_grid(model):
    """`LLMEngine(quantize="int8", ...)` rewrites block linears in place
    on an int8 grid; the held-out mean NLL may exceed f32 by at most
    NLL_DELTA, norms/embeddings stay f32 (bit-identical), and the serve
    still passes the greedy parity gate."""
    rs = np.random.RandomState(3)
    calib = [rs.randint(0, VOCAB, (24,)).tolist() for _ in range(4)]
    held = [rs.randint(0, VOCAB, (32,)).tolist() for _ in range(4)]

    paddle.seed(0)
    m2 = GPT(model.cfg)
    m2.eval()
    for (_, p1), (_, p2) in zip(model.named_parameters(),
                                m2.named_parameters()):
        p2._array = p1._array
    base_nll = _mean_nll(model, held)
    wte_before = np.asarray(m2.wte.weight._array).copy()
    ln_before = np.asarray(m2.blocks[0].ln1.weight._array).copy()

    _, ref = _serve_wave(model)
    eng, outs = _serve_wave(m2, quantize="int8", calib_prompts=calib,
                            quantize_iters=40)
    q_nll = _mean_nll(m2, held)
    assert q_nll - base_nll <= NLL_DELTA, (q_nll, base_nll)
    assert _parity_rate(outs, ref) >= PARITY_RATE, (outs, ref)
    # f32 tensors really untouched; quantized weights really on the grid
    assert np.array_equal(np.asarray(m2.wte.weight._array), wte_before)
    assert np.array_equal(np.asarray(m2.blocks[0].ln1.weight._array),
                          ln_before)
    w = np.asarray(m2.blocks[0].fc1.weight._array, np.float32)
    scales = np.abs(w).max(axis=0, keepdims=True) / 127.0
    grid = w / np.maximum(scales, 1e-12)
    assert np.allclose(grid, np.round(grid), atol=1e-3)
    assert eng.quantize == "int8"


def test_adaround_rejects_sharded_engine(model):
    with pytest.raises(ValueError, match="quantize first"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                  mesh=2, quantize="int8")


def test_bad_knobs_raise(model):
    with pytest.raises(ValueError, match="kv_dtype"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                  mesh=1, kv_dtype="int4")
    with pytest.raises(ValueError, match="quant_allreduce"):
        LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                  mesh=2, quant_allreduce=["attn_out"])


# -- the PR 16 race guard -----------------------------------------------------


def test_sync_drive_rejected_while_async_loop_owns_engine(model):
    """`engine.generate()` (and step/stream) from a foreign thread while
    an AsyncLLMEngine background loop owns the engine raises a pointed
    RuntimeError instead of interleaving two schedulers over one pool —
    and the engine is drivable again after stop()."""
    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=96,
                    mesh=1)

    async def main():
        fe = await AsyncLLMEngine(eng).start()
        try:
            with pytest.raises(RuntimeError, match="AsyncLLMEngine"):
                eng.generate([[1, 2, 3]], max_new_tokens=2)
            with pytest.raises(RuntimeError, match="AsyncLLMEngine"):
                eng.step()
            with pytest.raises(RuntimeError, match="AsyncLLMEngine"):
                next(eng.stream([1, 2, 3], max_new_tokens=2))
            # the async surface itself serves fine through the guard
            toks, reason = await fe.submit(
                [5, 6, 7], max_new_tokens=3, temperature=0.0).collect()
            assert len(toks) == 3 and reason == "length"
        finally:
            await fe.shutdown(drain=True)

    asyncio.run(main())
    # owner thread gone: the synchronous surface works again
    outs = eng.generate([[1, 2, 3]], max_new_tokens=2, temperature=0.0)
    assert len(outs[0]) == 2
