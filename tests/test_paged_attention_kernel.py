"""Ragged paged-attention kernel vs. the XLA gather fallback.

The Pallas kernel (ops/pallas/paged_attention.py) runs in `interpret=True`
mode on CPU against the padded-gather reference across ragged cases: mixed
decode/prefill rows, chunks crossing block boundaries, a partially filled
last block (whose stale tail the positional mask must discard), and
null-block table padding. A small smoke subset always runs; the full sweep
is marked `slow` so tier-1 stays inside its timeout.

Also covers the shared backend gate (`ops/pallas/_backend.py`) env knobs.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas._backend import interpret_mode, use_pallas
from paddle_tpu.ops.pallas.paged_attention import (
    _build_ragged,
    _pages_per_fetch,
    paged_attention_xla,
    ragged_grid_steps,
    ragged_paged_attention,
)

TOL = 1e-3  # issue acceptance: kernel matches fallback to >= 1e-3


def _case(lengths_counts, *, block_size, num_heads=2, head_dim=16,
          num_layers=2, layer=1, seed=0, table_padding=1):
    """Build a random arena + ragged batch. `lengths_counts` is a list of
    (total_tokens, chunk_count): each row's query chunk is the LAST `count`
    positions of its `total` tokens (count == total -> fresh prefill;
    count == 1 -> decode row)."""
    rs = np.random.RandomState(seed)
    B = len(lengths_counts)
    blocks_per = [
        max(1, -(-total // block_size)) for total, _ in lengths_counts
    ]
    num_blocks = 1 + sum(blocks_per)  # block 0 = null
    # leave table padding to exercise, unless the case is a full row
    max_blocks = max(blocks_per) + table_padding
    # garbage EVERYWHERE (incl. the null block and each partially filled
    # last block's tail): correctness must come from masking, not zeros
    k = rs.randn(num_layers, num_heads, num_blocks, block_size,
                 head_dim).astype(np.float32)
    v = rs.randn(num_layers, num_heads, num_blocks, block_size,
                 head_dim).astype(np.float32)
    tables = np.zeros((B, max_blocks), np.int32)
    nxt = 1
    for i, nb in enumerate(blocks_per):
        tables[i, :nb] = np.arange(nxt, nxt + nb)
        nxt += nb
    S = max(c for _, c in lengths_counts)
    q = rs.randn(B, S, num_heads, head_dim).astype(np.float32)
    qpos = np.zeros((B, S), np.int32)
    q_start = np.zeros(B, np.int32)
    kv_live = np.ones(B, np.int32)
    for i, (total, count) in enumerate(lengths_counts):
        start = total - count
        qpos[i, :count] = np.arange(start, total)
        q_start[i] = start
        kv_live[i] = (total - 1) // block_size + 1
    return (jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), layer,
            jnp.asarray(tables), jnp.asarray(qpos), jnp.asarray(q_start),
            jnp.asarray(kv_live))


def _check(lengths_counts, **kw):
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        lengths_counts, **kw)
    out_k = np.asarray(ragged_paged_attention(
        q, k, v, layer, tables, q_start, kv_live, interpret=True))
    out_r = np.asarray(paged_attention_xla(q, k, v, layer, tables, qpos))
    for i, (_, count) in enumerate(lengths_counts):
        err = np.abs(out_k[i, :count] - out_r[i, :count]).max()
        assert err < TOL, f"row {i} (count {count}): max err {err}"
        assert np.isfinite(out_k[i, :count]).all()


def test_kernel_matches_fallback_smoke():
    """Always-on subset: one mixed batch with a decode row, a fresh prefill
    chunk, and a boundary-crossing chunk over a partially filled block."""
    _check([(18, 1), (5, 5), (13, 7)], block_size=8)


def test_kernel_single_row_partial_last_block():
    """A lone decode row whose last block is partially filled: the stale
    tail beyond qpos must not leak into the softmax."""
    _check([(9, 1)], block_size=8)


def _check_ragged_q(lengths_counts, pad_to, **kw):
    """Like _check but with per-row ragged QUERY lengths (`q_lens`): the
    step width pads to `pad_to` and every row declares its own live
    count — the unified step program's shape (a decode row inside a wide
    launch). Live outputs must match the reference; dead q tiles may
    hold garbage."""
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        lengths_counts, **kw)
    B, S = q.shape[:2]
    assert pad_to >= S
    qw = jnp.zeros((B, pad_to) + q.shape[2:], q.dtype).at[:, :S].set(q)
    q_lens = jnp.asarray([c for _, c in lengths_counts], jnp.int32)
    out_k = np.asarray(ragged_paged_attention(
        qw, k, v, layer, tables, q_start, kv_live, q_lens=q_lens,
        interpret=True))
    out_r = np.asarray(paged_attention_xla(q, k, v, layer, tables, qpos))
    for i, (_, count) in enumerate(lengths_counts):
        err = np.abs(out_k[i, :count] - out_r[i, :count]).max()
        assert err < TOL, f"row {i} (count {count}): max err {err}"
        assert np.isfinite(out_k[i, :count]).all()


def test_kernel_ragged_query_lengths_smoke():
    """Per-row ragged q: a decode row, a short chunk, and a full-width
    chunk share one 16-wide launch (qt=8, two query tiles — the decode
    row computes only tile 0); live rows match the reference exactly."""
    _check_ragged_q([(18, 1), (5, 5), (16, 16)], pad_to=16, block_size=8)


def test_kernel_ragged_query_decode_in_wide_launch():
    """The dominant unified-program case: width-1 decode rows riding a
    wide (verify/chunk) program width — q_lens=1 everywhere, padding
    tiles dead."""
    _check_ragged_q([(9, 1), (23, 1)], pad_to=8, block_size=8)


PAGES = _pages_per_fetch(16)    # pages one iteration of the walk fetches

# rows as (total tokens, live query tokens) at block_size 16: what the walk
# over page groups has to get right at a group's edges
WALK_CASES = {
    "one-page": [(5, 1)],
    "one-less-than-a-group": [(16 * (PAGES - 1) - 3, 1)],
    "a-whole-group": [(16 * PAGES, 1), (16 * PAGES - 15, 1)],
    "one-more-than-a-group": [(16 * PAGES + 1, 1), (9, 1)],
    "two-groups-and-a-page": [(16 * 2 * PAGES + 7, 3), (40, 3)],
    "chunk-128-beside-decode-rows": [(150, 128), (16 * PAGES + 1, 1),
                                     (33, 1)],
    # two query tiles: the decode row's second runs nothing
    "chunk-256-beside-a-decode-row": [(300, 256), (40, 1)],
}


@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_kernel_walks_live_page_groups(case):
    """The walk fetches `PAGES` pages an iteration: rows of one page, one
    less than, exactly and one more than a group, and chunks of one and
    of two query tiles beside decode rows, against the reference."""
    rows = WALK_CASES[case]
    width = max(c for _, c in rows)
    _check_ragged_q(rows, pad_to=width, block_size=16)


def test_kernel_full_row_walks_every_table_slot():
    """`kv_live == max_blocks`: no table padding behind the last page,
    and a last group that is not whole (the slots past the row's last
    page re-fetch it; their positions are masked)."""
    rows = [(16 * (PAGES + 3), 1), (16 * (PAGES + 3) - 20, 5)]
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        rows, block_size=16, table_padding=0)
    assert int(kv_live.max()) == tables.shape[1] == PAGES + 3
    _check_ragged_q(rows, pad_to=5, block_size=16, table_padding=0)


def test_kernel_idle_lane_writes_zeros_and_leaves_neighbours():
    """A lane that carries no request (`q_lens` 0, or `kv_live` 0 as the
    engine marks it) runs no iteration and writes zeros, whatever its
    table points at; the live lanes beside it read as they do alone."""
    rows = [(37, 1), (5, 1), (150, 1)]
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        rows, block_size=16)
    alone = np.asarray(ragged_paged_attention(
        q, k, v, layer, tables, q_start, kv_live,
        q_lens=jnp.asarray([1, 1, 1]), interpret=True))
    for q_lens, kvl in (([1, 0, 1], kv_live),
                        ([1, 1, 1], kv_live.at[1].set(0))):
        out = np.asarray(ragged_paged_attention(
            q, k, v, layer, tables, q_start, kvl,
            q_lens=jnp.asarray(q_lens), interpret=True))
        assert np.isfinite(out).all()
        assert not out[1].any()
        np.testing.assert_array_equal(out[[0, 2]], alone[[0, 2]])
    ref = np.asarray(paged_attention_xla(q, k, v, layer, tables, qpos))
    assert np.abs(alone - ref).max() < TOL


def test_kernel_int8_arena_matches_dequantized_reference():
    """int8 pages go to the dots as they are and the scales, a key, are
    applied to the scores and the probabilities: the same numbers as the
    reference's dequantize-then-attend, across a group's edge."""
    rows = [(16 * PAGES + 9, 4), (21, 1)]
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        rows, block_size=16)
    rs = np.random.RandomState(1)
    k8, v8 = (jnp.asarray(rs.randint(-127, 128, k.shape), jnp.int8)
              for _ in range(2))
    ksc, vsc = (jnp.asarray(rs.uniform(0.005, 0.02, k.shape[:3]),
                            jnp.float32) for _ in range(2))
    q_lens = jnp.asarray([c for _, c in rows], jnp.int32)
    out = np.asarray(ragged_paged_attention(
        q, k8, v8, layer, tables, q_start, kv_live, q_lens=q_lens,
        interpret=True, k_scale=ksc, v_scale=vsc))
    ref = np.asarray(paged_attention_xla(q, k8, v8, layer, tables, qpos,
                                         k_scale=ksc, v_scale=vsc))
    for i, (_, count) in enumerate(rows):
        assert np.abs(out[i, :count] - ref[i, :count]).max() < TOL


def test_kernel_head_size_64():
    _check_ragged_q([(16 * PAGES + 2, 2), (30, 1)], pad_to=2,
                    block_size=16, head_dim=64)


def test_one_built_kernel_serves_every_layer():
    """The layer is an index the kernel reads, not a key of the build:
    two layers through the same shapes build one kernel, traced or not."""
    import jax

    rows = [(40, 1), (16 * PAGES + 1, 1)]
    q, k, v, _, tables, qpos, q_start, kv_live = _case(
        rows, block_size=16, num_layers=3)
    _build_ragged.cache_clear()
    traced = jax.jit(lambda layer: ragged_paged_attention(
        q, k, v, layer, tables, q_start, kv_live, interpret=True))
    for layer in (0, 2):
        ref = np.asarray(paged_attention_xla(q, k, v, layer, tables, qpos))
        for out in (traced(layer), ragged_paged_attention(
                q, k, v, layer, tables, q_start, kv_live, interpret=True)):
            assert np.abs(np.asarray(out) - ref).max() < TOL
    assert not np.allclose(traced(0), traced(2))
    info = _build_ragged.cache_info()
    assert info.currsize == 1 and info.misses == 1, info


def test_ragged_grid_steps_against_a_count_by_hand():
    """Width 256 cuts into two query tiles of 128; block_size 16 makes a
    fetch 32 pages. Row 0: a chunk of 200 tokens (2 live tiles) over 65
    pages (3 fetches): 6 iterations, nothing skipped. Row 1: a decode row
    over 32 pages: 1 iteration, 1 tile skipped. Row 2: a decode row over
    33 pages: 2 iterations, 1 skipped. Row 3: no request: 2 skipped."""
    assert (pa._q_tile(256), PAGES) == (128, 32)
    got = ragged_grid_steps(np.array([200, 1, 1, 0], np.int32),
                            np.array([65, 32, 33, 0], np.int32), 256, 16)
    assert got == (6 + 1 + 2, 6 + 1 + 2 + 0 + 1 + 1 + 2)
    # width 1: one tile a row, nothing skipped but the idle lane
    got = ragged_grid_steps(np.array([1, 1, 1, 0], np.int32),
                            np.array([65, 32, 33, 0], np.int32), 1, 16)
    assert got == (3 + 1 + 2, 3 + 1 + 2 + 1)


def test_dispatch_keeps_a_head_size_mosaic_refuses_on_the_xla_path(
        monkeypatch):
    """Compiled (not interpreted), the walk's page copies slice the arena
    in HBM, which Mosaic refuses where it pads the head size to the 128
    lanes: the dispatch keeps such a model on the XLA path."""
    monkeypatch.setattr(pa, "use_pallas", lambda: True)
    monkeypatch.setattr(pa, "interpret_mode", lambda: False)

    def refuse(*a, **kw):
        raise AssertionError("the kernel was dispatched")

    monkeypatch.setattr(pa, "ragged_paged_attention", refuse)
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        [(20, 1)], block_size=16, head_dim=64)
    out = pa.paged_attention_arrays(q, k, v, layer, tables, qpos,
                                    q_start=q_start, kv_live=kv_live)
    ref = paged_attention_xla(q, k, v, layer, tables, qpos)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    with pytest.raises(AssertionError, match="was dispatched"):
        q, k, v, *_ = _case([(20, 1)], block_size=16, head_dim=128)
        pa.paged_attention_arrays(q, k, v, layer, tables, qpos,
                                  q_start=q_start, kv_live=kv_live)


@pytest.mark.slow
@pytest.mark.parametrize("block_size", [4, 8, 16])
@pytest.mark.parametrize("lengths_counts", [
    [(1, 1)],                                  # minimal decode
    [(16, 16)],                                # exact block multiple prefill
    [(17, 17)],                                # one past a block boundary
    [(31, 15), (32, 1), (3, 3), (20, 4)],      # ragged mixed batch
    [(8, 1), (8, 8), (24, 12), (5, 2)],        # decode + chunks, shared S
])
def test_kernel_matches_fallback_sweep(block_size, lengths_counts):
    """Interpret-mode sweep over ragged lengths x block sizes (slow: the
    Pallas interpreter runs one grid step at a time)."""
    _check(lengths_counts, block_size=block_size, seed=hash(
        (block_size, tuple(lengths_counts))) % 2**31)


@pytest.mark.slow
def test_kernel_bfloat16_tolerance():
    q, k, v, layer, tables, qpos, q_start, kv_live = _case(
        [(18, 1), (13, 7)], block_size=8)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out_k = np.asarray(ragged_paged_attention(
        qb, kb, vb, layer, tables, q_start, kv_live, interpret=True)
    ).astype(np.float32)
    out_r = np.asarray(paged_attention_xla(
        qb, kb, vb, layer, tables, qpos)).astype(np.float32)
    for i, count in enumerate((1, 7)):
        err = np.abs(out_k[i, :count] - out_r[i, :count]).max()
        assert err < 2e-2, f"row {i}: bf16 max err {err}"


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("D", [128, 64], ids=["d128", "d64"])
@pytest.mark.parametrize("width", [1, 5, 128])
def test_kernel_lowers_for_tpu(width, D, quant):
    """Cross-lower (no chip needed) the compiled kernel for TPU at the
    serving shapes. JAX's Pallas TPU lowering refuses a block whose last
    two dims are neither (8, 128)-divisible nor the whole array — which is
    what the int8 scale sidecars' (1, 1, 1) blocks were — before Mosaic
    ever sees the kernel, so a refusal of that kind shows up here. What
    Mosaic itself refuses does not: head size 64 lowers here and is
    refused there (tests/test_mosaic_aot.py compiles; the dispatch keeps
    that size on the XLA path)."""
    import jax

    B, H, bs, nk, NB = 8, 16, 16, 128, 64
    f32, i32 = jnp.float32, jnp.int32
    sds = jax.ShapeDtypeStruct
    arena = sds((2, H, NB, bs, D), jnp.int8 if quant else jnp.bfloat16)
    args = [sds((B, width, H, D), jnp.bfloat16), arena, arena,
            sds((B, nk), i32), sds((B,), i32), sds((B,), i32),
            sds((B,), i32)]
    if quant:
        args += [sds((2, H, NB), f32)] * 2

    def f(q, k, v, tables, q_start, kv_live, q_lens, *scales):
        kw = dict(zip(("k_scale", "v_scale"), scales))
        return ragged_paged_attention(q, k, v, 1, tables, q_start, kv_live,
                                      q_lens=q_lens, interpret=False, **kw)

    lowered = jax.jit(f).trace(*args).lower(lowering_platforms=("tpu",))
    assert lowered.as_text().count("tpu_custom_call") == 1


def test_backend_gate_env_overrides(monkeypatch):
    """DISABLE beats FORCE beats platform; FORCE turns on interpret mode."""
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", raising=False)
    assert use_pallas() is False  # CPU backend, no opt-in
    assert interpret_mode() is False
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    assert use_pallas() is True
    assert interpret_mode() is True
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert use_pallas() is False  # DISABLE wins


def test_flash_attention_shares_backend_gate():
    """The flash kernel's gate is the hoisted shared one, not a copy."""
    from paddle_tpu.ops.pallas import flash_attention

    assert flash_attention._use_pallas is use_pallas


@pytest.mark.slow
def test_engine_greedy_identical_through_interpreted_kernel(monkeypatch):
    """End to end: LLMEngine with PADDLE_TPU_FORCE_PALLAS_INTERPRET serves
    greedy outputs token-identical to sequential GPT.generate — the kernel
    slots into the jitted mixed step without changing argmax decisions."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, attn_impl="xla",
                    dropout=0.0)
    m = GPT(cfg)
    m.eval()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (n,)).tolist() for n in (5, 11)]

    def ref(p, n):
        ids = paddle.to_tensor(np.asarray([p], np.int64))
        out = m.generate(ids, max_new_tokens=n, temperature=0.0)
        return out.numpy()[0, len(p):].tolist()

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    engine = LLMEngine(m, block_size=8, max_batch=2, max_seq_len=32,
                       prefill_chunk=8)
    outs = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o == ref(p, 4)
    assert engine.metrics.counters["jit_traces"] == 2
