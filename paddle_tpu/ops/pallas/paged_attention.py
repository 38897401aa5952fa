"""Ragged paged attention: Pallas TPU kernel + XLA gather fallback.

The serving engine's attention (PAPERS.md "Ragged Paged Attention"): K/V live
in a head-major block arena ``[layers, heads, num_blocks, block_size,
head_dim]`` and every batch row attends through its own block table. One
launch serves a MIXED batch — decode rows (1 live query token) next to
prefill-chunk rows (up to `prefill_chunk` tokens) — which is what lets the
engine run chunked prefill and decode in a single XLA program.

Kernel design (TPU):
- Grid ``(rows, heads, q_blocks, kv_blocks)`` with the KV-block dimension
  innermost. The block index map reads the row's block table through
  scalar prefetch (SMEM), so each grid step DMAs exactly ONE live KV
  block ``[block_size, head_dim]`` from the arena in HBM — the padded
  tail of the block table is never fetched: dead iterations clamp the
  index map to the last live block (Mosaic elides the re-fetch of an
  unchanged block) and `pl.when` skips their compute. This is the whole
  point vs. the XLA fallback below, which gathers the full padded
  ``[rows, max_blocks]`` table every layer.
- Query lengths are ragged PER ROW (``q_lens``): the query axis is tiled
  and each row declares how many tiles are live, so a decode row (1 live
  token) riding a wide mixed/verify-width program computes one query
  tile while a full prefill chunk in the same launch walks them all —
  dead q blocks clamp their index map (no DMA) and skip compute exactly
  like dead KV iterations. This is what lets ONE program shape serve
  decode, prefill-chunk, and speculative-verify rows (the unified
  ragged step program in serving/engine.py).
- Online-softmax state (m, l, acc) lives in VMEM scratch across the KV
  iterations, exactly like flash_attention.py; fp32 accumulation on the MXU.
- Causal masking is positional: query positions are ``q_start[row] + iota``
  (chunk tokens are consecutive), key positions ``block * block_size +
  iota``; ``qpos >= kpos`` also discards the garbage tail of a partially
  filled last block.
- Head-major arena so each (head, block) tile is a 2-D ``(block_size,
  head_dim)`` VMEM block: Mosaic requires the minor two dims of a block to
  be (8, 128)-divisible or equal to the array dims, which a head axis in
  second-to-minor position would violate (same constraint that shapes
  flash_attention.py's [B*H, S, D] layout).

The dispatch (`paged_attention_arrays`) is the seam `serving/block_pool.py`
calls after scattering the step's new K/V into the arena: Pallas on TPU (or
interpreted when PADDLE_TPU_FORCE_PALLAS_INTERPRET is set), XLA gather
everywhere else. The fallback gathers into the SAME
``[rows, seq, heads, head_dim]`` layout and einsum as `models/gpt.py`'s
contiguous-cache decode, keeping greedy serving outputs token-for-token
identical to `GPT.generate`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._backend import interpret_mode, note_xla_fallback, use_pallas

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA fallback (also the correctness reference in tests)
# ---------------------------------------------------------------------------

def paged_attention_xla(q, k_arena, v_arena, layer, block_tables, qpos,
                        scale=None, k_scale=None, v_scale=None):
    """Reference paged attention: gather the full padded block table.

    q: [B, S, H, D]; arenas: [layers, H, num_blocks, block_size, D];
    block_tables: [B, max_blocks] int32 (0 = null block); qpos: [B, S]
    absolute query positions (padding rows/cols carry 0 and are discarded
    by the caller). `k_scale`/`v_scale` [layers, H, num_blocks] dequantize
    an int8 arena BEFORE the einsum, so this path stays the correctness
    reference that brackets the kernel's in-VMEM dequant. Returns
    [B, S, H, D].
    """
    B, S, H, D = q.shape
    if scale is None:
        scale = 1.0 / np.sqrt(D)
    k_seq = k_arena[layer][:, block_tables]  # [H, B, nb, bs, D]
    v_seq = v_arena[layer][:, block_tables]
    if k_scale is not None:
        ksc = k_scale[layer][:, block_tables]  # [H, B, nb]
        vsc = v_scale[layer][:, block_tables]
        k_seq = k_seq.astype(jnp.float32) * ksc[..., None, None]
        v_seq = v_seq.astype(jnp.float32) * vsc[..., None, None]
    nb, bs = k_seq.shape[2], k_seq.shape[3]
    L = nb * bs
    # back to the [B, L, H, D] layout of models/gpt.py's contiguous-cache
    # path so the einsum below is the exact same contraction (bit-parity
    # with GPT.generate is a serving acceptance criterion)
    k_seq = jnp.transpose(k_seq, (1, 2, 3, 0, 4)).reshape(B, L, H, D)
    v_seq = jnp.transpose(v_seq, (1, 2, 3, 0, 4)).reshape(B, L, H, D)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k_seq, preferred_element_type=jnp.float32
    ) * scale
    kpos = jnp.arange(L)[None, None, None, :]
    qp = qpos[:, None, :, None]  # [B, 1, S, 1]
    s = jnp.where(kpos <= qp, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_seq.dtype), v_seq)


# ---------------------------------------------------------------------------
# Pallas ragged kernel
# ---------------------------------------------------------------------------

def _ragged_kernel(bt_ref, qs_ref, kl_ref, qb_ref, q_ref, k_ref, v_ref,
                   *rest, bs, qt, scale, quant):
    """One (row, head, q-block) tile's online-softmax walk over its live
    KV blocks.

    bt_ref/qs_ref/kl_ref/qb_ref are the scalar-prefetched block tables,
    per-row query start positions, per-row live KV block counts, and
    per-row live QUERY block counts (SMEM). The q-block grid dimension is
    what makes query length ragged PER ROW: a decode row (1 live query
    token) riding a wide mixed/verify program computes only its first
    ``qt``-wide query tile — dead q blocks re-address the last live tile
    (no DMA) and skip all compute, exactly like the dead KV iterations.

    ``quant`` (int8 arena): two extra f32 scale refs carry this (row,
    head)'s per-block scales as one ``[1, nk]`` lane row in block-table
    order; step j picks lane j and each DMA'd int8 tile dequantizes IN
    VMEM (one multiply per tile) before the MXU dot — the arena walk
    moves a quarter of the f32 bytes and the compute path is unchanged."""
    from jax.experimental import pallas as pl

    if quant:
        ksc_ref, vsc_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ksc_ref = vsc_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest

    i = pl.program_id(0)   # batch row
    qb = pl.program_id(2)  # query block
    j = pl.program_id(3)   # kv block step (innermost)
    q_live = qb < qb_ref[i]

    @pl.when(q_live & (j == 0))
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(q_live & (j < kl_ref[i]))
    def _():
        q = q_ref[0, 0]        # [qt, D]
        kt = k_ref[0, 0, 0]    # [bs, D]
        if quant:
            lane = jax.lax.broadcasted_iota(
                jnp.int32, ksc_ref.shape[2:], 1) == j      # [1, nk]
            kt = kt.astype(jnp.float32) * jnp.sum(
                jnp.where(lane, ksc_ref[0, 0], 0.0), axis=1, keepdims=True)
        s = jax.lax.dot_general(
            q, kt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        # chunk query positions are consecutive from q_start; key positions
        # follow from the block index. qpos >= kpos is both the causal mask
        # and the guard over a partially filled last block's stale tail.
        qp = (qs_ref[i] + qb * qt
              + jax.lax.broadcasted_iota(jnp.int32, (qt, bs), 0))
        kp = j * bs + jax.lax.broadcasted_iota(jnp.int32, (qt, bs), 1)
        s = jnp.where(qp >= kp, s, _NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        vt = v_ref[0, 0, 0]    # [bs, D]
        if quant:
            vt = vt.astype(jnp.float32) * jnp.sum(
                jnp.where(lane, vsc_ref[0, 0], 0.0), axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(q_live & (j == kl_ref[i] - 1))
    def _():
        o_ref[0, 0] = (
            acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
        ).astype(o_ref.dtype)


def _q_tile(S):
    """Query-tile width: the whole width for narrow programs, 8-wide
    sublane-aligned tiles when the width divides (fp32 Mosaic tiling —
    minor-two dims of a block must be (8, 128)-divisible or equal to the
    array dims). A width that is neither <= 8 nor 8-divisible keeps one
    full-width tile (per-row raggedness then costs nothing extra: it
    degrades to the pre-ragged single-tile layout)."""
    return 8 if S > 8 and S % 8 == 0 else S


def ragged_grid_steps(q_lens, kv_live, width, max_blocks):
    """`(live, total)` grid steps of one launch of the ragged kernel, a
    head a layer: what the engine's step span reports as ``grid_live`` /
    ``grid_total``. Host-side arithmetic over the numpy vectors the step
    is built from, kept beside the grid it counts (`_build_ragged`: grid
    ``(B, H, nq, nk)``, a step live where ``qb < qb_live[i]`` and ``j <
    kv_live[i]``) so that a change to the one changes the other.

    q_lens, kv_live: ``[B]``, one entry a lane. A lane whose ``q_lens``
    is 0 carries no request and counts as dead: the kernel does walk one
    step there, over the null block, but it is no useful work."""
    qt = _q_tile(width)
    q_tiles = -(-q_lens // qt)
    live = int(np.sum(q_tiles * np.where(q_lens > 0, kv_live, 0)))
    return live, len(q_lens) * (width // qt) * max_blocks


@functools.lru_cache(maxsize=None)
def _build_ragged(B, H, sq, d, bs, nk, layer, dtype_name, interpret,
                  quant=False):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    scale = 1.0 / np.sqrt(d)
    qt = _q_tile(sq)
    nq = sq // qt

    def q_index(i, h, qb, j, bt, qs, kl, qlb):
        # dead q blocks re-address the row's last live tile: Mosaic
        # elides the DMA for an unchanged index, pl.when skips compute
        return (i, h, jnp.minimum(qb, qlb[i] - 1), 0)

    def kv_index(i, h, qb, j, bt, qs, kl, qlb):
        # dead iterations (j >= live count) re-address the last live
        # block; dead q TILES freeze the whole KV walk there too — the
        # index must stay UNCHANGED across their inner j steps or Mosaic
        # re-fetches every live KV block once per dead tile (kl[i]-1 is
        # also where the preceding live tile's walk ended, so the freeze
        # elides the DMA across the tile boundary as well)
        jc = jnp.where(qb < qlb[i], jnp.minimum(j, kl[i] - 1), kl[i] - 1)
        return (layer, h, bt[i, jc], 0, 0)

    def sc_index(i, h, qb, j, bt, qs, kl, qlb):
        # the int8 scales arrive gathered per row, [B, H, 1, nk] in
        # block-table order (see ragged_paged_attention): one whole lane
        # row per (row, head), fetched once and unchanged across qb and j
        return (i, h, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, qt, d), q_index),
        pl.BlockSpec((1, 1, 1, bs, d), kv_index),
        pl.BlockSpec((1, 1, 1, bs, d), kv_index),
    ]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, nk), sc_index),
                     pl.BlockSpec((1, 1, 1, nk), sc_index)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, H, nq, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, qt, d), q_index),
        scratch_shapes=[
            pltpu.VMEM((qt, 1), jnp.float32),   # running max m
            pltpu.VMEM((qt, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((qt, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, bs=bs, qt=qt, scale=scale,
                          quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, sq, d), jnp.dtype(dtype_name)),
        interpret=interpret,
        name="ragged_paged_attention",
    )


def ragged_paged_attention(q, k_arena, v_arena, layer, block_tables,
                           q_start, kv_live, q_lens=None, interpret=False,
                           k_scale=None, v_scale=None):
    """Pallas ragged paged attention over live KV blocks — and live
    QUERY tiles — only.

    q: [B, S, H, D]; arenas: [layers, H, num_blocks, bs, D];
    block_tables: [B, max_blocks]; q_start: [B] first query position per
    row; kv_live: [B] number of live KV blocks per row (>= 1); q_lens:
    [B] live query tokens per row (ragged widths — a decode row riding a
    wide program declares 1 and pays one query tile; None means every
    row is full-width). `k_scale`/`v_scale` [layers, H, num_blocks]
    switch the kernel to int8 arenas with in-VMEM dequant. Returns
    [B, S, H, D]. Rows/columns beyond each row's live tokens hold
    garbage — the engine discards them.
    """
    B, S, H, D = q.shape
    bs = k_arena.shape[3]
    nk = block_tables.shape[1]
    quant = k_scale is not None
    fn = _build_ragged(B, H, S, D, bs, nk, int(layer), str(q.dtype),
                       bool(interpret), quant=quant)
    qt = _q_tile(S)
    if q_lens is None:
        qb_live = jnp.full((B,), S // qt, jnp.int32)
    else:
        # live query TILES per row (>= 1: padding lanes walk one tile of
        # the null block, like kv_live's clamp)
        ql = jnp.maximum(q_lens.astype(jnp.int32), 1)
        qb_live = (ql + qt - 1) // qt
    qh = jnp.transpose(q, (0, 2, 1, 3))  # [B, H, S, D]
    operands = (qh, k_arena, v_arena)
    if quant:
        # The arena-side sidecars stay [layers, H, num_blocks], but a
        # (1, 1, 1) block over them does not lower for TPU ("the last two
        # dimensions of your block shape [must be] divisible by 8 and 128
        # respectively, or be equal to the respective dimensions of the
        # overall array"). So each row's scales are gathered through its
        # block table here, a [B, H, 1, nk] f32 array of a few KB, whose
        # (1, nk) minor dims a block can take whole.
        def row_scales(sc):
            return jnp.transpose(sc[layer][:, block_tables],
                                 (1, 0, 2))[:, :, None, :]

        operands += (row_scales(k_scale), row_scales(v_scale))
    o = fn(
        block_tables.astype(jnp.int32),
        q_start.astype(jnp.int32),
        jnp.maximum(kv_live.astype(jnp.int32), 1),
        qb_live,
        *operands,
    )
    return jnp.transpose(o, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# dispatch — the seam serving/block_pool.py calls
# ---------------------------------------------------------------------------

def ragged_paged_attention_sharded(q, k_arena, v_arena, layer, block_tables,
                                   q_start, kv_live, q_lens=None,
                                   mesh=None, tp_axis="tp",
                                   interpret=False,
                                   k_scale=None, v_scale=None):
    """Per-shard dispatch of the single-device ragged kernel on a tp mesh.

    The kernel walks one (row, head, block) grid and DMAs (head, block)
    tiles out of the local arena — it has no concept of a mesh. Under
    `shard_map` over the head axis each shard sees exactly its local
    slice: q ``[B, S, H/tp, D]`` and arenas ``[layers, H/tp, blocks,
    block_size, head_dim]``, with the block table / ragged metadata
    replicated (block ids are global, shard-invariant host bookkeeping).
    Heads never mix across chips inside attention, so the per-shard
    outputs concatenate with NO collective here — the tp all-reduce
    happens where the layout demands it, on the output-projection matmul
    that follows (serving/sharded.py documents the full layout)."""
    from jax.sharding import PartitionSpec as P

    from ...parallel._compat import shard_map

    if q_lens is None:
        q_lens = jnp.full((q.shape[0],), q.shape[1], jnp.int32)

    quant = k_scale is not None
    if quant:
        # scale sidecars [layers, H, num_blocks] shard over the same head
        # axis as the arenas — each shard dequantizes with its local heads'
        # scales and no collective is introduced
        def local(qh, ka, va, ks, vs, bt, qs, kl, ql):
            return ragged_paged_attention(qh, ka, va, layer, bt, qs, kl,
                                          q_lens=ql, interpret=interpret,
                                          k_scale=ks, v_scale=vs)

        fn = shard_map(
            local, mesh=mesh,
            in_specs=(P(None, None, tp_axis, None), P(None, tp_axis),
                      P(None, tp_axis), P(None, tp_axis), P(None, tp_axis),
                      P(), P(), P(), P()),
            out_specs=P(None, None, tp_axis, None),
        )
        return fn(q, k_arena, v_arena, k_scale, v_scale,
                  block_tables, q_start, kv_live, q_lens)

    def local(qh, ka, va, bt, qs, kl, ql):
        return ragged_paged_attention(qh, ka, va, layer, bt, qs, kl,
                                      q_lens=ql, interpret=interpret)

    fn = shard_map(
        local, mesh=mesh,
        in_specs=(P(None, None, tp_axis, None), P(None, tp_axis),
                  P(None, tp_axis), P(), P(), P(), P()),
        out_specs=P(None, None, tp_axis, None),
    )
    # raw metadata passes through; ragged_paged_attention normalizes
    # (int32 casts + the >=1 kv_live/q_lens clamps) per shard — one
    # canonical site
    return fn(q, k_arena, v_arena, block_tables, q_start, kv_live, q_lens)


def paged_attention_arrays(q, k_arena, v_arena, layer, block_tables, qpos,
                           q_start=None, kv_live=None, q_lens=None,
                           scale=None, mesh=None, tp_axis="tp",
                           k_scale=None, v_scale=None):
    """Attend q through the block table: Pallas ragged kernel when the
    backend gate and the ragged metadata allow it, XLA gather otherwise.
    `q_lens` (per-row live query counts) makes the kernel ragged in the
    QUERY dimension too — the unified step program's decode rows pay one
    query tile inside a wide mixed/verify-width launch. With a `mesh`
    (tensor-parallel serving, serving/sharded.py) the Pallas path runs
    per-shard over the head axis via `shard_map`; the XLA fallback needs
    no wrapper — GSPMD partitions the padded gather over the arena's
    head sharding on its own (and its causal qpos mask already discards
    dead query rows, so it ignores q_lens)."""
    why_xla = None
    if q_start is None or kv_live is None:
        why_xla = "no ragged metadata (q_start / kv_live)"
    elif scale is not None:  # kernel bakes 1/sqrt(D)
        why_xla = "custom softmax scale"
    elif not use_pallas():
        why_xla = "kernels off"
    if why_xla is not None:
        # off-TPU this is the default path; on a tpu backend it is
        # counted and logged, never quiet
        note_xla_fallback("paged_attention", why_xla)
        return paged_attention_xla(q, k_arena, v_arena, layer, block_tables,
                                   qpos, scale, k_scale=k_scale,
                                   v_scale=v_scale)
    if mesh is not None and mesh.shape.get(tp_axis, 1) > 1:
        return ragged_paged_attention_sharded(
            q, k_arena, v_arena, layer, block_tables, q_start, kv_live,
            q_lens=q_lens, mesh=mesh, tp_axis=tp_axis,
            interpret=interpret_mode(),
            k_scale=k_scale, v_scale=v_scale,
        )
    return ragged_paged_attention(
        q, k_arena, v_arena, layer, block_tables, q_start, kv_live,
        q_lens=q_lens, interpret=interpret_mode(),
        k_scale=k_scale, v_scale=v_scale,
    )
