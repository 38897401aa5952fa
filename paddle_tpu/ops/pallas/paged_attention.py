"""Ragged paged attention: Pallas TPU kernel + XLA gather fallback.

The serving engine's attention (PAPERS.md "Ragged Paged Attention"): K/V live
in a head-major block arena ``[layers, heads, num_blocks, block_size,
head_dim]`` and every batch row attends through its own block table. One
launch serves a MIXED batch — decode rows (1 live query token) next to
prefill-chunk rows (up to `prefill_chunk` tokens) — which is what lets the
engine run chunked prefill and decode in a single XLA program.

Kernel design (TPU):
- The arenas stay where they are (HBM, unblocked). The grid is what is
  not ragged: ``(rows, query tiles)``, every head a step. The walk over
  a row's KV pages is a loop INSIDE the kernel whose trip count is the
  row's live pages (scalar prefetch, SMEM) over the pages a fetch
  brings: each iteration copies a group of pages named by the row's
  block table, one strided ``[heads, block_size, head_dim]`` copy a page,
  into one of two VMEM buffers while the other is multiplied, and a
  tile's last iteration starts the next grid step's first fetch. A call
  costs its live work: the padded tail of a block table is neither
  fetched nor stepped over.
  This is the whole point vs. the XLA fallback below, which gathers the
  full padded ``[rows, max_blocks]`` table every layer.
- Query lengths are ragged PER ROW (``q_lens``): the query axis is tiled
  and a tile past a row's live tokens runs no iteration and writes zeros,
  so a decode row (1 live token) riding a wide mixed/verify-width program
  computes one query tile while a full prefill chunk in the same launch
  walks them all. A lane that carries no request runs nothing. This is
  what lets ONE program shape serve decode, prefill-chunk, and
  speculative-verify rows (the unified ragged step program in
  serving/engine.py).
- The layer is a scalar the kernel reads, so one built kernel (and one
  Mosaic body in a step program) serves every layer.
- Online-softmax state (m, l, acc) lives in VMEM scratch across the
  iterations, exactly like flash_attention.py; fp32 accumulation on the MXU.
- Causal masking is positional: query positions are ``q_start[row] + iota``
  (chunk tokens are consecutive), key positions follow from a page's place
  in the table; ``qpos >= kpos`` also discards the garbage tail of a
  partially filled last block and the slots of a last fetch past it.
- Grouped-query attention: the arena has the model's key/value heads,
  fewer than the query heads; a fetch brings each K/V head once and the
  query heads of its group are multiplied against it.
- Head-major arena so each (head, block) tile is a 2-D ``(block_size,
  head_dim)`` slab whose minor two dims a copy takes whole: Mosaic tiles
  the minor two dims of an array by (8, 128) (x2 rows for bf16, x4 for
  int8), which a head axis in second-to-minor position would cut across
  (same constraint that shapes flash_attention.py's [B*H, S, D] layout).
  For the same reason a head size that is no multiple of 128 stays on the
  XLA path when compiled: Mosaic pads such an arena's minor dimension in
  HBM and then refuses to slice it.

The dispatch (`paged_attention_arrays`) is the seam `serving/block_pool.py`
calls after appending the step's new K/V to the arena (by the block writer
of paged_kv_append.py wherever this kernel runs: `paged_kernels_off` is the
one rule for both): Pallas on TPU (or
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

    q: [B, S, H, D]; arenas: [layers, Hkv, num_blocks, block_size, D], Hkv
    dividing H (grouped-query attention: query head i reads K/V head
    ``i // (H / Hkv)``; the gathered keys are repeated by group here);
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
    Hkv = k_seq.shape[0]
    # back to the [B, L, H, D] layout of models/gpt.py's contiguous-cache
    # path so the einsum below is the exact same contraction (bit-parity
    # with GPT.generate is a serving acceptance criterion)
    k_seq = jnp.transpose(k_seq, (1, 2, 3, 0, 4)).reshape(B, L, Hkv, D)
    v_seq = jnp.transpose(v_seq, (1, 2, 3, 0, 4)).reshape(B, L, Hkv, D)
    if Hkv != H:
        k_seq = jnp.repeat(k_seq, H // Hkv, axis=2)
        v_seq = jnp.repeat(v_seq, H // Hkv, axis=2)
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

# Chosen on the chip (PERF.md section 6, PR 31): the keys one loop iteration
# fetches (whole pages of them) and the widest query tile. A grid step
# serves every head: fewer, larger copies beat more steps at every size.
_KV_FETCH_TOKENS = 512
_Q_TILE = 128
# Two slots of 512 keys of K and of V across 16 heads of 128 are 8 MiB, half
# of what Mosaic lets a kernel have unasked; more heads need more.
_VMEM_LIMIT_BYTES = 64 << 20


def _pages_per_fetch(bs):
    return max(1, _KV_FETCH_TOKENS // bs)


def _padded(nk, bs):
    """`nk` table slots rounded up to whole fetches."""
    pages = _pages_per_fetch(bs)
    return -(-nk // pages) * pages


def _q_tile(S):
    """Query-tile width: the whole width for narrow programs, `_Q_TILE`
    where it divides a wider one (Mosaic tiling: the minor two dims of a
    block are (8, 128)-divisible or equal to the array's). A width that
    is neither keeps one full-width tile."""
    return _Q_TILE if S > _Q_TILE and S % _Q_TILE == 0 else S


def _ragged_kernel(layer_ref, bt_ref, qs_ref, kl_ref, ql_ref, q_ref,
                   k_hbm, v_hbm, *rest, bs, qt, pages, scale, quant):
    """One (row, query tile)'s online-softmax walk over the row's live KV
    pages, every head: a loop whose trip count is the row's live page
    groups, `pages` pages a fetch, double-buffered.

    layer_ref/bt_ref/qs_ref/kl_ref/ql_ref are scalar-prefetched (SMEM):
    the layer, the block tables, and per row the first query position,
    the live KV pages and the live query tokens. `k_hbm`/`v_hbm` are the
    whole arenas, left where they are: iteration j copies pages
    ``bt[i, j*pages : (j+1)*pages]`` across heads (one strided
    ``[H, bs, D]`` copy a page) into one slot of the VMEM buffers while
    the other is multiplied, and a tile's last iteration starts the first
    fetch of the grid step that follows (`state` hands its slot over),
    so only a launch's first fetch is waited for in full. Slots past the
    row's last live page re-fetch that page (finite values, masked by
    position). A query tile past the row's live tokens, or a lane with
    none, runs no iteration and writes zeros (`kv_live` 0 says the same
    of a lane).

    ``quant`` (int8 arena): two f32 refs carry the row's scales a KEY
    (``[1, H, max_blocks * bs]``, block-table order), applied along the
    key axis of the scores and of the probabilities, so the int8 tiles
    go to the MXU as they are."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if quant:
        ksc_ref, vsc_ref, o_ref, *scratch = rest
    else:
        o_ref, *scratch = rest
    kbuf, vbuf, sem, state, m_ref, l_ref, acc_ref = scratch

    i = pl.program_id(0)   # batch row
    qb = pl.program_id(1)  # query tile
    rows, tiles = pl.num_programs(0), pl.num_programs(1)
    D = kbuf.shape[-1]
    # grouped-query attention: the arena holds H / G key/value heads, each
    # fetched once and multiplied against the G query heads of its group
    H = q_ref.shape[1]
    G = H // kbuf.shape[1]
    T = pages * bs
    layer = layer_ref[0]

    def fetches(row, tile):
        return jnp.where(tile * qt < ql_ref[row],
                         (kl_ref[row] + pages - 1) // pages, 0)

    def fetch(row, slot, j, wait=False):
        """Start, or wait for, the copies of row `row`'s j-th page group
        into `slot`: one copy a page and arena, a loop and not `pages`
        copies in a row, which the interpreter would take minutes over."""
        last = kl_ref[row] - 1

        def page_copies(p, carry):
            page = bt_ref[row, jnp.minimum(j * pages + p, last)]
            for s, (arena, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                copy = pltpu.make_async_copy(
                    arena.at[layer, :, page], buf.at[slot, :, p],
                    sem.at[s, slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, pages, page_copies, None)

    m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[:] = jnp.zeros_like(l_ref)
    acc_ref[:] = jnp.zeros_like(acc_ref)
    n_fetch = fetches(i, qb)
    # state: whether the grid step before this one started this one's
    # first fetch, and into which slot
    started = jnp.where((i == 0) & (qb == 0), 0, state[0])
    slot0 = jnp.where(started == 1, state[1], 0)
    state[0] = 0

    @pl.when((n_fetch > 0) & (started == 0))
    def _():
        fetch(i, 0, 0)

    # chunk query positions are consecutive from q_start; key positions
    # follow from the page's place in the table. qpos >= kpos is both the
    # causal mask and the guard over the last page's stale tail and over
    # the slots past it.
    qp = (qs_ref[i] + qb * qt
          + jax.lax.broadcasted_iota(jnp.int32, (qt, T), 0))
    kp0 = jax.lax.broadcasted_iota(jnp.int32, (qt, T), 1)

    def body(j, carry):
        slot = (slot0 + j) % 2

        @pl.when(j + 1 < n_fetch)
        def _():
            fetch(i, 1 - slot, j + 1)

        @pl.when(j + 1 == n_fetch)
        def _():
            last = qb + 1 == tiles
            row = jnp.where(last, i + 1, i)
            row_c = jnp.minimum(row, rows - 1)
            tile = jnp.where(last, 0, qb + 1)

            @pl.when((row < rows) & (fetches(row_c, tile) > 0))
            def _():
                fetch(row_c, 1 - slot, 0)
                state[0] = 1
                state[1] = 1 - slot

        fetch(i, slot, j, wait=True)
        live = qp >= j * T + kp0
        for h in range(H):
            q = q_ref[0, h]                                   # [qt, D]
            kt = kbuf[slot, h // G].astype(q.dtype).reshape(T, D)
            s = jax.lax.dot_general(
                q, kt, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if quant:
                keys = pl.ds(pl.multiple_of(j * T, T), T)
                s = s * ksc_ref[0, pl.ds(h // G, 1), keys]
            s = jnp.where(live, s, _NEG_INF)
            m_prev = m_ref[h]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            if quant:
                p = p * vsc_ref[0, pl.ds(h // G, 1), keys]
            vt = vbuf[slot, h // G].astype(q.dtype).reshape(T, D)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
        return carry

    jax.lax.fori_loop(0, n_fetch, body, None)
    o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def ragged_grid_steps(q_lens, kv_live, width, block_size):
    """`(live, total)` steps of one launch of the ragged kernel, a layer:
    what the engine's step span reports as ``grid_live`` /
    ``grid_total``. Host-side arithmetic over the numpy vectors the step
    is built from, kept beside the walk it counts (`_ragged_kernel`: grid
    ``(B, nq)``, a query tile live where ``qb * qt < q_lens[i]``, and a
    live tile runs ``ceil(kv_live[i] / pages)`` iterations) so that a
    change to the one changes the other.

    ``live`` is the loop iterations, each of which fetched a live row's
    pages; ``total`` adds the grid steps that ran none: query tiles past
    a row's live tokens, and every tile of a lane that carries no request
    (``q_lens`` 0)."""
    qt = _q_tile(width)
    q_tiles = -(-q_lens // qt)
    fetches = -(-kv_live // _pages_per_fetch(block_size))
    live = int(np.sum(q_tiles * fetches))
    return live, live + int(np.sum(width // qt - q_tiles))


@functools.lru_cache(maxsize=None)
def _build_ragged(B, H, Hkv, sq, d, bs, nk, dtype_name, kv_dtype_name,
                  interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    qt = _q_tile(sq)
    pages = _pages_per_fetch(bs)
    quant = kv_dtype_name == "int8"

    def tile(i, qb, *_):
        return (i, 0, qb, 0)

    in_specs = [
        pl.BlockSpec((1, H, qt, d), tile),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    if quant:
        row = pl.BlockSpec((1, Hkv, _padded(nk, bs) * bs),
                           lambda i, qb, *_: (i, 0, 0))
        in_specs += [row, row]
    kv_buf = pltpu.VMEM((2, Hkv, pages, bs, d), jnp.dtype(kv_dtype_name))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(B, sq // qt),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, qt, d), tile),
        scratch_shapes=[
            kv_buf, kv_buf,
            pltpu.SemaphoreType.DMA((2, 2)),       # (K or V, slot)
            pltpu.SMEM((2,), jnp.int32),           # next step's first fetch
            pltpu.VMEM((H, qt, 1), jnp.float32),   # running max m
            pltpu.VMEM((H, qt, 1), jnp.float32),   # running normalizer l
            pltpu.VMEM((H, qt, d), jnp.float32),   # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, bs=bs, qt=qt, pages=pages,
                          scale=1.0 / np.sqrt(d), quant=quant),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, sq, d), jnp.dtype(dtype_name)),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="ragged_paged_attention",
    )


def ragged_paged_attention(q, k_arena, v_arena, layer, block_tables,
                           q_start, kv_live, q_lens=None, interpret=False,
                           k_scale=None, v_scale=None):
    """Pallas ragged paged attention over live KV pages — and live
    QUERY tiles — only.

    q: [B, S, H, D]; arenas: [layers, Hkv, num_blocks, bs, D], Hkv dividing
    H (grouped-query attention: query head i reads K/V head
    ``i // (H / Hkv)``, which is fetched once for its whole group);
    block_tables: [B, max_blocks]; q_start: [B] first query position per
    row; kv_live: [B] number of live KV blocks per row; q_lens: [B] live
    query tokens per row (ragged widths — a decode row riding a wide
    program declares 1 and pays one query tile; None means every row is
    full-width). A lane that carries no request declares 0 of either
    and pays nothing. `k_scale`/`v_scale` [layers, H, num_blocks] switch
    the kernel to int8 arenas, dequantized by the scales inside it.
    `layer` may be traced: one built kernel serves every layer. Returns
    [B, S, H, D]. Query tiles with no live token hold zeros; positions
    past a row's live tokens inside a live tile hold garbage — the engine
    discards them.
    """
    B, S, H, D = q.shape
    bs = k_arena.shape[3]
    nk = block_tables.shape[1]
    fn = _build_ragged(B, H, k_arena.shape[1], S, D, bs, nk, str(q.dtype),
                       str(k_arena.dtype), bool(interpret))
    if q_lens is None:
        q_lens = jnp.full((B,), S, jnp.int32)
    operands = (jnp.transpose(q, (0, 2, 1, 3)), k_arena, v_arena)  # [B,H,S,D]
    if k_scale is not None:
        # each row's scales gathered through its block table (padded to
        # whole fetches) and repeated a key, [B, H, keys] f32: the kernel
        # takes one aligned lane slice of them an iteration
        tables = jnp.pad(block_tables, ((0, 0), (0, _padded(nk, bs) - nk)))

        def key_scales(sc):
            return jnp.repeat(jnp.transpose(sc[layer][:, tables],
                                            (1, 0, 2)), bs, axis=2)

        operands += (key_scales(k_scale), key_scales(v_scale))
    o = fn(
        jnp.asarray(layer, jnp.int32).reshape(1),
        block_tables.astype(jnp.int32),
        q_start.astype(jnp.int32),
        kv_live.astype(jnp.int32),
        q_lens.astype(jnp.int32),
        *operands,
    )
    return jnp.transpose(o, (0, 2, 1, 3))


# ---------------------------------------------------------------------------
# dispatch — the seam serving/block_pool.py calls
# ---------------------------------------------------------------------------

def paged_kernels_off(head_dim):
    """Why the paged arena's two kernels, the append's block writer
    (paged_kv_append.py) and the ragged walk, cannot run here, or None
    where they can. One rule for both dispatches
    (`serving/block_pool.py _append`, `paged_attention_arrays`), so an
    engine never appends by one and attends by another."""
    if not use_pallas():
        return "kernels off"
    if head_dim % 128 and not interpret_mode():
        # Mosaic (jaxlib 0.9.0) refuses to slice an HBM array whose minor
        # dimension it pads to the 128 lanes, which both kernels' block
        # copies do (PERF.md section 7)
        return "head size not a multiple of 128"
    return None


def ragged_paged_attention_sharded(q, k_arena, v_arena, layer, block_tables,
                                   q_start, kv_live, q_lens=None,
                                   mesh=None, tp_axis="tp",
                                   interpret=False,
                                   k_scale=None, v_scale=None):
    """Per-shard dispatch of the single-device ragged kernel on a tp mesh.

    The kernel copies pages across the heads of the local arena — it has
    no concept of a mesh. Under `shard_map` over the head axis each shard
    sees exactly its local
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
    # raw metadata passes through; ragged_paged_attention casts it per
    # shard — one canonical site
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
    if q_start is None or kv_live is None:
        why_xla = "no ragged metadata (q_start / kv_live)"
    elif scale is not None:  # kernel bakes 1/sqrt(D)
        why_xla = "custom softmax scale"
    else:
        why_xla = paged_kernels_off(q.shape[-1])
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
