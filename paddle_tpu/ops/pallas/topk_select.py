"""The positions of the `k` largest scores of each row, as a set, without a
sort: the full layers' key selection of `models/dots3.py` (`select_keys`).

`topk_select(scores [Q, K], qpos [Q], k) -> chosen [Q, k]` int32 is the
dispatch. A query at `qpos` ranks the slots `s <= qpos`; the others count as
`-inf`. The result is the set `jax.lax.top_k(where(visible, scores, -inf),
k)` names, ties included: every slot above the `k`-th largest score, then
the slots equal to it, lowest position first, until `k` are taken (so a
query that sees fewer than `k` slots takes them all and the lowest invisible
ones). It is listed in ascending position, not in `top_k`'s order: the
softmax over the rows it gathers does not depend on their order, and the
masks made from it are sets.

This kernel where the kernels are on (`_backend.use_pallas`), `scores` are
float32, `K` a multiple of 128 and `k < K`; `jax.lax.top_k` everywhere
else (`k >= K` selects every slot, as it did).

Kernel design (TPU), `QB` queries a grid step, a query's `K` slots laid out
`[K / 128, 128]` so that no axis of a block is one row tall:
- Each score becomes its order-preserving int32 image (`order_key`); the
  invisible slots get `-inf`'s.
- THRESHOLD: the `k`-th largest image, built bit by bit from the top by
  bisection: 32 passes over the block, each a compare and a sum over the
  query's slots, no data moving across lanes. The block stays in VMEM for
  all of them.
- RANK: a slot's place among the chosen, as exclusive prefix counts: within
  a 128-slot row a product with a 0/1 triangular matrix, the rows' offsets
  a product with another over the query's rows, both exact in float32 (0/1
  operands, counts below 2^24). Ties at the threshold are ranked first
  among themselves, so that the lowest positions are taken.
- COMPACTION: every chosen slot moves down by `d` = its position - its
  rank, bit by bit from the lowest (shifts by 1, 2, 4, ... slots, lane and
  sublane rotations): a stream compaction network in which two slots never
  land on one place. What a place holds is `d` alone: at the end the slot
  at place `j` came from `j + d`.

Measured on a TPU v5e: PERF.md section 5 (PR 41).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._backend import interpret_mode, note_xla_fallback, use_pallas

LANES = 128
# queries a grid step: one float32 sublane tile of them
QB = 8
_INT_MIN = -(1 << 31)
# `order_key(-inf)`: the bits 0xff800000 with the low 31 flipped
_NEG_INF_KEY = 0x807FFFFF - (1 << 32)


def order_key(x):
    """float32 -> int32 whose signed order is `jax.lax.top_k`'s order of
    the floats (`-0.0` below `+0.0`, `-inf` lowest of the finite and
    infinite)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def _count(mask):
    """`[QB, R, L]` bool -> `[QB, 1, 1]` float32: how many are set a query.
    Rows first (adds of whole vregs), then one lane reduction a query: on a
    v5e the kernel takes 0.135 ms for 64 queries so, 0.179 with the lanes
    reduced first (PERF.md section 5, PR 41)."""
    return jnp.sum(jnp.sum(mask.astype(jnp.float32), axis=1, keepdims=True),
                   axis=2, keepdims=True)


def _exclusive_prefix(mask):
    """`[QB, R, L]` bool -> float32: how many slots before each one, in the
    query's order (row-major), are set. Two products on the matrix units with
    0/1 operands: exact."""
    qb, R, L = mask.shape
    x = mask.astype(jnp.bfloat16).reshape(qb * R, L)
    i = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # `[U | 1]`: the slots before each in its row, and the row's total
    ut = jnp.concatenate([(i < j), jnp.ones((L, L), bool)], axis=1)
    both = jnp.dot(x, ut.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    within = both[:, :L].reshape(qb, R, L)
    totals = both[:, L:].reshape(qb, R, L).astype(jnp.bfloat16)   # <= 128
    lower = (jax.lax.broadcasted_iota(jnp.int32, (qb, R, R), 1)
             > jax.lax.broadcasted_iota(jnp.int32, (qb, R, R), 2))
    rows = jnp.einsum("qij,qjl->qil", lower.astype(jnp.bfloat16), totals,
                      preferred_element_type=jnp.float32)
    return within + rows


def _shift_down(d, step):
    """`out[p] = d[p + step]` over a query's slots in row-major order, -1
    where `p + step` lies behind the last."""
    from jax.experimental.pallas import tpu as pltpu

    qb, R, L = d.shape
    row = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, d.shape, 2)
    if step < L:
        here = pltpu.roll(d, L - step, 2)         # d[r, (l + step) % L]
        below = pltpu.roll(here, R - 1, 1)        # the same from row r + 1
        out = jnp.where(lane < L - step, here, below)
        return jnp.where((row == R - 1) & (lane >= L - step), -1, out)
    rows = step // L
    return jnp.where(row >= R - rows, -1, pltpu.roll(d, R - rows, 1))


def _kernel(k, bits, qpos_ref, s_ref, o_ref, key_ref):
    """One block of `QB` queries: `s_ref` `[QB, R, 128]` their scores,
    `qpos_ref` `[QB, 1, 128]` their positions (every lane the same),
    `o_ref` `[QB, ceil(k / 128), 128]` the chosen positions, ascending."""
    qb, R, L = s_ref.shape
    pos = (jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 1) * L
           + jax.lax.broadcasted_iota(jnp.int32, s_ref.shape, 2))
    key_ref[...] = jnp.where(pos <= qpos_ref[...], order_key(s_ref[...]),
                             _NEG_INF_KEY)

    def bisect(i, t):
        # the largest `t` such that at least `k` keys are `>= t`, its bits
        # from the top; `t ^ bit` sets bit `31 - i` of `t - INT_MIN`
        cand = t ^ jnp.left_shift(jnp.int32(1), 31 - i)
        return jnp.where(_count(key_ref[...] >= cand) >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, bisect,
                          jnp.full((qb, 1, 1), _INT_MIN, jnp.int32))
    key = key_ref[...]
    above, tied = key > t, key == t
    take = above | (tied & (_exclusive_prefix(tied) < k - _count(above)))
    d = jnp.where(take, pos - _exclusive_prefix(take).astype(jnp.int32), -1)
    for s in range(bits):
        src = _shift_down(d, 1 << s)
        moves_in = (src >= 0) & (((src >> s) & 1) == 1)
        moves_out = (d >= 0) & (((d >> s) & 1) == 1)
        d = jnp.where(moves_in, src, jnp.where(moves_out, -1, d))
    out = o_ref.shape[1]
    o_ref[...] = d[:, :out, :] + pos[:, :out, :]


@functools.lru_cache(maxsize=None)
def _build(Q, K, k, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, out = K // LANES, -(-k // LANES)
    # no slot moves further than K - k: the compaction's stages
    bits = max(K - k, 1).bit_length()
    block = QB * R * LANES * 4
    return pl.pallas_call(
        functools.partial(_kernel, k, bits),
        grid=(Q // QB,),
        in_specs=[pl.BlockSpec((QB, 1, LANES), lambda q: (q, 0, 0)),
                  pl.BlockSpec((QB, R, LANES), lambda q: (q, 0, 0))],
        out_specs=pl.BlockSpec((QB, out, LANES), lambda q: (q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, out, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((QB, R, LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            # the block twice, its keys and the compiler's temporaries (it
            # compiles in 4 MiB at the cell's shapes, not in 3). No more:
            # the limit is taken from the VMEM the program around it may
            # keep arrays in, and a chunk row's 22 MB of latent rows, which
            # its tiles gather from, then went to HBM and the gather took
            # four times as long (PERF.md section 6, PR 41)
            vmem_limit_bytes=8 * block + (1 << 20)),
        interpret=interpret,
        name="topk_select",
    )


def topk_select_kernel(scores, qpos, k, interpret=False):
    """The Pallas path of `topk_select`, whatever the backend: float32
    scores, `K` a multiple of 128, `k < K`."""
    Q, K = scores.shape
    rows = -(-Q // QB) * QB
    if rows != Q:
        # padded queries see nothing; their result is cut away
        scores = jnp.pad(scores, ((0, rows - Q), (0, 0)))
        qpos = jnp.pad(qpos, (0, rows - Q), constant_values=-1)
    q = jnp.broadcast_to(qpos.astype(jnp.int32)[:, None, None],
                         (rows, 1, LANES))
    out = _build(rows, K, k, bool(interpret))(
        q, scores.reshape(rows, K // LANES, LANES))
    return out.reshape(rows, -1)[:Q, :k]


def _why_xla(scores):
    if not use_pallas():
        return "kernels off"
    if scores.dtype != jnp.float32:
        return f"{scores.dtype} scores"
    if scores.shape[-1] % LANES:
        return "a context that is no multiple of 128"
    return None


def topk_select(scores, qpos, k):
    """`chosen [Q, k]` int32: the positions `jax.lax.top_k(where(s <= qpos,
    scores, -inf), k)` chooses from each row of `scores` `[Q, K]`, as a
    set (ascending on the kernel's path, `top_k`'s order on the other).
    `k >= K` takes every slot."""
    K = scores.shape[-1]
    if k < K:
        why = _why_xla(scores)
        if why is None:
            return topk_select_kernel(scores, qpos, k,
                                      interpret=interpret_mode())
        # off-TPU this is the default path; on a tpu backend it is counted
        note_xla_fallback("topk_select", why)
    visible = jnp.arange(K)[None, :] <= qpos[:, None]
    return jax.lax.top_k(jnp.where(visible, scores, -jnp.inf), min(k, K))[1]
