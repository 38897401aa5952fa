"""A grouped matmul for the routed experts of a serving step: rows of ``x``
sorted by group, group ``g`` multiplied with ``w[g]``, for the groups a
step's tokens hit and no others.

`models/nemotron_h.py LatentMoE.forward` is the caller: a decode step hands
it a few hundred (token, pick) rows spread over most of the held experts in
groups of one to fifteen, and three quarters of the rows belong to no group
(absent experts' pairs, dead slots). The time of such a product is the time
to read each hit expert's matrix from HBM once; the rows are nearly free.

`grouped_matmul(x [M, K], w [G, K, N], sizes [G]) -> [M, N] float32` is the
dispatch: this kernel where the kernels are on (`_backend.use_pallas`) and
`K`, `N` are multiples of 128, `jax.lax.ragged_dot` everywhere else. Inside
the groups the two agree (operands as given, float32 accumulation, float32
out); the kernel returns zeros in the rows behind the last group whatever
``x`` holds there. No backward pass: training's expert layer
(`distributed/moe.py`) computes its experts another way.

Kernel design (TPU):
- The walk is made inside the step program from `sizes` (`_visits`) and
  handed over by scalar prefetch: a VISIT is one (group, row tile) pair,
  groups in order, a group's row tiles in order, so a row tile's visits are
  consecutive and so are a group's. Behind the live visits come the row
  tiles that hold no group's row, once each, to be zeroed, and behind those
  the rest of the static grid, which repeats the last visit's block indices
  and does nothing: Pallas fetches a block only where its index changes,
  so an expert's matrix is read once however many row tiles its group
  straddles, an empty group's never, and a dead visit costs a grid step.
- Grid ``(N / tn, visits)``, ``visits = M / tm + G - 1`` (every row tile
  once and one more for each further group that can start inside one). A
  visit multiplies its row tile ``[tm, K]`` with the whole ``[K, tn]`` of
  its group's matrix on the matrix units and lays the rows that belong to
  the group over what the tile's earlier visits wrote; the first visit of
  a row tile lays them over zeros. The output tile stays in VMEM until the
  walk leaves it.
- The weights' block is the pipeline's, double-buffered: one expert's
  ``[K, tn]`` is in flight while the one before it is multiplied. With few
  rows a visit the matrix units are bound by loading the weights, not by
  the rows, so the row tile is as tall as costs nothing (`tiles`).

Measured on a TPU v5e (PERF.md section 6, PR 39): the ten products of a
decode step of the hybrid cell (704 rows, 85 of 128 experts hit, 4.66 GB)
take 7.1 ms where `ragged_dot` took 16.1, 80 % of the time their bytes need
at 819 GB/s by the host's clock and 91 % by the device's. The installed
`jax.experimental.pallas.ops.tpu.megablox.gmm` reaches the same with the
same tiles; it is not what runs here because its custom call is named `gmm`
after its own `jax.jit`, it leaves the rows behind the groups unwritten, its
grid's length is traced and it is built anew at every trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ._backend import interpret_mode, note_xla_fallback, use_pallas

# the pipeline's two copies of a weights block may take this much of VMEM
# (a v5e core has 128 MiB, of which the compiler grants 16 unasked)
_WEIGHT_BLOCKS_BYTES = 24 << 20


def tiles(M, K, N, G, dtype):
    """`(tm, tn)`: rows of a row tile and columns of a weights block, a
    function of the shapes alone; None where no block of the whole `K` fits.
    `tn`: the widest multiple of 128 dividing `N` whose two copies fit the
    budget, the whole matrix where it can be (one contiguous copy an
    expert). `tm`: 64, or all the rows where there are fewer, rounded up to
    the sublane tile of the operands' type. On a v5e at the hybrid cell's
    shapes (PERF.md section 6, PR 39; ms for the ten products of a step,
    704 rows in 85 groups / 3,520 rows in 128): row tiles of 16, 32, 64
    take 7.21, 7.08, 7.09 / 11.26, 10.87, 10.70 (a visit's time on the
    matrix units does not grow with its rows, and a taller tile is fewer
    visits), 128 rows 7.16; column blocks of 896 and 512 in place of the
    whole matrices 7.21 / 10.89."""
    itemsize = jnp.dtype(dtype).itemsize
    fits = [tn for tn in range(128, N + 1, 128)
            if N % tn == 0 and 2 * K * tn * itemsize <= _WEIGHT_BLOCKS_BYTES]
    if not fits:
        return None
    sublane = 32 // itemsize
    return min(64, -(-M // sublane) * sublane), max(fits)


def _visits(sizes, rows, tm):
    """The walk over `rows` rows (a multiple of `tm`) in groups of `sizes`:
    `(offsets [G + 1], group [V], tile [V], live [1])`, int32. Visits
    ``< live`` are the (group, row tile) pairs that hold rows, in order;
    then every row tile behind the last group's rows once; then the last
    tile again to the grid's end. A dead visit names the last live group
    (0 where there is none), so that it fetches nothing."""
    G, T = sizes.shape[0], rows // tm
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    visit_ends = jnp.cumsum(spans)
    live = visit_ends[-1]
    v = jnp.arange(T + G - 1, dtype=jnp.int32)
    # the group of live visit v: the first whose visits end behind v
    g = jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32)
    last = jnp.max(jnp.where(sizes > 0, jnp.arange(G, dtype=jnp.int32), 0))
    g = jnp.where(v < live, g, last)
    t_live = first[g] + v - (visit_ends[g] - spans[g])
    t_dead = jnp.minimum((ends[-1] + tm - 1) // tm + v - live, T - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return (offsets, g, jnp.where(v < live, t_live, t_dead),
            jnp.reshape(live, (1,)))


def _kernel(off_ref, group_ref, tile_ref, live_ref, x_ref, w_ref, o_ref):
    """Visit `v` of one column block: `x_ref` ``[tm, K]`` the visit's row
    tile, `w_ref` ``[K, tn]`` its group's block, `o_ref` ``[tm, tn]`` the
    row tile's output, resident over the tile's consecutive visits."""
    from jax.experimental import pallas as pl

    v = pl.program_id(1)
    tm, tn = o_ref.shape
    g, t = group_ref[v], tile_ref[v]
    first = (v == 0) | (t != tile_ref[jnp.maximum(v - 1, 0)])
    live = v < live_ref[0]

    @pl.when(live)
    def _():
        acc = jnp.dot(x_ref[...], w_ref[...],
                      preferred_element_type=jnp.float32)
        row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
        mine = (row >= off_ref[g]) & (row < off_ref[g + 1])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.where(mine, acc, 0.0)

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[...] = jnp.where(mine, acc, o_ref[...])

    @pl.when(jnp.logical_not(live) & first)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.lru_cache(maxsize=None)
def _build(rows, K, N, G, dtype_name, tm, tn, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    itemsize = jnp.dtype(dtype_name).itemsize
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(N // tn, rows // tm + G - 1),
        in_specs=[
            pl.BlockSpec((tm, K), lambda n, v, off, g, t, live: (t[v], 0)),
            pl.BlockSpec((None, K, tn),
                         lambda n, v, off, g, t, live: (g[v], 0, n)),
        ],
        out_specs=pl.BlockSpec((tm, tn),
                               lambda n, v, off, g, t, live: (t[v], n)),
    )
    # two copies each of the weights' block, the row tile and the output
    # tile, the product before it is laid down, and room for the compiler
    need = (2 * K * tn * itemsize + 2 * tm * K * itemsize
            + 3 * tm * tn * 4 + (8 << 20))
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, N), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=max(need, 16 << 20)),
        interpret=interpret,
        name="grouped_matmul",
    )


def grouped_matmul_kernel(x, w, sizes, interpret=False, tiling=None):
    """The Pallas path of `grouped_matmul`, whatever the backend: `K` and
    `N` multiples of 128 (`tiles` not None). `tiling` overrides `tiles`
    (the chip's sweep and the tests' small row tiles)."""
    M, K = x.shape
    G, _, N = w.shape
    tm, tn = tiling or tiles(M, K, N, G, x.dtype)
    rows = -(-M // tm) * tm
    if rows != M:
        x = jnp.pad(x, ((0, rows - M), (0, 0)))
    out = _build(rows, K, N, G, str(x.dtype), tm, tn, bool(interpret))(
        *_visits(sizes, rows, tm), x, w)
    return out if rows == M else out[:M]


def _why_xla(x, w):
    if not use_pallas():
        return "kernels off"
    M, K = x.shape
    G, _, N = w.shape
    if K % 128 or N % 128:
        return "a width that is no multiple of 128"
    if x.dtype != w.dtype:
        return "operands of two types"
    if tiles(M, K, N, G, x.dtype) is None:
        return "no block of the whole inner width fits VMEM"
    return None


def grouped_matmul(x, w, sizes):
    """``x`` ``[M, K]``, rows sorted by group; ``w`` ``[G, K, N]``;
    ``sizes`` ``[G]`` int32: group ``g`` owns the ``sizes[g]`` rows behind
    those of the groups before it. Returns ``[M, N]`` float32: inside the
    groups ``jax.lax.ragged_dot(x, w, sizes,
    preferred_element_type=float32)``; what the rows behind the last group
    hold is zeros on the kernel's path and whatever `ragged_dot` leaves on
    the other: read neither. Not differentiable."""
    why = _why_xla(x, w)
    if why is not None:
        # off-TPU this is the default path; on a tpu backend it is counted
        # and logged, never quiet
        note_xla_fallback("grouped_matmul", why)
        return jax.lax.ragged_dot(x, w, sizes,
                                  preferred_element_type=jnp.float32)
    return grouped_matmul_kernel(x, w, sizes, interpret=interpret_mode())
