"""The paged arena's block writer: the step's new K/V reach their blocks as
whole ``[heads, block_size, head_dim]`` copies, not as ``head_dim`` rows.

`serving/block_pool.py _append` is the dispatch: this kernel where the
ragged one runs (`paged_attention.paged_kernels_off`), the XLA row scatter
everywhere else. Same values at the same addresses: ``new[b, s, h, :]`` goes
to ``arena[layer, h, slots[b, s], offs[b, s], :]``.

What the kernel leans on (`BlockPool.positions_to_slots` gives it): a row's
live tokens are a prefix of the width at consecutive positions, so they
fill the end of one block, then whole blocks, then the start of a last one;
dead tokens and idle lanes carry slot 0, the null block, whose content is
scratch and which the kernel therefore never touches; no two rows write
one live block in a step.

Kernel design (TPU):
- The arenas stay in HBM, unblocked and aliased input to output, so they
  keep the one layout they have from the program's donated parameter to
  its output. One call serves every arena it is given (K and V: their
  block ids are the same) and, the layer being a scalar it reads, every
  layer.
- Grid ``(rows,)``. A row's tokens arrive head-major ``[H, S, D]`` through
  the pipeline; each head's are shifted by the row's first offset (one
  sublane rotate in f32) so that block j's tokens stand in rows
  ``[j * bs, (j + 1) * bs)``, cast back and stored into a staging block.
  A block the row covers whole needs no read; its first and its last
  block, when partial, are read first (`rd`) and laid under the tokens
  (no branch: a whole block's mask passes none of what was read).
  A row of at most a block's worth of tokens (a decode or verify row in
  a chunk-width program) shifts and lays out that many, not the width.
- Copies overlap across grid steps: row i + 1's partial blocks are read
  and row i - 1's staged blocks written back while row i is laid out
  (two slots each). Every copy is one strided ``[H, bs, D]`` block, the
  shape the ragged kernel's walk copies, which Mosaic's tiling of the
  minor two dims takes for bf16, f32 and int8 alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def blocks_per_row(width, block_size):
    """The most arena blocks `width` consecutive positions straddle."""
    return (width + block_size - 2) // block_size + 1


def append_blocks(slots):
    """Blocks one launch of the writer copies back, an arena a layer: what
    the engine's step span reports as ``append_blocks``. Host-side
    arithmetic over the numpy `slots` the step is built from, kept beside
    the kernel it counts: each row's live tokens sit in consecutive
    blocks, so a block is counted where a live slot differs from the
    slot before it."""
    before = np.concatenate([np.zeros_like(slots[:, :1]), slots[:, :-1]], 1)
    return int(np.count_nonzero((slots != 0) & (slots != before)))


def _append_kernel(layer_ref, slots_ref, offs_ref, *refs, n, bs, nb):
    """Row i's live tokens into its blocks, for each of `n` arenas.

    layer_ref/slots_ref/offs_ref are scalar-prefetched (SMEM): the layer
    and the step's scatter targets ``[rows, S]`` as they are, from which
    the kernel reads a row's first offset, its block ids (the slot of the
    first token that lands in each) and, once a launch, its live tokens
    (`cnt_ref`; 0: an idle lane, nothing is copied). `refs`: the `n` rows
    of new tokens ``[1, H, S, D]`` (VMEM, pipelined), the `n` arenas in
    and the same `n` out (HBM, aliased: the kernel reads and writes the
    outputs), then scratch: partial blocks read ``rd [n, 2, 2, H, bs, D]``
    (slot; first, last), blocks to write ``wr [n, 2, nb, H, bs, D]``, a
    rotate's staging rows ``[nb * bs, D]`` f32, the DMA semaphores
    ``[2, n, 2]`` (read or write; arena; slot) and `cnt_ref` ``[rows]``
    (SMEM)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    news, arenas = refs[:n], refs[2 * n:3 * n]
    rd, wr, stage, sem, cnt_ref = refs[3 * n:]
    i = pl.program_id(0)
    rows = pl.num_programs(0)
    H, S, D = news[0].shape[1:]
    layer = layer_ref[0]

    @pl.when(i == 0)
    def _():
        def count(row, carry):
            # live tokens are a prefix: halve [lo, hi) onto the first dead
            lo, hi = jnp.int32(0), jnp.int32(S)
            for _ in range(S.bit_length()):
                mid = jnp.minimum((lo + hi) // 2, S - 1)
                live = (lo < hi) & (slots_ref[row, mid] != 0)
                lo, hi = (jnp.where(live, mid + 1, lo),
                          jnp.where(live, hi, jnp.minimum(hi, mid)))
            cnt_ref[row] = lo
            return carry

        jax.lax.fori_loop(0, rows, count, None)

    def block_id(row, j):
        """The j-th block row `row`'s tokens fill: the slot of the first
        token that lands in it."""
        return slots_ref[row, jnp.clip(j * bs - offs_ref[row, 0], 0, S - 1)]

    def live_rows(row, j):
        """`(first offset, tokens)` row `row` writes in its j-th block;
        `j` static or traced."""
        off, cnt = offs_ref[row, 0], cnt_ref[row]
        lo = jnp.maximum(off - j * bs, 0)
        return lo, jnp.clip(cnt - jnp.maximum(j * bs - off, 0), 0, bs - lo)

    def live_blocks(row):
        return jnp.where(cnt_ref[row] > 0,
                         (offs_ref[row, 0] + cnt_ref[row] - 1) // bs + 1, 0)

    def run(copy, wait):
        if wait:
            copy.wait()
        else:
            copy.start()

    def reads(row, slot, wait=False):
        """Start, or wait for, the copies of row `row`'s first and last
        blocks into `rd[:, slot]`, where the row fills them in part."""
        jt = live_blocks(row) - 1
        # (slot of `rd`, block, whether it is another than the one before)
        for which, j, another in ((0, 0, True), (1, jt, jt > 0)):
            _, cnt = live_rows(row, j)

            @pl.when(another & (cnt > 0) & (cnt < bs))
            def _():
                for a in range(n):
                    run(pltpu.make_async_copy(
                        arenas[a].at[layer, :, block_id(row, j)],
                        rd.at[a, slot, which], sem.at[0, a, slot]), wait)

    def writes(row, slot, wait=False):
        """Start, or wait for, the copies of row `row`'s staged blocks
        `wr[:, slot]` to their homes."""
        def block(j, carry):
            for a in range(n):
                run(pltpu.make_async_copy(
                    wr.at[a, slot, j],
                    arenas[a].at[layer, :, block_id(row, j)],
                    sem.at[1, a, slot]), wait)
            return carry

        jax.lax.fori_loop(0, live_blocks(row), block, None)

    slot = i % 2

    @pl.when(i == 0)
    def _():
        reads(0, 0)

    @pl.when(i + 1 < rows)
    def _():
        reads(i + 1, 1 - slot)

    reads(i, slot, wait=True)

    @pl.when(i >= 2)
    def _():
        writes(i - 2, slot, wait=True)

    def lay(width, blocks):
        """The row's first `width` tokens into its first `blocks` blocks
        of `wr[:, slot]`, every head. Straight-line code: blocks past the
        row's last are staged too and never copied."""
        off, cnt = offs_ref[i, 0], cnt_ref[i]
        jt = live_blocks(i) - 1
        last = pl.ds(pl.multiple_of(jt * bs, bs), bs)
        at = jax.lax.broadcasted_iota(jnp.int32, (bs, D), 0)

        def over(under, tokens, j):
            """Block j: the row's tokens where it has any, `under` else."""
            live = (at + j * bs >= off) & (at + j * bs < off + cnt)
            return jnp.where(live, tokens, under.astype(jnp.float32))

        def head(h, carry):
            for a in range(n):
                # tokens to rows [off, off + width) of the staging rows:
                # block j's stand in [j * bs, (j + 1) * bs). Rows outside
                # hold what they held; `over` passes only live tokens.
                stage[0:width, :] = news[a][0, h, 0:width].astype(
                    jnp.float32)
                moved = pltpu.roll(stage[0:blocks * bs, :], off, 0)
                wr[a, slot, 0, h] = over(
                    rd[a, slot, 0, h], moved[0:bs], 0).astype(wr.dtype)
                if blocks > 1:
                    for j in range(1, blocks):
                        wr[a, slot, j, h] = moved[
                            j * bs:(j + 1) * bs].astype(wr.dtype)
                    # the last block again, over what it held (the first
                    # block's, where it is that one)
                    stage[0:blocks * bs, :] = moved
                    wr[a, slot, jt, h] = over(
                        rd[a, slot, jnp.minimum(jt, 1), h], stage[last, :],
                        jt).astype(wr.dtype)
            return carry

        jax.lax.fori_loop(0, H, head, None)

    # a decode row riding a wide program lays out one block's worth of
    # tokens, not the width (PERF.md section 6, PR 33: half a chunk
    # step's append in the benchmark's mix)
    few = min(S, bs)

    @pl.when((cnt_ref[i] > 0) & (cnt_ref[i] <= few))
    def _():
        lay(few, blocks_per_row(few, bs))

    if few < S:
        @pl.when(cnt_ref[i] > few)
        def _():
            lay(S, nb)

    writes(i, slot)

    @pl.when(i + 1 == rows)
    def _():
        @pl.when(i >= 1)
        def _():
            writes(i - 1, 1 - slot, wait=True)

        writes(i, slot, wait=True)


@functools.lru_cache(maxsize=None)
def _build_append(n, B, H, S, D, bs, arena_shape, dtype_name, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    nb = blocks_per_row(S, bs)
    dtype = jnp.dtype(dtype_name)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[pl.BlockSpec((1, H, S, D), lambda i, *_: (i, 0, 0, 0))] * n
        + [anywhere] * n,
        out_specs=[anywhere] * n,
        scratch_shapes=[
            pltpu.VMEM((n, 2, 2, H, bs, D), dtype),     # partial blocks read
            pltpu.VMEM((n, 2, nb, H, bs, D), dtype),    # blocks to write
            pltpu.VMEM((nb * bs, D), jnp.float32),      # a rotate's rows
            pltpu.SemaphoreType.DMA((2, n, 2)),
            pltpu.SMEM((B,), jnp.int32),                # live tokens a row
        ],
    )
    return pl.pallas_call(
        functools.partial(_append_kernel, n=n, bs=bs, nb=nb),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(arena_shape, dtype)] * n,
        # operands: 3 prefetched scalars, n rows of tokens, then the arenas
        input_output_aliases={3 + n + a: a for a in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_kv_append",
    )


def paged_kv_append(arenas, layer, news, slots, offs, interpret=False):
    """Pallas block writer: each of `news` ``[B, S, H, D]`` into its arena
    of `arenas` ``[layers, H, num_blocks, bs, D]`` at ``[layer, h,
    slots[b, s], offs[b, s], :]``, cast to the arena's dtype; the null
    block (slot 0) is left as it is. `slots`/`offs` ``[B, S]`` are
    `BlockPool.positions_to_slots`' (module docstring: live tokens a
    prefix at consecutive positions). The arenas share one shape and
    dtype, as K and V do. `layer` may be traced: one built kernel serves
    every layer. Returns the updated arenas, a tuple."""
    B, S, H, D = news[0].shape
    bs = arenas[0].shape[3]
    fn = _build_append(len(arenas), B, H, S, D, bs, tuple(arenas[0].shape),
                       str(arenas[0].dtype), bool(interpret))
    # little is traced here, and the kernel reads `slots`/`offs` as they
    # are: a program makes this call once a layer, and `LLMEngine.warmup()`
    # pays for every operation traced (PERF.md section 6, PR 33)
    return tuple(fn(
        np.full((1,), layer, np.int32) if isinstance(layer, int)
        else jnp.reshape(layer, (1,)).astype(jnp.int32),
        slots.astype(jnp.int32), offs.astype(jnp.int32),
        *(jnp.transpose(new.astype(arena.dtype), (0, 2, 1, 3))  # [B,H,S,D]
          for new, arena in zip(news, arenas)),
        *arenas))
