"""Paged KV cache: a global block arena + per-sequence block tables.

The TPU-native answer to vLLM's PagedAttention (PAPERS.md "Ragged Paged
Attention"): K/V live in ONE fixed-shape, head-major arena
``[layers, heads, num_blocks, block_size, head_dim]`` (`heads` the model's
key/value heads: all of them, or fewer than its query heads under
grouped-query attention) and every sequence owns a list of block ids. Head-major is the Pallas-friendly layout: each
(layer, head, block) slice is a contiguous ``[block_size, head_dim]`` tile
the ragged kernel DMAs straight from HBM (ops/pallas/paged_attention.py).
Appending tokens (`_append`) is a fixed-shape write that keeps the arena in
the kernel's layout: on TPU a Pallas writer of whole blocks
(ops/pallas/paged_kv_append.py), everywhere else an ``.at[...].set``
scatter of ``head_dim`` rows; attention runs through
`PagedLayerView.attend`, which dispatches to the ragged Pallas kernel on
TPU and to an XLA gather of the padded ``[rows, max_blocks]`` block table
everywhere else. Because every device op has a static shape, the whole
mixed prefill+decode serve compiles to two programs — no shape ever depends
on how many requests are in flight or how long they are.

A model whose cache rows are not a head's K and V declares its CACHE KINDS
(`CacheKind`; models/dots3.py: a latent row a token shared by the heads, an
index key, a window layer's latent row) and the pool owns an arena a kind in
place of the pair: headless arenas ``[slabs, num_blocks, block_size, width]``
under the same block ids and tables (`rows_append`, `rows_gather`,
`blocks_gather`: plain XLA scatters and gathers of rows), and for a kind a
window bounds a ring a lane among the lane state. The two Pallas kernels walk
the K/V pair only.

Block 0 is the NULL block: the allocator never hands it out, and every
padded/inactive scatter is routed there, so out-of-range writes can never
corrupt a live sequence. Reads through padding gather garbage from block 0,
which the causal ``kpos <= qpos`` mask then discards.

Host-side bookkeeping (the free list) is plain Python — allocation decisions
are scheduling, not device work.

**Automatic prefix caching** (vLLM-style) lives entirely in this host-side
bookkeeping: every block carries a refcount, and FULL blocks (all
``block_size`` token slots written) can be published under a chained
content hash — ``h_i = hash((h_{i-1}, tokens of block i))`` — into a
hash→block index. A published block whose refcount drops to zero moves to
a **cached-free LRU tier** instead of the truly-free list: its KV stays
valid and `match_prefix` can hand it to a later request with the same
token prefix (refcount goes back up, the prefill skips those tokens).
``num_free`` counts BOTH tiers; `allocate` pops truly-free blocks first
and evicts cached blocks oldest-first only when the free list runs dry,
so caching never reduces the pool's usable capacity. Writes into a block
shared by several sequences go through copy-on-write (`copy_blocks` +
the scheduler's `_ensure_writable`).
"""
from __future__ import annotations

import hashlib
from collections import OrderedDict
from typing import NamedTuple

import numpy as np

from ..core.tensor import Tensor
from . import faults


def blocks_for(num_tokens, block_size):
    """KV blocks `num_tokens` tokens occupy (>= 1) — THE worst-case
    ceiling formula: `BlockPool.blocks_for` and the engine's
    construction-time `kv_hbm_bytes` gate (which runs before the pool
    exists) both delegate here so admission and construction bounds can
    never drift apart."""
    return max(1, -(-int(num_tokens) // int(block_size)))


class CacheKind(NamedTuple):
    """One kind of cache a model keeps a token: what `BlockPool` owns an
    arena of. `slabs` layers of it (the arena's leading axis), rows of
    `width` numbers in the pool's type. With `heads` the arena
    is the head-major K/V layout `[slabs, heads, blocks, block_size, width]`
    the paged kernels walk; without, a token's row is shared by the heads
    (a latent, an index key): `[slabs, blocks, block_size, width]`,
    addressed by the same block ids and tables. `window` (positions a query
    may see, its own included) bounds the kind's lifetime: the pool then
    keeps no blocks of it but a RING a lane, `[slabs, lanes, ring, width]`
    with `ring = max(window - 1, chunk)` (the positions behind the step's
    first; a step's own rows are in hand), held, donated and reset as the
    lane state it is: its bytes do not grow with the sequence."""
    name: str
    slabs: int
    width: int
    heads: int | None = None
    window: int | None = None


def _paged_arena(name):
    """The arena `paged[name]` as an attribute: how the pair `k`, `v` of a
    model that declares no kinds is still read and replaced by name."""
    return property(lambda self: self.paged[name],
                    lambda self, arena: self.paged.__setitem__(name, arena))


def kv_pair(slabs, heads, head_dim):
    """The kinds of a model that declares none: a head's keys and values."""
    return (CacheKind("k", slabs, head_dim, heads),
            CacheKind("v", slabs, head_dim, heads))


def rows_append(arena, slab, rows, slots, offs):
    """Write a step's rows `[b, s, width]` to `arena[slab, slots, offs]` of a
    headless arena `[slabs, blocks, block_size, width]`: one row scatter on
    the arena seen as rows (a bitcast). Dead slots carry slot 0, the null
    block."""
    slabs, blocks, bs, width = arena.shape
    flat = (slab * blocks + slots) * bs + offs
    return arena.reshape(-1, width).at[flat.reshape(-1)].set(
        rows.reshape(-1, width).astype(arena.dtype)).reshape(arena.shape)


def rows_gather(arena, slab, table, positions):
    """Rows `[..., width]` at `positions` `[...]` of the sequence whose
    block table is `table` `[max_blocks]`, out of a headless arena."""
    slabs, blocks, bs, width = arena.shape
    flat = (slab * blocks + table[positions // bs]) * bs + positions % bs
    return arena.reshape(-1, width)[flat]


def blocks_gather(arena, slab, table):
    """Every row the block table `table` `[max_blocks]` names, in the
    sequence's order `[max_blocks x block_size, width]`, out of a headless
    arena: whole blocks (rows behind the sequence's last are the null
    block's, or stale: the caller masks by position)."""
    slabs, blocks, bs, width = arena.shape
    return arena.reshape(-1, bs, width)[slab * blocks + table].reshape(
        -1, width)


def positions_to_slots(block_size, blocks, start, count, width):
    """(slots[width], offs[width]) scatter targets for token positions
    [start, start+count) of a sequence that owns `blocks`; positions
    beyond `count` go to the null block. `width` is the padded step
    width. THE formula the append's writer leans on
    (ops/pallas/paged_kv_append.py): live tokens are a prefix of the
    width, at consecutive positions."""
    pos = np.arange(width)
    idx = (start + pos) // block_size
    offs = ((start + pos) % block_size).astype(np.int32)
    btab = np.asarray(blocks, np.int64)
    valid = (pos < count) & (idx < len(btab))
    slots = np.where(valid, btab[np.minimum(idx, len(btab) - 1)], 0)
    return slots.astype(np.int32), np.where(valid, offs, 0).astype(np.int32)


def chain_block_hashes(token_ids, block_size, salt=None):
    """Chained content digests of each FULL block of `token_ids`.

    ``h_i = sha256(h_{i-1} || tokens[i*bs:(i+1)*bs])`` (empty seed), so a
    block's digest commits to the ENTIRE token prefix through its last
    token — two sequences share digest i iff their first
    ``(i+1)*block_size`` tokens are identical. The trailing partial block
    (if any) gets no digest: only immutable full blocks are shareable.
    A real cryptographic digest, NOT Python's builtin ``hash``: the index
    serves KV across requests, so an engineerable collision would silently
    hand one prompt another prompt's KV blocks (the vLLM prefix-cache
    collision advisory, CVE-2025-25183).

    ``salt`` seeds the chain (models/lora.py adapter serving: a
    sequence's KV depends on the adapter its tokens ran under, so the
    same prompt under different adapters must NEVER share blocks — the
    engine salts with the request's adapter name).
    """
    bs = int(block_size)
    hashes = []
    h = b"" if salt is None else str(salt).encode("utf-8")
    for i in range(len(token_ids) // bs):
        m = hashlib.sha256(h)
        m.update(np.asarray(token_ids[i * bs:(i + 1) * bs],
                            np.int64).tobytes())
        h = m.digest()
        hashes.append(h)
    return hashes


class PagedLayerView:
    """One layer's window onto a threaded-through paged forward, and the
    part of that layer which is serving's to decide.

    The model's attention and MLP receive this as their `cache` argument
    and hand it three jobs: `attend` (how K/V reach the arena and how the
    block table is read), `column_parallel` (which adapter rows a lane
    adds) and `row_parallel` (which collective sums the tp partials). The
    arena arrays live on the shared `state` so each layer's update feeds
    the next layer's trace; the gates live there too, not on the model's
    modules, so ONE model serves quantized, adapter-enabled and plain
    engines at once, the training path never sees them, and an engine
    without them traces the byte-identical program it always has.
    """

    is_paged = True

    def __init__(self, state, layer):
        self.state = state
        self.layer = layer

    def attend(self, q, k_new, v_new):
        """Append `k_new`/`v_new` into the arena and attend `q` through
        the block table, a group of the step's rows at a time (one group:
        the tensors as they are). Tensors [b, s, heads, head_dim] in the
        step's layout in and out, all shapes static. The attention itself
        is ops/pallas/paged_attention.py's dispatch: ragged Pallas kernel
        over live blocks on TPU, padded XLA gather elsewhere."""
        st = self.state
        q, k_new, v_new = q._array, k_new._array, v_new._array
        if st.mesh is not None:
            # tensor-parallel serving: pin the step's new K/V (and q) to
            # the head sharding BEFORE the append, so each chip quantizes
            # and writes its own head slab of the arena and GSPMD invents
            # no gather
            q = st.constrain(q, None, None, "tp", None)
            k_new = st.constrain(k_new, None, None, "tp", None)
            v_new = st.constrain(v_new, None, None, "tp", None)
        # a later group reads an arena an earlier one has written, which
        # is harmless: a request is in one group a step
        return Tensor._from_op(st.per_group(self._attend_group,
                                            q, k_new, v_new))

    def _attend_group(self, g, q, k_new, v_new):
        """One group's append and attention: arrays `[rows, width, heads,
        head_dim]` beside the group's own row metadata."""
        from ..ops.pallas.paged_attention import paged_attention_arrays

        st, layer = self.state, self.layer
        if st.k_scale is not None:
            # int8 arena: quantize at the append, scales growing per
            # touched block (dequant happens inside the Pallas kernel /
            # before the XLA fallback's einsum)
            st.k, st.k_scale = _quantize_scatter(
                st.k, st.k_scale, layer, k_new, g.slots, g.offs,
                g.touched, g.touch_idx, st.mesh)
            st.v, st.v_scale = _quantize_scatter(
                st.v, st.v_scale, layer, v_new, g.slots, g.offs,
                g.touched, g.touch_idx, st.mesh)
        else:
            st.k, st.v = _append((st.k, st.v), layer, (k_new, v_new),
                                 g.slots, g.offs, st.mesh)
        return paged_attention_arrays(
            q, st.k, st.v, layer, g.block_tables, g.qpos,
            q_start=g.q_start, kv_live=g.kv_live, q_lens=g.q_lens,
            mesh=st.mesh, k_scale=st.k_scale, v_scale=st.v_scale,
        )

    def column_parallel(self, layer, x, op_name):
        """ColumnParallel projection `layer(x)`, with each lane's LoRA
        delta added when the state carries gathered adapter rows for
        `op_name` (models/lora.py: ``y + x @ A[slot] @ B[slot]``, slot 0
        all-zeros = base). The delta inherits the base output's tp layout
        from B's sharded out axis (no new collectives)."""
        y = layer(x)
        st = self.state
        if st.groups[0].lora is None or op_name not in st.groups[0].lora:
            return y
        from ..models.lora import apply_adapter_rows

        delta = st.per_group(
            lambda g, xg: apply_adapter_rows(xg, *g.lora[op_name],
                                             self.layer), x._array)
        return Tensor._from_op(y._array + delta)

    def row_parallel(self, layer, x, op_name):
        """RowParallel output projection `layer(x)`: routed through the
        EQuARX-quantized collective (serving/sharded.py
        `quantized_row_parallel`: int8 payload + per-shard scale instead
        of the f32 psum) when the state gates `op_name` on. GSPMD's
        implicit training-mesh all-reduce has no jnp-level seam to
        quantize, which is why this is the cache's job."""
        st = self.state
        if st.mesh is not None and op_name in st.quant_collectives:
            from .sharded import quantized_row_parallel

            return Tensor._from_op(quantized_row_parallel(
                x._array, layer.weight._array,
                None if layer.bias is None else layer.bias._array,
                st.mesh))
        return layer(x)


class PagedGroup:
    """One group of a step's rows: a rectangle `[rows, width]` of token
    slots at `offset` on the step's token axis, with the row metadata the
    parts that mix along a sequence need (all fixed-shape, jnp):

      block_tables  [rows, max_blocks] int32 (padded with 0 = null block)
      slots         [rows, width] int32 — destination block id of each
                    new token
      offs          [rows, width] int32 — destination offset inside it
      qpos          [rows, width] int32 — absolute position of each query
                    token (also the model's position-embedding indices)
      q_start       [rows] int32 — first live query position per row
                    (ragged kernel metadata; chunk tokens are consecutive)
      kv_live       [rows] int32 — live KV blocks per row (0: an idle
                    row); the ragged kernel walks exactly this many
      q_lens        [rows] int32 — live query tokens per row (ragged
                    widths: a decode row riding a wide launch declares 1
                    and the kernel computes one query tile)

    An int8 arena (``kv_dtype="int8"`` on the pool) adds:
      touched       [rows, T] int32 — the block ids this step's scatter
                    can write per row, slot 0 reserved for the null block
                    (padded tokens route their scale updates there)
      touch_idx     [rows, width] int32 — each fed token's index into its
                    row's `touched` list (0 = the null slot)
    LoRA (models/lora.py) adds `lora`, the adapter rows already gathered
    for THIS group's rows: {target op -> (a_rows [rows, L, in, r], b_rows
    [rows, L, r, out])}, or None (no adapters in the program).
    State a lane holds (`PagedState.lane_state`) adds:
      fresh         [rows] int32 — 1 where the row starts a sequence (its
                    first chunk, also after preemption): it starts from
                    zeros
    and a group behind the decode group has:
      lanes         [rows] int32 — the decode group's row each row stands
                    in for: where its request is decided (the engine's
                    sampler) and, where lanes hold state, the lane whose
                    state it carries (an idle row: one past the last lane,
                    which a scatter drops). None in the decode group: row
                    i IS lane i, and the state is used in place
    """

    def __init__(self, offset, rows, width, block_tables, slots, offs, qpos,
                 q_start, kv_live, q_lens, touched=None, touch_idx=None,
                 lora=None, fresh=None, lanes=None):
        self.offset, self.rows, self.width = offset, rows, width
        self.block_tables = block_tables
        self.slots = slots
        self.offs = offs
        self.qpos = qpos
        self.q_start = q_start
        self.kv_live = kv_live
        self.q_lens = q_lens
        self.touched = touched
        self.touch_idx = touch_idx
        self.lora = lora
        self.fresh = fresh
        self.lanes = lanes

    def live_slots(self):
        """`[rows, width]` bool: which slots carry a token. A row's live
        tokens are a prefix (`q_lens`); a row without a request has no live
        block (`kv_live` 0) and carries none."""
        import jax.numpy as jnp

        return ((jnp.arange(self.width)[None, :] < self.q_lens[:, None])
                & (self.kv_live[:, None] > 0))


class PagedState:
    """Traced arena + step metadata threaded through GPT.forward.

    A step's tokens lie on ONE token axis made of `groups` (`PagedGroup`),
    each a small rectangle of rows with metadata of its own: the decode
    group (the rows that feed one token, or one and their drafts) and,
    in a step that carries a prompt chunk, the chunk group behind it.
    What is a function of one token (embedding, norms, projections,
    feed-forward, experts, the head) the model computes once over the
    whole axis, in the step's LAYOUT: `[rows, width, ...]` where there
    is one group (the rectangle itself, no operation added), `[1, T,
    ...]` with the groups' slots side by side where there are more. What
    mixes along a sequence runs a group at a time (`per_group`) on that
    group's static slice reshaped to `[rows, width, ...]`: attention
    (`PagedLayerView.attend`), LoRA's per-row adapters, a state-space
    layer's scan.

    Arrays (all fixed-shape, jnp):
      paged         {kind: arena} — the pool's arena of every cache kind the
                    model declares that lives in blocks (`CacheKind`), in the
                    pool's order. A model that declares none has the pair
      k, v          [slabs, heads, num_blocks, block_size, head_dim] (also
                    `paged["k"]`, `paged["v"]`); a slab is one layer's cache
                    (a looped model's: one (pass, layer)'s, the model's
                    `kv_slabs`). A kind a WINDOW bounds is a ring a lane and
                    travels in `lane_state` under its name
      qpos          int32, in the step's layout — absolute position of each
                    token (the model's position-embedding indices)
      k_scale, v_scale  [layers, heads, num_blocks] float32 — an int8
                    arena's per-block per-head dequant scales (the
                    head-major arena's natural sidecar). None on float
                    engines.

    Lane state (a model that keeps state a lane which no position
    addresses, e.g. a state-space layer's; models/nemotron_h.py) adds:
      lane_state    {name: [layers, lanes, ...]} — the pool's arrays of
                    that state, one row a lane; the model reads and replaces
                    them (a group says whose rows it carries,
                    `PagedGroup.lanes`), and they travel with the arenas
                    (`arenas()`)
    and the model leaves on `step_stats` the int32 vector of counts its
    `step_stat_names` names, which the engine hands back to the host.

    `mesh` (static, not an array) is the tensor-parallel serving mesh
    (serving/sharded.py) or None: it selects the per-shard Pallas dispatch
    and lets `constrain` pin traced activations to the tp layout.
    `quant_collectives` (static frozenset) names the RowParallel output
    projections whose tp all-reduce runs quantized (serving/sharded.py
    `quantized_row_parallel`); `PagedLayerView.row_parallel` consults it
    per op.
    """

    is_paged = True

    def __init__(self, paged, groups, mesh=None, k_scale=None, v_scale=None,
                 quant_collectives=frozenset(), lane_state=None):
        self.paged = dict(paged)
        self.groups = tuple(groups)
        self.mesh = mesh
        self.k_scale = k_scale
        self.v_scale = v_scale
        self.quant_collectives = quant_collectives
        self.lane_state = lane_state
        self.step_stats = None
        self.qpos = self.join([g.qpos for g in self.groups])

    k, v = _paged_arena("k"), _paged_arena("v")

    def cut(self, g, x):
        """Group `g`'s slots of `x` (the step's layout) as `[rows, width,
        ...]`."""
        if len(self.groups) == 1:
            return x
        n = g.rows * g.width
        return x[0, g.offset:g.offset + n].reshape(
            (g.rows, g.width) + x.shape[2:])

    def join(self, parts):
        """The step's layout out of one `[rows, width, ...]` a group."""
        if len(self.groups) == 1:
            return parts[0]
        import jax.numpy as jnp

        return jnp.concatenate(
            [x.reshape((1, -1) + x.shape[2:]) for x in parts], axis=1)

    def per_group(self, fn, *xs):
        """`fn(group, *xs cut to the group)` for every group in turn, the
        results joined: how a part that mixes along a sequence runs."""
        return self.join([fn(g, *(self.cut(g, x) for x in xs))
                          for g in self.groups])

    def live_slots(self):
        """Bool, in the step's layout: which slots carry a token."""
        return self.join([g.live_slots() for g in self.groups])

    def layer(self, i):
        """The view of cache slab `i` (a layer's; a looped model's (pass,
        layer)'s). `i` may be traced."""
        return PagedLayerView(self, i)

    def arenas(self):
        """The arena arrays a layer's `attend` replaces, as a tuple: what a
        model that runs its layers inside a `lax` loop carries through it
        (models/looped.py), without knowing which arrays an engine has."""
        kv = tuple(self.paged.values())
        if self.k_scale is not None:
            kv += (self.k_scale, self.v_scale)
        if self.lane_state is None:
            return kv
        return kv + tuple(self.lane_state.values())

    def set_arenas(self, arenas):
        if self.lane_state is not None:
            n = len(arenas) - len(self.lane_state)
            self.lane_state = dict(zip(self.lane_state, arenas[n:]))
            arenas = arenas[:n]
        if self.k_scale is not None:
            self.k_scale, self.v_scale = arenas[-2:]
            arenas = arenas[:-2]
        self.paged = dict(zip(self.paged, arenas))

    def constrain(self, arr, *spec):
        """`with_sharding_constraint` on the serving mesh — the explicit
        tp layout pin for serving activations (heads axis of per-step
        K/V/Q, vocab axis of the logits). A no-op single-chip, so the
        unsharded engine traces byte-identical programs."""
        if self.mesh is None:
            return arr
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(self.mesh, PartitionSpec(*spec))
        )


def _append(arenas, layer, news, slots, offs, mesh):
    """Write the step's tokens, each of `news` [B, S, H, D], to their homes
    ``arena[layer, h, slots, offs, :]`` in its arena of the tuple `arenas`
    (K and V, or one int8 payload): THE append of the step programs, float
    and int8 alike. Returns the updated arenas, a tuple.

    Where the paged kernels run (`paged_kernels_off`, the rule
    `paged_attention_arrays` attends by) the tokens go in as whole blocks
    through the Pallas writer (ops/pallas/paged_kv_append.py), one call
    for the arenas it is given; elsewhere, and counted on a tpu backend,
    through the row scatter below.

    In the scatter the head is an INDEX, not part of its window: each
    update is one ``head_dim`` row at a ``(head, block, offset)``, which is
    how the kernels address the arena too. With a ``[heads, head_dim]``
    window the TPU compiler lays the arena out heads-minor for the scatter
    and row-major for the Pallas call that follows, and relays the whole
    arena between the two in every layer (77 % of a decode step's device
    time on a v5e; PERF.md section 6, PR 25); with the ``[head_dim]``
    window the scatter runs on a bitcast of the row-major arena, which
    then keeps one layout from the program's parameter to its donated
    output, as it does under the writer, whose arenas are aliased.

    Padded and inactive tokens carry slot 0 (the null block); their rows
    collide there, which is what the null block is for (the writer leaves
    it alone). Under a tp `mesh` the write runs per shard over the head
    axis (the specs of `ragged_paged_attention_sharded`): each chip writes
    its own heads with the replicated block ids, and GSPMD has no index
    array to gather.
    """
    import jax.numpy as jnp

    from ..ops.pallas._backend import interpret_mode, note_xla_fallback
    from ..ops.pallas.paged_attention import paged_kernels_off
    from ..ops.pallas.paged_kv_append import paged_kv_append

    def per_shard(write, n):
        """`write(*arenas, *news, slots, offs) -> arenas`, `n` arenas, run
        on each chip's heads."""
        if mesh is None or mesh.shape.get("tp", 1) == 1:
            return write
        from jax.sharding import PartitionSpec as P

        from ..parallel._compat import shard_map

        arena, new = P(None, "tp"), P(None, None, "tp", None)
        return shard_map(
            write, mesh=mesh,
            in_specs=(arena,) * n + (new,) * n + (P(), P()),
            out_specs=(arena,) * n)

    why_xla = paged_kernels_off(arenas[0].shape[-1])
    if why_xla is None:
        n, interpret = len(arenas), interpret_mode()

        def blocks(*a):
            return paged_kv_append(a[:n], layer, a[n:2 * n], *a[2 * n:],
                                   interpret=interpret)

        return per_shard(blocks, n)(*arenas, *news, slots, offs)
    note_xla_fallback("paged_kv_append", why_xla)

    def rows(arena, new, slots, offs):
        heads = jnp.arange(arena.shape[1], dtype=jnp.int32)
        return (arena.at[layer, heads, slots[..., None],
                         offs[..., None]].set(new.astype(arena.dtype)),)

    return tuple(per_shard(rows, 1)(arena, new, slots, offs)[0]
                 for arena, new in zip(arenas, news))


def _quantize_scatter(arena, scales, layer, new, slots, offs, touched,
                      touch_idx, mesh):
    """Int8 arena append with per-(layer, head, block) scale growth.

    `new` [B, S, H, D] f32 tokens land in blocks `slots`/`offs`; every
    block the step can write is listed in `touched` [B, T] (slot 0 = the
    null block) and `touch_idx` [B, S] maps each token to its row's
    touched slot. Scales only GROW while a block is owned — when a new
    token's per-head absmax exceeds the block's stored scale, the block's
    EXISTING int8 payload is requantized (gather → rescale → set) to the
    grown scale before the new tokens scatter, so earlier tokens keep
    dequantizing correctly. A block's first write under its current owner
    always carries offset 0 (positions are consecutive; preempt-by-
    recompute and spec rollback both restart at the block head), so
    ``offs == 0`` marks the block fresh and its STALE scale from a prior
    occupant is ignored instead of compounding across reuse. Duplicate
    `touched` entries only ever name the null block, whose payload/scale
    are scratch. Returns the updated (arena, scales)."""
    import jax.numpy as jnp

    B, S, H, Dh = new.shape
    T = touched.shape[1]
    flat_t = touched.reshape(-1)                            # [B*T]
    gidx = (touch_idx.astype(jnp.int32)
            + jnp.arange(B, dtype=jnp.int32)[:, None] * T).reshape(-1)
    am = jnp.max(jnp.abs(new.astype(jnp.float32)), axis=3)  # [B, S, H]
    blk_am = jnp.zeros((B * T, H), jnp.float32).at[gidx].max(
        am.reshape(B * S, H))
    fresh = jnp.zeros((B * T,), jnp.float32).at[gidx].max(
        (offs.reshape(-1) == 0).astype(jnp.float32)) > 0.0
    old_sc = scales[layer][:, flat_t]                       # [H, B*T]
    old_eff = jnp.where(fresh[None, :], 0.0, old_sc)
    new_sc = jnp.maximum(jnp.maximum(old_eff, blk_am.T / 127.0), 1e-8)
    # requantize the touched blocks' existing payload to the grown scale
    # (fresh blocks have ratio 0 — their stale bytes zero out, which also
    # clears a recycled block's prior occupant)
    ratio = old_eff / new_sc
    old_q = arena[layer][:, flat_t]                         # [H, B*T, bs, D]
    req = jnp.clip(jnp.round(old_q.astype(jnp.float32)
                             * ratio[..., None, None]), -127, 127)
    # NB: in ``arena.at[layer, :, flat_t]`` the scalar `layer` and the
    # index array are advanced indices SEPARATED by a slice, so the
    # broadcast dims land at the FRONT of the updated slice: it has shape
    # [B*T, H, ...], hence the swap/transpose on the updates
    arena = arena.at[layer, :, flat_t].set(
        jnp.swapaxes(req, 0, 1).astype(arena.dtype))
    scales = scales.at[layer, :, flat_t].set(new_sc.T)
    # quantize the new tokens at their block's (grown) scale and scatter
    tok_sc = new_sc.T[gidx].reshape(B, S, H)                # [B, S, H]
    qn = jnp.clip(jnp.round(new.astype(jnp.float32) / tok_sc[..., None]),
                  -127, 127)
    arena, = _append((arena,), layer, (qn,), slots, offs, mesh)
    return arena, scales


class BlockPool:
    """Host-side allocator over the device arena.

    Owns the K/V arena arrays plus the two-tier free bookkeeping:

    - ``_free``    — truly-free blocks (contents meaningless);
    - ``_cached``  — refcount-0 blocks whose full-block KV is still valid
      and published in ``_hash_index`` (LRU order: oldest first). They are
      reusable via `match_prefix` until `allocate` evicts them.

    A block handed out (or pinned via a cache hit) lives in ``_refcount``;
    every holder releases exactly once, and a release below zero — the
    double-free that would alias two sequences onto one block — raises.
    `positions_to_slots` maps token positions to (block, offset) scatter
    targets for a sequence's block list.

    `num_layers` is the arena's leading axis, the model's `kv_slabs`: one
    slab a layer, or a looped model's (pass, layer). The bookkeeping here
    is per LOGICAL block whatever that count: a block id names the same
    `block_size` token slots in every slab. `num_heads` is the arena's
    head count, the model's `kv_heads` (fewer than its query heads under
    grouped-query attention).

    `lane_state` (`{name: (shape, dtype)}`, the model's `lane_state_spec`)
    is state a LANE and not a block holds: one array a name, `shape` with
    the `lanes` axis put in second, zeros. The pool owns them beside the
    arenas (`arenas()` hands all of it to the step program, donated, and
    `set_arenas` takes it back); nothing here addresses them by position,
    so prefix hits, copy-on-write and the host tier do not carry them.

    `kinds` (a tuple of `CacheKind`, the model's `cache_kinds()`) replaces
    the K/V pair by an arena a kind, all addressed by the same block ids:
    `paged` `{name: arena}` for the kinds that live the sequence, and for a
    kind a window bounds a ring a lane among the lane state (`chunk` is the
    widest step the engine runs, which a ring must hold). `num_layers`,
    `num_heads` and `head_dim` are then read by nothing.
    """

    def __init__(self, num_blocks, num_layers, block_size, num_heads,
                 head_dim, dtype=None, metrics=None, tracer=None,
                 sharding=None, kv_dtype=None, lane_state=None, lanes=0,
                 kinds=None, chunk=0):
        import jax.numpy as jnp

        if num_blocks < 2:
            raise ValueError("BlockPool needs >= 2 blocks (block 0 is null)")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # the cache kinds the model declares (`CacheKind`), an arena a kind;
        # a model that declares none keeps a head's keys and values, the
        # pair `k`, `v` the paged kernels walk (the int8 arena and the tp
        # sharding are the pair's: the engine refuses them to declared kinds)
        self.kinds = tuple(kinds) if kinds is not None \
            else kv_pair(num_layers, num_heads, head_dim)
        # `kv_dtype="int8"`: the arena stores int8 payloads with
        # per-(layer, head, block) f32 dequant scales in `k_scale`/
        # `v_scale` sidecars [layers, heads, num_blocks]. Anything else
        # (None / a float dtype) keeps the plain float arena with no
        # sidecars — every int8 hook below is one `self.quantized` test.
        self.kv_dtype = (str(kv_dtype) if kv_dtype is not None
                         else str(jnp.dtype(dtype or jnp.float32).name))
        self.quantized = self.kv_dtype == "int8"
        dt = jnp.int8 if self.quantized else (dtype or jnp.float32)

        def arena_shape(kind):
            lead = (kind.slabs,) if kind.heads is None \
                else (kind.slabs, kind.heads)
            return lead + (self.num_blocks, self.block_size, kind.width)

        # `sharding` (tensor-parallel serving, serving/sharded.py): a
        # NamedSharding placing the head axis over tp — each chip owns its
        # heads' slab of every block. ALL host bookkeeping below (free
        # lists, refcounts, hashes) stays per-LOGICAL-block and identical
        # to the single-chip pool: sharding changes where bytes live,
        # never which block ids exist.
        self._sharding = sharding
        self.k_scale = self.v_scale = None
        if sharding is None:
            self.paged = {k.name: jnp.zeros(arena_shape(k), dt)
                          for k in self.kinds if k.window is None}
            if self.quantized:
                sc_shape = self.k.shape[:3]   # [layers, heads, num_blocks]
                self.k_scale = jnp.zeros(sc_shape)
                self.v_scale = jnp.zeros(sc_shape)
        else:
            # the shared cached jit-with-out_shardings builder: allocates
            # the arena SHARDED from the start — eager zeros + device_put
            # would materialize the full logical arena on the default chip
            # first, and under a per-chip ``kv_hbm_bytes`` budget the
            # logical arena is tp x one chip's HBM (OOM at construction
            # on real accelerators)
            from ..parallel.spmd import _sharded_zeros_fn

            shape = arena_shape(self.kinds[0])
            zeros = _sharded_zeros_fn(shape, str(jnp.dtype(dt)), sharding)
            self.paged = {"k": zeros(), "v": zeros()}
            if self.quantized:
                # same NamedSharding: its PartitionSpec (None, 'tp')
                # shards the sidecar's head axis exactly like the arena's
                sc_zeros = _sharded_zeros_fn(shape[:3], "float32", sharding)
                self.k_scale = sc_zeros()
                self.v_scale = sc_zeros()
        self.lane_state = {
            name: jnp.zeros((shape[0], int(lanes)) + tuple(shape[1:]),
                            dt_ or dtype or jnp.float32)
            for name, (shape, dt_) in (lane_state or {}).items()}
        # a kind a window bounds: a ring a lane, beside the lane state
        self.lane_state.update({
            k.name: jnp.zeros((k.slabs, int(lanes),
                               max(k.window - 1, int(chunk), 1), k.width),
                              dt)
            for k in self.kinds if k.window is not None})
        # block 0 reserved as the null/scratch block
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._refcount = {}           # block -> holders (held blocks only)
        self._hash_index = {}         # content hash -> block
        self._block_hash = {}         # block -> content hash (inverse)
        self._cached = OrderedDict()  # refcount-0 indexed blocks, LRU order
        self.evictions = 0
        self.metrics = metrics
        self.tracer = tracer          # serving/trace.py EngineTracer or None
        self._copy_fn = None          # jitted donated block-copy (lazy)
        self.tier = None              # host-memory tier (serving/kv_tier.py)

    def arenas(self):
        """Everything the step program is handed donated and hands back, in
        its order: the K/V arenas, the int8 scale sidecars, the lane
        state."""
        kv = tuple(self.paged.values())
        if self.quantized:
            kv += (self.k_scale, self.v_scale)
        return kv + tuple(self.lane_state.values())

    def set_arenas(self, arenas):
        n = len(self.paged)
        self.paged = dict(zip(self.paged, arenas[:n]))
        if self.quantized:
            self.k_scale, self.v_scale = arenas[n:n + 2]
            n += 2
        self.lane_state = dict(zip(self.lane_state, arenas[n:]))

    k, v = _paged_arena("k"), _paged_arena("v")

    def state_bytes_per_lane(self):
        """Device bytes of lane state one lane holds, all layers (a
        window-bounded kind's rings among it)."""
        return sum(a.nbytes // a.shape[1] for a in self.lane_state.values())

    def bytes_per_token(self):
        """`{kind: device bytes a token of context costs}` of the kinds
        that live in blocks (a windowed kind costs a lane, not a token:
        `window_bytes_per_lane`)."""
        return {name: a.nbytes // (self.num_blocks * self.block_size)
                for name, a in self.paged.items()}

    def window_bytes_per_lane(self):
        """Device bytes one lane's rings of the windowed kinds take."""
        return sum(self.lane_state[k.name].nbytes
                   // self.lane_state[k.name].shape[1]
                   for k in self.kinds if k.window is not None)

    def attach_tier(self, tier):
        """Install the host-memory tier (serving/kv_tier.py): evicted
        cached-free blocks demote to host instead of dying, and the
        scheduler can swap them back on a prefix match. One pointer —
        None keeps every hook below a single test."""
        self.tier = tier

    @property
    def num_free(self):
        """Allocatable blocks: truly free PLUS evictable cached-free."""
        return len(self._free) + len(self._cached)

    @property
    def num_truly_free(self):
        """Blocks allocatable WITHOUT evicting a cached-free prefix block
        (what ``allocate(n, evict=False)`` can hand out)."""
        return len(self._free)

    @property
    def num_cached_blocks(self):
        """Blocks currently parked in the cached-free tier."""
        return len(self._cached)

    def cached_blocks(self):
        """``(block, hash)`` pairs parked in the cached-free tier, LRU
        order — the migration demote walk (engine.export_kv_tier)."""
        return list(self._cached.items())

    def blocks_for(self, num_tokens):
        """How many blocks a sequence of `num_tokens` tokens needs."""
        return blocks_for(num_tokens, self.block_size)

    def bytes_per_block(self):
        """Device bytes one LOGICAL block costs in the active KV dtype —
        K + V payloads plus (int8 arenas) their per-head scale sidecar
        entries. The observability twin of `sharded.kv_capacity_blocks`'s
        per-shard formula: pool_stats/healthz/bench all report THIS."""
        per = sum(a.nbytes // self.num_blocks for a in self.paged.values())
        if self.quantized:
            per += 2 * (self.k_scale.nbytes // self.num_blocks)
        return per

    def refcount(self, block):
        """Holders of `block` (0 = free or cached-free)."""
        return self._refcount.get(int(block), 0)

    def block_hash(self, block):
        """The content hash `block` is published under, or None."""
        return self._block_hash.get(int(block))

    def allocate(self, n, evict=True):
        """Pop `n` blocks, or None if not enough. Truly-free blocks go
        first; only when that list is empty are cached-free blocks evicted,
        LRU (least recently released/matched) first — eviction is the ONLY
        way a published hash leaves the index. ``evict=False`` restricts
        the request to truly-free blocks (speculative-decoding
        reservations: a drafted token that MIGHT be rejected must never
        push a cached prefix out of the index)."""
        if faults._PLAN is not None:
            fp = faults._PLAN.match("alloc_fail")
            if fp is not None:
                # report the pool as dry: callers defer/preempt exactly as
                # under real block pressure
                if self.tracer is not None:
                    self.tracer.pool_instant("fault[alloc_fail]", {"n": n})
                return None
        if n > (self.num_free if evict else len(self._free)):
            return None
        out = []
        n_evicted = 0
        for _ in range(n):
            if self._free:
                b = self._free.pop()
            else:
                b, _ = self._cached.popitem(last=False)  # LRU victim
                h = self._block_hash.pop(b)
                del self._hash_index[h]
                if self.tier is not None:
                    # demote instead of dying: the tier buffers the (hash,
                    # block) pair and gathers the bytes at the next
                    # flush — which every arena-write site runs first, so
                    # the contents are still valid when the gather reads
                    self.tier.save(h, b)
                self.evictions += 1
                n_evicted += 1
                if self.metrics is not None:
                    self.metrics.inc("prefix_cache_evictions")
            self._refcount[b] = 1
            out.append(b)
        if self.tracer is not None and n_evicted:
            self.tracer.pool_instant(
                "evict", {"blocks": n_evicted,
                          "cached_free": len(self._cached),
                          "truly_free": len(self._free)})
        return out

    def free(self, blocks):
        """Release `blocks` without publishing hashes (back-compat alias
        for `release`)."""
        self.release(blocks)

    def release(self, blocks, hashes=()):
        """Drop one holder's reference on each of `blocks`. A block whose
        refcount reaches zero retires to the cached-free tier when
        ``hashes[i]`` supplies its (valid, full-block) content hash, to the
        truly-free list otherwise. Raises on the null block and on
        refcount underflow (a double free)."""
        for i, b in enumerate(blocks):
            b = int(b)
            if b == 0:
                raise ValueError("cannot free the null block")
            rc = self._refcount.get(b)
            if rc is None:
                raise ValueError(f"double free of block {b}")
            if rc > 1:
                self._refcount[b] = rc - 1
                continue
            del self._refcount[b]
            self._retire(b, hashes[i] if i < len(hashes) else None)

    def _retire(self, b, h):
        """Move refcount-0 block `b` to its tier, keeping ``_hash_index``
        and ``_block_hash`` exact inverses throughout."""
        old = self._block_hash.get(b)
        if h is None:
            if old is not None:
                # hashless retire of a published block (e.g. a partially
                # re-written tail): never leave a dangling index entry
                del self._hash_index[old]
                del self._block_hash[b]
            self._free.append(b)
            return
        if old is not None and old != h:
            del self._hash_index[old]
            del self._block_hash[b]
        owner = self._hash_index.get(h)
        if owner is not None and owner != b:
            # another block already serves this content — duplicate copy
            # (e.g. a COW clone released after its original): free truly
            self._free.append(b)
            return
        self._hash_index[h] = b
        self._block_hash[b] = h
        self._cached[b] = h           # MRU end of the LRU order

    def match_prefix(self, hashes):
        """Longest cached prefix: walk `hashes` through the index and pin
        (refcount++) every matched block, stopping at the first miss.
        Returns the pinned block ids in prefix order. Matched blocks leave
        the cached-free tier but KEEP their index entry, so concurrent
        requests can share one pinned block (refcount > 1)."""
        out = []
        for h in hashes:
            b = self._hash_index.get(h)
            if b is None:
                break
            if b in self._cached:
                del self._cached[b]
                self._refcount[b] = 1
            else:
                self._refcount[b] += 1
            out.append(b)
        return out

    def adopt(self, blocks, hashes):
        """Publish freshly ALLOCATED (held, refcount >= 1) blocks into the
        content index — the tier's swap-in path: a restored block holds
        valid full-block KV for ``hashes[i]`` and must be matchable by
        concurrent admissions exactly like a device-warm block. A hash
        already served by another block is skipped (the block stays held
        and correct, just unpublished) so the index/inverse invariant
        can never break."""
        for b, h in zip(blocks, hashes):
            b = int(b)
            if self._hash_index.get(h) is not None:
                continue
            old = self._block_hash.get(b)
            if old is not None:
                del self._hash_index[old]
            self._hash_index[h] = b
            self._block_hash[b] = h

    def copy_blocks(self, src, dst):
        """Device-side block copy (the copy-on-write path: a sequence about
        to append into a block shared with other holders first duplicates
        it): arena blocks `src` are copied into blocks `dst` in one
        scatter. Jitted with the arenas DONATED — an eager ``.at[].set``
        would materialize a full copy of both arenas per COW (this sits on
        the cache-hit admission path); donation lets XLA scatter in place,
        the same contract as the engine's step program."""
        import jax
        import jax.numpy as jnp

        if self.tier is not None:
            # arena-write ordering: buffered demotions must gather their
            # (still-valid) bytes before this scatter lands on them
            self.tier.flush_saves()
        if self._copy_fn is None:
            def _copy(k, v, s, d):
                return (k.at[:, :, d].set(k[:, :, s]),
                        v.at[:, :, d].set(v[:, :, s]))

            def _copy_q(k, v, ks, vs, s, d):
                # int8 arenas: the COW clone must carry its source's
                # dequant scales or the copy dequantizes garbage
                return (k.at[:, :, d].set(k[:, :, s]),
                        v.at[:, :, d].set(v[:, :, s]),
                        ks.at[:, :, d].set(ks[:, :, s]),
                        vs.at[:, :, d].set(vs[:, :, s]))

            fn = _copy_q if self.quantized else _copy
            nargs = (0, 1, 2, 3) if self.quantized else (0, 1)
            if self._sharding is not None:
                # sharded arenas: donation MUST route through the JL004
                # gate — the host-platform CPU mesh miscompiles donated
                # sharded buffers, real accelerators keep the in-place
                # scatter
                from ..parallel.spmd import mesh_donate_argnums

                self._copy_fn = jax.jit(
                    fn, donate_argnums=mesh_donate_argnums(nargs))
            else:
                # jaxlint: disable=JL004 -- COW scatter donates the single-device KV arenas (and int8 scale sidecars) in place; gating would materialize a full arena copy per COW on CPU (see docstring). Not IR-checkable directly: hlolint lowers the engine's step programs, and this jit shares their arenas — IR002 verifying step-program arena aliasing at tp=1 covers the same donation class
                self._copy_fn = jax.jit(fn, donate_argnums=nargs)
        s32 = jnp.asarray(src, jnp.int32)
        d32 = jnp.asarray(dst, jnp.int32)
        if self.quantized:
            self.k, self.v, self.k_scale, self.v_scale = self._copy_fn(
                self.k, self.v, self.k_scale, self.v_scale, s32, d32)
        else:
            self.k, self.v = self._copy_fn(self.k, self.v, s32, d32)

    def table_for(self, blocks, max_blocks):
        """Padded [max_blocks] int32 block table (0-padded) for a sequence."""
        t = np.zeros(max_blocks, np.int32)
        t[: len(blocks)] = blocks
        return t

    def positions_to_slots(self, blocks, start, count, width):
        """`positions_to_slots` (module level) at this pool's block size."""
        return positions_to_slots(self.block_size, blocks, start, count,
                                  width)
