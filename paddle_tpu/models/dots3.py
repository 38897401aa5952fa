"""A decoder-only LM with two kinds of latent attention and a gated mixture
of experts: the language model dots-studio publishes as `dots3-note-prev`
(`model_type` `dots3_note`; 46 layers, hidden 5,120, 13 full + 33 sliding
layers, 256 routed experts of which 8 a token, 1 shared).

Stream `h` `[tokens, hidden]`, pre-norm: `h = h + Attn_l(RMSNorm(h))`,
`h = h + FFN_l(RMSNorm(h))`; a final RMSNorm and an untied head. No bias.
`a` is the normed stream below.

LATENT ATTENTION (both kinds), sizes `(heads N, r_q, r_kv, d_n, d_r, d_v)`:
`c_q = RMSNorm(a W_qa)` `[r_q]`; `q_h = c_q W_qb` -> `[q_nope_h (d_n) |
q_rope_h (d_r)]`; `[c_kv (r_kv) | k_r (d_r)] = a W_kva`; `c_kv =
RMSNorm(c_kv)`; rotary (rotate-half) on `q_rope_h` and on `k_r` (ONE `k_r` a
token, shared by the heads) at the token's absolute position; `[k_nope_h
(d_n) | v_h (d_v)] = c_kv W_kvb`. Scores `(q_nope_h . k_nope_h + q_rope_h .
k_r) / sqrt(d_n + d_r)`, softmax over the keys the layer may see, `o_h = sum
p v_h`. Headwise gate: `g = sigmoid(a W_g)` `[N]`, `o_h <- g_h o_h`; then
`W_o` `[N d_v, hidden]`. `lora_rescale` (the published
`apply_mla_qkv_lora_rescale`, taken as the family's convention: ASSUMED)
scales the normed latents, `c_q <- c_q sqrt(hidden / r_q)`, `c_kv <- c_kv
sqrt(hidden / r_kv)`.

- FULL layers (`full_attention`): the keys a query at position `t` may see
  are `S_t`, the `index_topk` positions `s <= t` with the largest index
  score (all of them while `t < index_topk`). Indexer (the DeepSeek-V3.2
  form): `qI_j = c_q W_Iq` `[index_n_heads, index_head_dim]`, `kI_s =
  LayerNorm(a_s W_Ik)`, rotary on the first `d_r` dims of both, `w = a W_Iw`
  times `index_n_heads^-1/2 index_head_dim^-1/2`, `I(t, s) = sum_j w_j
  relu(qI_j . kI_s)`. No Hadamard rotation and no fp8 of the index keys.
- WINDOW layers (`sliding_attention`): sizes of their own (the `swa_*`
  keys), no indexer; keys at positions `t - (sliding_window - 1) ... t`
  (the window counts the query's own position: ASSUMED).

FFN. The first `first_k_dense` layers: `W2(silu(a W1) * (a W3))` of width
`intermediate_size`. The others: router in float32, `s = sigmoid(a W_r)`
over `router_experts`; picks = the `experts_per_tok` largest of `s + b` (`b`
chooses only; no group limit); `w_e = s_e / sum of the chosen s`, times
`routed_scaling`; expert `f_e(a) = W2_e(silu(a W1_e) * (a W3_e))`; output
`sum over the picks held here of w_e f_e(a) + shared(a)`. Router and
dispatch are models/experts.py (THE CHIP'S SHARE is stated there): the layer
holds experts `[experts_from, experts_from + experts_held)`.

LAYOUTS of the checkpoint, not of the mathematics: what multiplies `a` in an
attention layer stands side by side in one matrix `a_proj` `[hidden, W_qa |
W_kva | W_g | W_Ik | W_Iw]`; an expert's `W1 | W3` in one stacked leaf
`experts.w13` `[held, hidden, 2 x width]`, `W2` in `experts.w2`.

SERVING (`caches` a paged state, serving/block_pool.py). The model declares
three kinds of cache (`cache_kinds`): `latent`, a full layer's row `[c_kv |
k_r]` a token (after norm, rescale and rotary; shared by the heads, no V;
stored in whole lanes, `LANES`);
`index_key`, its `kI` a token; both live the sequence in blocks, one table.
`window_latent`, a window layer's row, of which only the last
`sliding_window - 1` positions are ever read again: a RING a lane
(`CacheKind.window`), whose bytes do not grow with the sequence; entry `i`
holds the latest position `= i (mod ring)` behind the step's first. A group
of the step at a time (`PagedState.per_group`):

- full layer (scopes `mla_index`, `mla_select`, `mla_full`): a tile of a
  row's queries scores the row's index keys (its blocks through its table),
  selects the exact top `index_topk`, gathers the chosen latent rows and
  attends ABSORBED: `q_nope_h W_kvb,K_h^T` against `c_kv` itself, `(sum p
  c_kv) W_kvb,V_h`: the expanded form's numbers by another order of
  products, and a query reads `index_topk` rows and the index keys, not the
  latent cache. Decode rows and chunk rows alike (a chunk a tile of
  `QUERY_TILE` queries at a time; the decode group's rows select together).
- window layer (`mla_window`): the ring's rows and the step's own, banded
  and causal; absorbed at width 1, expanded (`k_nope`, `v` through `W_kvb`)
  over a chunk, where the keys are few and the queries many.
The plain path (`caches` None) is the expanded form over whole sequences
under a mask.

Precision, as models/looped.py and models/nemotron_h.py keep it: residual
stream, norms, router, softmax and index scores float32; matrix products
take operands in the weights' type and accumulate in float32; cache rows in
the weights' type. Why the index scores are float32: the selection is a
discrete choice, and scores rounded to bfloat16 tie by the hundred
(tests/test_dots3_model.py shows it failing the comparison).

Left out: the vision and audio towers and the multi-token-prediction module
(not part of the language model's forward pass). Training this block is not
wired (no tape is recorded).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops.pallas.grouped_matmul import grouped_matmul
from ..ops.pallas.topk_select import topk_select
from .experts import (held_expert_sum, held_experts_hit, pair_counts,
                      sigmoid_route, sum_step_stats)
from .looped import (_Gain, _Matrix, apply_rotary, gated_ffn, rms_norm,
                     rotary_tables)
from .looped import project as _project

RESIDUAL_DTYPE = jnp.float32
# the index scores' type; a test turns it to bfloat16 to show that its
# comparison would catch that
INDEX_SCORE_DTYPE = jnp.float32
INDEX_NORM_EPS = 1e-6
# queries of a chunk that score, select and attend together in a full layer:
# what bounds the step's temporaries (a tile's index scores are
# `QUERY_TILE x index_n_heads x context` float32)
QUERY_TILE = 64
# a masked score: finite, so that a row no key of which is live stays finite
MASKED = -1e30
# a cache row is stored in whole lanes of the chip's 128: zeros behind `[c_kv
# | k_r]` (576 -> 640 in a full layer, 1,088 -> 1,152 in a window layer). A
# row that ends inside a lane tile gets a device layout with the block's
# slots minor, not the row, and the compiler then relays the whole arena
# around every gather and scatter of rows (two copies of 1.3 GB a decode
# step, a quarter of it: PERF.md section 6, PR 40)
LANES = 128

FULL, WINDOW = "full_attention", "sliding_attention"

# what a step reports, in the order of the vector `forward` hands to
# `caches.step_stats`: the expert counts under the names the hybrid has, and
# keys summed over the layers of a kind and the live query tokens, COUNTED
# from the masks the program applies: `attn_keys_visible` the index scores a
# query's selection ranks, `attn_keys_selected` the gathered rows its
# attention reads, `window_keys_read` the ring's and the step's own rows a
# window layer's attention reads. The three `*_decode` are the decode
# group's part (the rows one token wide; every step of a busy engine has
# them, a step of decode rows alone may never come): the two full-layer
# counts, and the held experts its tokens hit
STEP_STATS = ("moe_pairs", "moe_pairs_local", "moe_experts_hit",
              "moe_max_load", "attn_keys_visible", "attn_keys_selected",
              "window_keys_read", "attn_keys_visible_decode",
              "attn_keys_selected_decode", "moe_experts_hit_decode")


class LatentSizes(NamedTuple):
    heads: int
    r_q: int
    r_kv: int
    d_n: int
    d_r: int
    d_v: int
    theta: float

    @property
    def row(self):
        """A cache row's stored width: `[c_kv | k_r]` in whole lanes."""
        return -(-(self.r_kv + self.d_r) // LANES) * LANES


class Dots3Config:
    def __init__(self, vocab_size=152064, hidden_size=5120,
                 layer_types=(FULL, FULL, WINDOW, WINDOW, WINDOW),
                 num_heads=128, q_lora_rank=1024, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=8e7, index_n_heads=64, index_head_dim=128,
                 index_topk=2048, swa_num_heads=64, swa_q_lora_rank=1024,
                 swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
                 swa_qk_rope_head_dim=64, swa_v_head_dim=128,
                 swa_rope_theta=5e4, sliding_window=513,
                 intermediate_size=13824, first_k_dense=1,
                 moe_intermediate_size=1536, router_experts=256,
                 experts_held=None, experts_from=0, experts_per_tok=8,
                 routed_scaling=1.0, norm_topk_prob=True, rms_eps=1e-5,
                 lora_rescale=True, max_seq_len=2048):
        if set(layer_types) - {FULL, WINDOW}:
            raise ValueError(f"layer_types {layer_types!r}: layers are "
                             f"{FULL} or {WINDOW}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_heads = num_heads
        self.sizes = {
            FULL: LatentSizes(num_heads, q_lora_rank, kv_lora_rank,
                              qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
                              float(rope_theta)),
            WINDOW: LatentSizes(swa_num_heads, swa_q_lora_rank,
                                swa_kv_lora_rank, swa_qk_nope_head_dim,
                                swa_qk_rope_head_dim, swa_v_head_dim,
                                float(swa_rope_theta))}
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.sliding_window = sliding_window
        self.intermediate_size = intermediate_size
        self.first_k_dense = first_k_dense
        self.moe_intermediate_size = moe_intermediate_size
        self.router_experts = router_experts
        self.experts_held = (router_experts if experts_held is None
                             else experts_held)
        self.experts_from = experts_from
        if not 0 <= experts_from <= router_experts - self.experts_held:
            raise ValueError(
                f"experts [{experts_from}, {experts_from + self.experts_held}"
                f") do not lie within the router's {router_experts}")
        self.experts_per_tok = experts_per_tok
        self.routed_scaling = float(routed_scaling)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.rms_eps = float(rms_eps)
        self.lora_rescale = bool(lora_rescale)
        self.max_seq_len = max_seq_len

    def count(self, kind):
        return self.layer_types.count(kind)


def headwise_gate(x):
    """`g = sigmoid(a W_g)`, one number a head."""
    return jax.nn.sigmoid(x)


def index_scores(q, w, keys):
    """`I(t, s) = sum_j w_tj relu(q_tj . k_s)` `[Q, K]` for a tile's index
    queries `q` `[Q, heads, d]`, head weights `w` `[Q, heads]` float32 and
    index keys `[K, d]` (in the cache's type, as the queries are cast)."""
    s = jnp.einsum("qjd,kd->qjk", q.astype(keys.dtype), keys,
                   preferred_element_type=jnp.float32)
    # the sum over heads as float32 multiplies and adds, not a matrix
    # product (whose float32 operands a TPU rounds to bfloat16 by default)
    return jnp.sum(jax.nn.relu(s) * w[:, :, None], axis=1).astype(
        INDEX_SCORE_DTYPE)


def select_keys(scores, qpos, topk):
    """`(chosen [Q, k], ok [Q, k], visible [Q, K])`: the `topk` key
    positions of largest index score among those a query may see (`visible`:
    `s <= qpos`), exactly, as a set: `jax.lax.top_k`'s, ties to the lower
    position (`ops/pallas/topk_select.py`, which takes no sort where it
    can); `ok` says which of them it may see (not all while `qpos + 1 <
    topk`)."""
    visible = jnp.arange(scores.shape[-1])[None, :] <= qpos[:, None]
    chosen = topk_select(scores, qpos, topk)
    return chosen, chosen <= qpos[:, None], visible


def live_keys(mask, live):
    """How many keys of `mask` `[..., Q, K]` belong to queries that carry a
    token (`live` `[..., Q]`): int32."""
    return jnp.sum(mask & live[..., None], dtype=jnp.int32)


class LatentAttention(nn.Layer):
    def __init__(self, cfg, kind):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        z = self.z = cfg.sizes[kind]
        H = cfg.hidden_size
        # what multiplies the normed stream, side by side: W_qa | W_kva |
        # W_g and, in a full layer, the indexer's W_Ik | W_Iw
        self.a_widths = [z.r_q, z.r_kv, z.d_r, z.heads] + (
            [cfg.index_head_dim, cfg.index_n_heads] if kind == FULL else [])
        self.a_proj = _Matrix(H, sum(self.a_widths), (None, None))
        self.q_norm = _Gain(z.r_q)
        self.kv_norm = _Gain(z.r_kv)
        self.q_b = _Matrix(z.r_q, z.heads * (z.d_n + z.d_r), (None, None))
        self.kv_b = _Matrix(z.r_kv, z.heads * (z.d_n + z.d_v), (None, None))
        self.o = _Matrix(z.heads * z.d_v, H, (None, None))
        if kind == FULL:
            self.index_q = _Matrix(
                z.r_q, cfg.index_n_heads * cfg.index_head_dim, (None, None))
            self.index_norm = nn.Layer()
            self.index_norm.weight = self.create_parameter(
                [cfg.index_head_dim], default_initializer=I.Constant(1.0))
            self.index_norm.bias = self.create_parameter(
                [cfg.index_head_dim], is_bias=True)

    # -- the two orders of products -----------------------------------------

    def _kv_b(self):
        z = self.z
        w = self.kv_b.weight._array.reshape(z.r_kv, z.heads, z.d_n + z.d_v)
        return w[..., :z.d_n], w[..., z.d_n:]

    def _softmax(self, scores, mask):
        z = self.z
        scores = scores / math.sqrt(z.d_n + z.d_r)
        return jax.nn.softmax(
            jnp.where(mask[:, None, :], scores, MASKED), axis=-1)

    def attend_absorbed(self, q_nope, q_rope, rows, mask):
        """`o` `[Q, heads, d_v]` float32 for queries `q_nope` `[Q, heads,
        d_n]`, `q_rope` `[Q, heads, d_r]` (float32) over cache rows `[c_kv |
        k_r]`: `[K, r_kv + d_r]` shared by the queries or `[Q, K, ...]` a
        query's own; `mask` `[Q, K]`. `W_kvb` is absorbed into the query and
        the output: the rows are multiplied as they lie in the cache."""
        w_k, w_v = self._kv_b()
        dt, z = w_k.dtype, self.z
        q_abs = jnp.einsum("qnd,rnd->qnr", q_nope.astype(dt), w_k,
                           preferred_element_type=jnp.float32)
        q = jnp.concatenate([q_abs, q_rope], axis=-1).astype(dt)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, rows.shape[-1] - q.shape[-1])))
        keys = "qkr" if rows.ndim == 3 else "kr"
        p = self._softmax(
            jnp.einsum(f"qnr,{keys}->qnk", q, rows,
                       preferred_element_type=jnp.float32), mask)
        ctx = jnp.einsum(f"qnk,{keys}->qnr", p.astype(dt),
                         rows[..., :z.r_kv],
                         preferred_element_type=jnp.float32)
        return jnp.einsum("qnr,rnd->qnd", ctx.astype(dt), w_v,
                          preferred_element_type=jnp.float32)

    def attend_expanded(self, q_nope, q_rope, rows, mask):
        """The same over rows `[K, r_kv + d_r]` shared by the queries, with
        `k_nope` and `v` expanded through `W_kvb`."""
        z = self.z
        dt = self.kv_b.weight._array.dtype
        kv = _project(rows[..., :z.r_kv], self.kv_b).astype(dt).reshape(
            rows.shape[0], z.heads, z.d_n + z.d_v)
        scores = (jnp.einsum("qnd,knd->qnk", q_nope.astype(dt),
                             kv[..., :z.d_n],
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("qnd,kd->qnk", q_rope.astype(dt),
                               rows[..., z.r_kv:z.r_kv + z.d_r],
                               preferred_element_type=jnp.float32))
        return jnp.einsum("qnk,knd->qnd", self._softmax(scores, mask)
                          .astype(dt), kv[..., z.d_n:],
                          preferred_element_type=jnp.float32)

    # -- the keys a layer may see, plain and paged ---------------------------

    def _plain(self, qpos, q_nope, q_rope, rows, index=()):
        """One whole sequence from position 0, expanded under a mask."""
        cfg = self.cfg
        mask = qpos[None, :] <= qpos[:, None]
        if self.kind == WINDOW:
            mask &= qpos[None, :] > qpos[:, None] - cfg.sliding_window
        elif len(qpos) > cfg.index_topk:
            chosen, ok, _ = select_keys(index_scores(*index), qpos,
                                        cfg.index_topk)
            mask = jnp.zeros_like(mask).at[
                jnp.arange(len(qpos))[:, None], chosen].set(ok)
        return self.attend_expanded(q_nope, q_rope, rows, mask)

    def _full_paged(self, slab, st, g, q_nope, q_rope, rows, q_i, w_i, k_i):
        """A full layer over group `g`: the step's rows and index keys into
        their arenas, then the queries: the decode group's rows side by side
        (one selection over the group), a chunk row's a tile at a time.
        Returns `(out, counts)`: the keys the live queries' selections
        ranked and their attention read."""
        from ..serving.block_pool import blocks_gather, rows_append, \
            rows_gather

        cfg = self.cfg
        st.paged["latent"] = latent = rows_append(
            st.paged["latent"], slab, rows, g.slots, g.offs)
        st.paged["index_key"] = keys = rows_append(
            st.paged["index_key"], slab, k_i, g.slots, g.offs)

        def scores(context, q_i, w_i):
            with jax.named_scope("mla_index"):
                return index_scores(q_i, w_i, context)

        def select(scores, qpos):
            with jax.named_scope("mla_select"):
                return select_keys(scores, qpos, cfg.index_topk)

        def attend(rows, q_nope, q_rope, ok):
            with jax.named_scope("mla_full"):
                return self.attend_absorbed(q_nope, q_rope, rows, ok)

        def counts(visible, ok, live):
            return {"attn_keys_visible": live_keys(visible, live),
                    "attn_keys_selected": live_keys(ok, live)}

        if g.width == 1:
            # a decode row reads the rows its selection names and the index
            # keys, not the latent cache; the group's one query a row select
            # together, `[rows, K]`
            s = jax.vmap(lambda table, q, w: scores(
                blocks_gather(keys, slab, table), q, w))(
                    g.block_tables, q_i, w_i)
            chosen, ok, visible = select(s[:, 0], g.qpos[:, 0])

            def row(table, chosen, q_nope, q_rope, ok):
                with jax.named_scope("mla_select"):
                    picked = rows_gather(latent, slab, table, chosen[None])
                return attend(picked, q_nope, q_rope, ok[None])

            out = jax.vmap(row)(g.block_tables, chosen, q_nope, q_rope, ok)
            return out, counts(visible, ok, g.live_slots()[:, 0])

        tq = QUERY_TILE if g.width % QUERY_TILE == 0 else g.width

        def chunk_row(x):
            table, *queries = x
            context = blocks_gather(keys, slab, table)
            # a chunk's queries share the row's context: its blocks once,
            # side by side, and a tile's choices index that
            mine = blocks_gather(latent, slab, table)

            def tile(y):
                qpos, live, q_nope, q_rope, q_i, w_i = y
                chosen, ok, visible = select(scores(context, q_i, w_i), qpos)
                with jax.named_scope("mla_select"):
                    picked = mine[chosen]
                out = attend(picked, q_nope, q_rope, ok)
                return out, counts(visible, ok, live)

            tiles = [q.reshape((-1, tq) + q.shape[1:]) for q in queries]
            out, n = tile([t[0] for t in tiles]) \
                if tiles[0].shape[0] == 1 else jax.lax.map(tile, tiles)
            return (out.reshape((g.width,) + out.shape[-2:]),
                    jax.tree.map(jnp.sum, n))

        # chunk rows one after the other
        out, n = jax.lax.map(chunk_row, (
            g.block_tables, g.qpos, g.live_slots(), q_nope, q_rope, q_i,
            w_i))
        return out, jax.tree.map(jnp.sum, n)

    def _window_paged(self, slab, st, g, q_nope, q_rope, rows):
        """A window layer over group `g`: the lanes' rings and the step's
        own rows, then the rows into the rings. Returns `(out, counts)`:
        the keys the live queries' attention read."""
        cfg = self.cfg
        rings = st.lane_state["window_latent"]
        ring = rings[slab]
        if g.lanes is not None:
            ring = jnp.take(ring, g.lanes, axis=0, mode="clip")
        R = ring.shape[1]
        if g.width > R:
            raise ValueError(f"a step {g.width} wide does not fit the "
                             f"window layers' ring of {R}")
        # entry i holds the latest position = i (mod R) behind the first
        # of the step's (none before position 0)
        last = g.qpos[:, :1] - 1
        behind = last - jnp.mod(last - jnp.arange(R)[None, :], R)
        live = g.live_slots()
        kpos = jnp.concatenate([behind, g.qpos], axis=1)[:, None, :]
        valid = jnp.concatenate([behind >= 0, live], axis=1)[:, None, :]
        qpos = g.qpos[:, :, None]
        mask = valid & (kpos <= qpos) & (kpos > qpos - cfg.sliding_window)
        attend = self.attend_absorbed if g.width == 1 \
            else self.attend_expanded
        with jax.named_scope("mla_window"):
            out = jax.vmap(attend)(
                q_nope, q_rope, jnp.concatenate([ring, rows], axis=1), mask)
        ring = ring.at[jnp.arange(g.rows)[:, None],
                       jnp.where(live, jnp.mod(g.qpos, R), R)].set(
                           rows.astype(ring.dtype), mode="drop")
        st.lane_state["window_latent"] = (
            rings.at[slab].set(ring) if g.lanes is None
            else rings.at[slab, g.lanes].set(ring, mode="drop"))
        return out, {"window_keys_read": live_keys(mask, live)}

    def latents(self, a, rotary):
        """What the layer makes of every token alone: `(gate [b, s, heads],
        q_nope, q_rope, rows[, q_i, w_i, k_i])`: the gate's raw score, the
        queries `[b, s, heads, d]` (rotated), the cache's row `[b, s, row]`
        in the cache's type and, in a full layer, the indexer's queries
        `[b, s, heads, d]`, head weights `[b, s, heads]` and key `[b, s,
        d]` (the cache's type)."""
        cfg, z = self.cfg, self.z
        b, s, H = a.shape
        dt = self.a_proj.weight._array.dtype
        splits = [sum(self.a_widths[:i + 1])
                  for i in range(len(self.a_widths) - 1)]
        c_q, c_kv, k_r, gate, *index = jnp.split(
            _project(a, self.a_proj), splits, axis=-1)
        c_q = rms_norm(c_q, self.q_norm, cfg.rms_eps)
        c_kv = rms_norm(c_kv, self.kv_norm, cfg.rms_eps)
        if cfg.lora_rescale:
            c_q = c_q * math.sqrt(H / z.r_q)
            c_kv = c_kv * math.sqrt(H / z.r_kv)
        q = _project(c_q, self.q_b).reshape(b, s, z.heads, z.d_n + z.d_r)
        q_nope, q_rope = q[..., :z.d_n], q[..., z.d_n:]
        with jax.named_scope("rotary"):
            q_rope = apply_rotary(q_rope, *rotary)
            k_r = apply_rotary(k_r[:, :, None, :], *rotary)[:, :, 0, :]
        # the cache's row, in the cache's type wherever it is read
        rows = jnp.pad(jnp.concatenate([c_kv, k_r], axis=-1).astype(dt),
                       ((0, 0), (0, 0), (0, z.row - z.r_kv - z.d_r)))
        xs = (q_nope, q_rope, rows)
        if self.kind == FULL:
            k_i, w_i = index
            q_i = _project(c_q, self.index_q).reshape(
                b, s, cfg.index_n_heads, cfg.index_head_dim)
            mean = jnp.mean(k_i, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(k_i - mean), axis=-1, keepdims=True)
            k_i = ((k_i - mean) * jax.lax.rsqrt(var + INDEX_NORM_EPS)
                   * self.index_norm.weight._array.astype(jnp.float32)
                   + self.index_norm.bias._array.astype(jnp.float32))
            with jax.named_scope("rotary"):
                q_i = jnp.concatenate([
                    apply_rotary(q_i[..., :z.d_r], *rotary),
                    q_i[..., z.d_r:]], axis=-1)
                k_i = jnp.concatenate([
                    apply_rotary(k_i[:, :, None, :z.d_r], *rotary)[:, :, 0],
                    k_i[..., z.d_r:]], axis=-1)
            w_i = w_i * (cfg.index_n_heads * cfg.index_head_dim) ** -0.5
            xs += (q_i, w_i, k_i.astype(dt))
        return (gate,) + xs

    def forward(self, a, positions, rotary, caches=None, slab=0):
        """`a` `[b, s, hidden]` the normed stream in the step's layout,
        `positions` `[b, s]`, `rotary` this kind's tables. `slab` is the
        layer's place among its kind's layers. Returns `([b, s, hidden]
        float32, counts)`, `counts` one `(group, {name: keys})` a group of a
        paged step (none on the plain path)."""
        z = self.z
        b, s, _ = a.shape
        gate, *xs = self.latents(a, rotary)
        counts = []
        if caches is None:
            o = jax.vmap(self._plain)(positions, *xs[:3], xs[3:])
        else:
            paged = self._full_paged if self.kind == FULL \
                else self._window_paged

            def group(g, *xs):
                out, keys = paged(slab, caches, g, *xs)
                counts.append((g, keys))
                return out

            o = caches.per_group(group, *xs)
        o = o * headwise_gate(gate)[..., None]
        return _project(o.reshape(b, s, z.heads * z.d_v), self.o), counts


class GatedMoE(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, F = cfg.hidden_size, cfg.moe_intermediate_size
        self.router = nn.Layer()
        self.router.weight = self.create_parameter(
            [H, cfg.router_experts], default_initializer=I.Normal(0.0, 0.02))
        # the published `e_score_correction_bias`: chooses, never weighs
        self.router.bias = self.create_parameter(
            [cfg.router_experts], is_bias=True)
        self.experts = nn.Layer()
        self.experts.w13 = self.create_parameter(
            [cfg.experts_held, H, 2 * F],
            default_initializer=I.Normal(0.0, 0.02))
        self.experts.w2 = self.create_parameter(
            [cfg.experts_held, F, H], default_initializer=I.Normal(0.0, 0.02))
        self.shared = nn.Layer()
        self.shared.gate = _Matrix(H, F, (None, None))
        self.shared.up = _Matrix(H, F, (None, None))
        self.shared.down = _Matrix(F, H, (None, None))

    def route(self, a):
        cfg = self.cfg
        return sigmoid_route(
            a, self.router.weight._array, self.router.bias._array,
            cfg.experts_per_tok, cfg.routed_scaling, cfg.norm_topk_prob)

    def forward(self, a, live, decode=None):
        """`a` `[T, hidden]` float32, `live` `[T]` which slots hold a token,
        `decode` `[T]` which of them are the decode group's (None: none,
        the plain path). Returns `(out
        [T, hidden] float32, stats, hit)`: `stats` as `LatentMoE`'s, `hit`
        the held experts the decode group's tokens picked."""
        cfg = self.cfg
        with jax.named_scope("moe_route"):
            picks, weights = self.route(a)
        with jax.named_scope("moe_experts"):
            w13, w2 = self.experts.w13._array, self.experts.w2._array

            def expert(x, sizes):
                gate, up = jnp.split(grouped_matmul(x, w13, sizes), 2,
                                     axis=-1)
                return grouped_matmul(
                    (jax.nn.silu(gate) * up).astype(w2.dtype), w2, sizes)

            routed, local, sizes = held_expert_sum(
                a.astype(w13.dtype), picks, weights, live, cfg.experts_from,
                cfg.experts_held, expert)
        with jax.named_scope("moe_shared"):
            shared = gated_ffn(a, self.shared.gate, self.shared.up,
                               self.shared.down)
        stats = pair_counts(live, local, sizes)
        hit = 0 if decode is None else held_experts_hit(
            picks, local & decode[:, None], cfg.experts_from,
            cfg.experts_held)
        return routed + shared, stats, hit


class Dots3Layer(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        H = cfg.hidden_size
        self.kind = cfg.layer_types[index]
        self.norm1 = _Gain(H)
        self.attn = LatentAttention(cfg, self.kind)
        self.norm2 = _Gain(H)
        self.dense = index < cfg.first_k_dense
        if self.dense:
            self.gate = _Matrix(H, cfg.intermediate_size, (None, None))
            self.up = _Matrix(H, cfg.intermediate_size, (None, None))
            self.down = _Matrix(cfg.intermediate_size, H, (None, None))
        else:
            self.moe = GatedMoE(cfg)


class Dots3(nn.Layer):
    # which of `LLMEngine`'s model-shaped options this block takes: none of
    # "tp", "quantize", "lora" (engine.py `_refuse_option`)
    serving_options = frozenset()
    step_stat_names = STEP_STATS
    kv_heads = 1            # a cache row is shared by the heads

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [Dots3Layer(cfg, i) for i in range(cfg.num_layers)])
        self.norm_f = _Gain(cfg.hidden_size)
        self.head = nn.Embedding(cfg.vocab_size, cfg.hidden_size)

    # -- what the serving pool is to hold for this model -------------------

    @property
    def kv_slabs(self):
        """Layers that keep a cache (every one; its kind says which)."""
        return self.cfg.num_layers

    def cache_kinds(self):
        """The three kinds of cache (serving/block_pool.py `CacheKind`):
        a full layer's latent row and index key live the sequence, a window
        layer's latent row lives the window."""
        from ..serving.block_pool import CacheKind

        cfg = self.cfg
        full, win = cfg.sizes[FULL], cfg.sizes[WINDOW]
        return (
            CacheKind("latent", max(1, cfg.count(FULL)), full.row),
            CacheKind("index_key", max(1, cfg.count(FULL)),
                      cfg.index_head_dim),
            CacheKind("window_latent", max(1, cfg.count(WINDOW)), win.row,
                      window=cfg.sliding_window))

    def serving_gauges(self):
        return {"experts_held": self.cfg.experts_held,
                "experts_total": self.cfg.router_experts}

    # ----------------------------------------------------------------------

    def forward(self, input_ids, caches=None):
        """Logits `[b, s, vocab]` float32; with a paged `caches`,
        `(logits, caches)` as GPT's paged path returns them, and the step's
        counts (`STEP_STATS`) left on `caches.step_stats`."""
        cfg = self.cfg
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        if caches is not None and not getattr(caches, "is_paged", False):
            raise ValueError("Dots3 takes a paged cache (serving) or none")
        if caches is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            live, decode = jnp.ones((b, s), bool), None
        else:
            positions, live = caches.qpos, caches.live_slots()
            # the decode group's live slots: row i there IS lane i
            decode = caches.join([
                g.live_slots() & (g.lanes is None) for g in caches.groups])
        with jax.named_scope("rotary"):
            rotary = {kind: rotary_tables(positions, z.d_r, z.theta)
                      for kind, z in cfg.sizes.items()}
        h = jnp.take(self.embed.weight._array, ids.astype(jnp.int32),
                     axis=0).astype(RESIDUAL_DTYPE)
        moe_stats, slabs = [], {FULL: 0, WINDOW: 0}
        keys = dict.fromkeys(STEP_STATS[4:], 0)
        for layer in self.layers:
            a = rms_norm(h, layer.norm1, cfg.rms_eps)
            part, counts = layer.attn(a, positions, rotary[layer.kind],
                                      caches, slabs[layer.kind])
            slabs[layer.kind] += 1
            for g, counted in counts:
                for name, n in counted.items():
                    keys[name] += n
                    if g.lanes is None and name + "_decode" in keys:
                        keys[name + "_decode"] += n
            h = h + part.astype(h.dtype)
            m = rms_norm(h, layer.norm2, cfg.rms_eps)
            if layer.dense:
                part = gated_ffn(m, layer.gate, layer.up, layer.down)
            else:
                part, stats, hit = layer.moe(
                    m.reshape(b * s, -1), live.reshape(-1),
                    None if decode is None else decode.reshape(-1))
                part = part.reshape(b, s, -1)
                moe_stats.append(stats)
                keys["moe_experts_hit_decode"] += hit
            h = h + part.astype(h.dtype)
        h = rms_norm(h, self.norm_f, cfg.rms_eps)
        w = self.head.weight._array
        logits = jnp.einsum("bsh,vh->bsv", h.astype(w.dtype), w,
                            preferred_element_type=jnp.float32)
        if caches is None:
            return Tensor._from_op(logits)
        caches.step_stats = jnp.concatenate([
            *sum_step_stats(moe_stats),
            jnp.stack([jnp.asarray(n, jnp.int32) for n in keys.values()])])
        return Tensor._from_op(logits), caches
