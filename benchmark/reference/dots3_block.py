"""Plain reference for the language model dots-studio publishes as
`dots3-note-prev` (`model_type` `dots3_note`): a pre-norm stack, `h = h +
Attn_l(RMSNorm(h))`, `h = h + FFN_l(RMSNorm(h))`, eps `rms_norm_eps`, a final
RMSNorm and an untied head, no bias. `a` is the normed stream.

LATENT ATTENTION, sizes `(heads N, r_q, r_kv, d_n, d_r, d_v)`, of two kinds
by `layer_types`: `c_q = RMSNorm(a W_qa)`; `q_h = c_q W_qb` -> `[q_nope_h |
q_rope_h]`; `[c_kv | k_r] = a W_kva`; `c_kv = RMSNorm(c_kv)`; rotary
(rotate-half) on `q_rope_h` and on the one `k_r` a token at its absolute
position; `[k_nope_h | v_h] = c_kv W_kvb`; scores `(q_nope_h . k_nope_h +
q_rope_h . k_r) / sqrt(d_n + d_r)`; softmax over the keys the layer may see;
`o_h = sum p v_h`; headwise gate `o_h <- sigmoid(a W_g)_h o_h`; `W_o`.
`apply_mla_qkv_lora_rescale` scales the normed latents by `sqrt(hidden /
rank)`.

- `full_attention` (`num_attention_heads`, `q_lora_rank`, `kv_lora_rank`,
  `qk_nope_head_dim`, `qk_rope_head_dim`, `v_head_dim`, `rope_theta`): a
  query at `t` sees the `index_topk` positions `s <= t` of largest index
  score (all while `t < index_topk`): FULL scores under an exact top-k mask.
  Indexer: `qI_j = c_q W_Iq` `[index_n_heads, index_head_dim]`, `kI_s =
  LayerNorm(a_s W_Ik)` (gain and bias, eps 1e-6), rotary on the first
  `qk_rope_head_dim` dims of both, `w = a W_Iw` times `index_n_heads^-1/2
  index_head_dim^-1/2`, `I(t, s) = sum_j w_j relu(qI_j . kI_s)`.
- `sliding_attention` (the `swa_*` keys): positions `t - (sliding_window_size
  - 1) ... t`: a band mask.

FFN. Layers below `first_k_dense_replace`: `W2(silu(a W1) * (a W3))`, width
`intermediate_size`. The others: `s = sigmoid(a W_r)` in float32; picks = the
`num_experts_per_tok` largest of `s + b` (`b` chooses only; no group limit);
weights `routed_scaling_factor x s_e / sum of the chosen s`; expert `f_e(a) =
W2_e(silu(a W1_e) * (a W3_e))`, width `moe_intermediate_size`; output `sum
over the held picks of w_e f_e(a)` + a shared expert of the same form.

THE SHARE, as `benchmark/reference/nemotron_h_block.py` states it: the file
may hold experts `[experts_held_from, experts_held_from + n_routed_experts)`
of the router's `router_experts`; router, picks and normalisation over all
of them, the sum over the picks that are held, what the absent experts would
have added left out. With `n_routed_experts == router_experts` this is the
uncut layer.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`; no cache, no
kernels, no batching, no absorbed products, no import from the program. It
is fed the program's seeded weights by name, and widens them a layer (an
expert) at a time inside the jitted functions.

BLOCKED, so that 17k positions fit beside 8 GB of weights and end in tens of
seconds: attention runs a group of `HEAD_GROUP` heads and a block of
`QUERY_BLOCK` queries at a time (the full layers' top-k mask `[T, T]` is made
once a layer, a query block at a time); a window layer's query block reads
the `sliding_window_size - 1` keys before it and its own; an expert computes
the tokens that picked it, `EXPERT_ROWS` at a time; what is a function of one
token runs `TOKEN_BLOCK` tokens at a time. The blocks change the order of no
sum: every product is a whole inner dimension.

Departures and assumptions (the configuration file lists them too):

- no network here: written from the catalog row's `config` and
  `described_as`; `apply_mla_qkv_lora_rescale` and the headwise gate's
  placement (on `o_h`, from the normed stream) follow the family's
  convention; the window counts the query's own position; the indexer is the
  DeepSeek-V3.2 form without its Hadamard rotation (which changes no score in
  exact arithmetic) and without fp8 index keys (a storage choice);
- the vision and audio towers and the multi-token-prediction module are left
  out;
- parameter names and layouts are the program's: matrices `[in, out]` but the
  head `[vocabulary, hidden]`; what multiplies `a` in an attention layer
  stands side by side in `attn.a_proj` `[hidden, W_qa | W_kva (c_kv, k_r) |
  W_g | W_Ik | W_Iw]`; `moe.experts.w13` is `[held, hidden, W1 | W3]`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# a sequence longer than this is padded behind (causal: no row asked for can
# see it) to a multiple of it, so that the jitted functions compile for three
# lengths up to 18,432 and not for every one: a compile costs a minute on the
# chip, a pass over 16k positions five seconds (my chip runs, PR 40)
LENGTH_BUCKET = 6144
HEAD_GROUP = 32
QUERY_BLOCK = 128
TOKEN_BLOCK = 2048
EXPERT_ROWS = 256
INDEX_NORM_EPS = 1e-6


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(gain)


def _block(total, want):
    """A block size dividing `total`: `want` where it does, else all."""
    return want if total % want == 0 else total


def _by_tokens(fn, x):
    """`fn` over the rows of `x` (an array `[T, ...]` or a tuple of them),
    `TOKEN_BLOCK` at a time."""
    T = jax.tree.leaves(x)[0].shape[0]
    n = _block(T, TOKEN_BLOCK)
    out = jax.lax.map(fn, jax.tree.map(
        lambda y: y.reshape((T // n, n) + y.shape[1:]), x))
    return jax.tree.map(lambda y: y.reshape((T,) + y.shape[2:]), out)


def _rotary(x, positions, theta):
    """Rotate-half rotary embedding of `x` `[T, ..., d]` at `positions`."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = positions.astype(jnp.float32)[:, None] * inv
    angle = jnp.concatenate([angle, angle], axis=-1)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (d,))
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * jnp.cos(angle) + jnp.concatenate([-x2, x1], -1) * jnp.sin(angle)


def sizes(config, kind):
    """`(heads, r_q, r_kv, d_n, d_r, d_v, theta)` of a layer kind."""
    p = "swa_" if kind == "sliding_attention" else ""
    return (config[p + "num_attention_heads"], config[p + "q_lora_rank"],
            config[p + "kv_lora_rank"], config[p + "qk_nope_head_dim"],
            config[p + "qk_rope_head_dim"], config[p + "v_head_dim"],
            float(config[p + "rope_theta"]))


@functools.partial(jax.jit, static_argnames=("z", "index", "eps", "rescale"))
def _latents(h, p, z, index, eps, rescale):
    """What an attention layer makes of every token alone: `(a-side parts,
    c_q, c_kv, k_r rotated, gate[, kI rotated, w])`."""
    heads, r_q, r_kv, d_n, d_r, d_v, theta = z
    T, H = h.shape
    pos = jnp.arange(T)
    widths = [r_q, r_kv, d_r, heads] + (list(index) if index else [])
    cuts = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]

    def token_block(hb_pos):
        hb, pb = hb_pos
        a = _rms_norm(hb, p["norm1.weight"], eps)
        c_q, c_kv, k_r, gate, *ix = jnp.split(
            a @ _f32(p["attn.a_proj.weight"]), cuts, axis=-1)
        c_q = _rms_norm(c_q, p["attn.q_norm.weight"], eps)
        c_kv = _rms_norm(c_kv, p["attn.kv_norm.weight"], eps)
        if rescale:
            c_q = c_q * jnp.sqrt(jnp.float32(H / r_q))
            c_kv = c_kv * jnp.sqrt(jnp.float32(H / r_kv))
        out = [c_q, c_kv, _rotary(k_r, pb, theta), jax.nn.sigmoid(gate)]
        if index:
            k_i, w = ix
            mean = k_i.mean(-1, keepdims=True)
            var = jnp.square(k_i - mean).mean(-1, keepdims=True)
            k_i = ((k_i - mean) / jnp.sqrt(var + INDEX_NORM_EPS)
                   * _f32(p["attn.index_norm.weight"])
                   + _f32(p["attn.index_norm.bias"]))
            k_i = jnp.concatenate(
                [_rotary(k_i[:, :d_r], pb, theta), k_i[:, d_r:]], axis=-1)
            out += [k_i, w * (index[1] * index[0]) ** -0.5]
        return tuple(out)

    return _by_tokens(token_block, (h, pos))


@functools.partial(jax.jit, static_argnames=("z", "index", "topk"))
def _topk_mask(c_q, k_i, w_i, index_q, z, index, topk):
    """`[T, T]` bool: which keys each query of a full layer sees. All the
    causal ones while there are no more than `topk`; else the `topk` of
    largest index score among them."""
    d_r, theta = z[4], z[6]
    T = c_q.shape[0]
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]
    if T <= topk:
        return causal
    wq = _f32(index_q)
    n = _block(T, QUERY_BLOCK)

    def block(x):
        c_q, w, qpos = x
        q = (c_q @ wq).reshape(n, index[1], index[0])
        q = jnp.concatenate(
            [_rotary(q[..., :d_r], qpos, theta), q[..., d_r:]], axis=-1)
        s = jnp.einsum("qjk,qj->qk",
                       jax.nn.relu(jnp.einsum("qjd,kd->qjk", q, k_i)), w)
        seen = pos[None, :] <= qpos[:, None]
        s = jnp.where(seen, s, -jnp.inf)
        # exactly `topk` (equal scores: the earlier position first, as
        # `lax.top_k` orders them; a score of exactly 0 is common where
        # the index heads are few)
        _, chosen = jax.lax.top_k(s, topk)
        return seen & jnp.zeros_like(seen).at[
            jnp.arange(n)[:, None], chosen].set(True)

    tiles = lambda x: x.reshape((T // n, n) + x.shape[1:])
    return jax.lax.map(block, (tiles(c_q), tiles(w_i), tiles(pos))).reshape(
        T, T)


@functools.partial(jax.jit, static_argnames=("z", "window"))
def _attend_heads(c_q, c_kv, k_r, w_qb, w_kvb, mask, z, window):
    """`o` `[T, G, d_v]` of a group of `G` heads: `w_qb` `[r_q, G, d_n +
    d_r]`, `w_kvb` `[r_kv, G, d_n + d_v]`. A full layer hands its `[T, T]`
    mask; a window layer (`window` set, `mask` None) reads, a query block at
    a time, the band of keys that block may see."""
    heads, r_q, r_kv, d_n, d_r, d_v, theta = z
    T = c_q.shape[0]
    pos = jnp.arange(T)
    scale = 1.0 / jnp.sqrt(jnp.float32(d_n + d_r))
    kv = jnp.einsum("tr,rgd->tgd", c_kv, _f32(w_kvb))
    k_nope, v = kv[..., :d_n], kv[..., d_n:]
    wq = _f32(w_qb)
    n = _block(T, QUERY_BLOCK)
    back = 0 if window is None else window - 1
    if back:
        # the band: a block's keys are the `back` positions before it and
        # its own, out of arrays padded in front
        pad = lambda x: jnp.concatenate(
            [jnp.zeros((back,) + x.shape[1:], x.dtype), x])
        k_nope, v, k_r = pad(k_nope), pad(v), pad(k_r)

    def block(x):
        c_q, qpos = x
        q = jnp.einsum("qr,rgd->qgd", c_q, wq)
        q_nope, q_rope = q[..., :d_n], _rotary(q[..., d_n:], qpos, theta)
        if back:
            cut = lambda a: jax.lax.dynamic_slice_in_dim(
                a, qpos[0], back + n, 0)
            kn, vv, kr = cut(k_nope), cut(v), cut(k_r)
            kpos = qpos[0] - back + jnp.arange(back + n)
            seen = ((kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
                    & (kpos[None, :] > qpos[:, None] - window))
        else:
            kn, vv, kr = k_nope, v, k_r
            seen = jax.lax.dynamic_slice_in_dim(mask, qpos[0], n, 0)
        s = (jnp.einsum("qgd,kgd->gqk", q_nope, kn)
             + jnp.einsum("qgd,kd->gqk", q_rope, kr)) * scale
        p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("gqk,kgd->qgd", p, vv)

    tiles = lambda x: x.reshape((T // n, n) + x.shape[1:])
    out = jax.lax.map(block, (tiles(c_q), tiles(pos)))
    return out.reshape((T,) + out.shape[2:])


@jax.jit
def _write(h, o, gate, w_o):
    """`h + (gate o) W_o`, `TOKEN_BLOCK` tokens at a time."""
    wo = _f32(w_o)
    return _by_tokens(
        lambda x: x[0] + (x[1] * x[2][..., None]).reshape(x[1].shape[0], -1)
        @ wo, (h, o, gate))


def _attention(h, p, config, kind):
    z = sizes(config, kind)
    heads, r_q, r_kv, d_n, d_r, d_v, _ = z
    full = kind == "full_attention"
    index = (config["index_head_dim"], config["index_n_heads"]) \
        if full else None
    eps = float(config["rms_norm_eps"])
    c_q, c_kv, k_r, gate, *ix = _latents(
        h, p, z, index, eps, bool(config["apply_mla_qkv_lora_rescale"]))
    mask, window = None, None
    if full:
        mask = _topk_mask(c_q, *ix, p["attn.index_q.weight"], z, index,
                          int(config["index_topk"]))
    else:
        window = int(config["sliding_window_size"])
    w_qb = p["attn.q_b.weight"].reshape(r_q, heads, d_n + d_r)
    w_kvb = p["attn.kv_b.weight"].reshape(r_kv, heads, d_n + d_v)
    G = _block(heads, HEAD_GROUP)
    o = jnp.concatenate([
        _attend_heads(c_q, c_kv, k_r, w_qb[:, g:g + G], w_kvb[:, g:g + G],
                      mask, z, window)
        for g in range(0, heads, G)], axis=1)
    return _write(h, o, gate, p["attn.o.weight"])


@functools.partial(jax.jit, static_argnames=("eps",))
def _dense_ffn(h, p, eps):
    w1, w3, w2 = (_f32(p[f"{k}.weight"]) for k in ("gate", "up", "down"))

    def block(hb):
        a = _rms_norm(hb, p["norm2.weight"], eps)
        return hb + (jax.nn.silu(a @ w1) * (a @ w3)) @ w2

    return _by_tokens(block, h)


def route(a, router_w, router_b, per_tok, scaling, normed=True):
    """`(picks [T, per_tok], weights [T, per_tok])` of the router over the
    normed stream `a`: float32 throughout."""
    s = jax.nn.sigmoid(a @ _f32(router_w))
    _, picks = jax.lax.top_k(s + _f32(router_b), per_tok)
    chosen = jnp.take_along_axis(s, picks, axis=-1)
    if normed:
        chosen = chosen / chosen.sum(-1, keepdims=True)
    return picks, scaling * chosen


@functools.partial(jax.jit, static_argnames=(
    "per_tok", "scaling", "normed", "held_from", "eps"))
def _experts(h, p, per_tok, scaling, normed, held_from, eps):
    T, H = h.shape
    held = p["moe.experts.w13"].shape[0]
    a = _by_tokens(lambda hb: _rms_norm(hb, p["norm2.weight"], eps), h)
    picks, weights = _by_tokens(
        lambda ab: route(ab, p["moe.router.weight"], p["moe.router.bias"],
                         per_tok, scaling, normed), a)
    # (token, pick) pairs in order of expert; an expert's are consecutive
    order = jnp.argsort(picks.reshape(-1), stable=True)
    expert_of = picks.reshape(-1)[order]
    token_of, weight_of = order // per_tok, weights.reshape(-1)[order]
    n = min(EXPERT_ROWS, T * per_tok)

    def expert(acc, e):
        start = jnp.searchsorted(expert_of, held_from + e)
        count = jnp.searchsorted(expert_of, held_from + e, side="right") \
            - start
        w13 = _f32(jax.lax.dynamic_index_in_dim(
            p["moe.experts.w13"], e, 0, False))
        w2 = _f32(jax.lax.dynamic_index_in_dim(
            p["moe.experts.w2"], e, 0, False))

        def rows(i, acc):
            at = jnp.minimum(start + i * n + jnp.arange(n), T * per_tok - 1)
            mine = i * n + jnp.arange(n) < count
            g, u = jnp.split(a[token_of[at]] @ w13, 2, axis=-1)
            y = (jax.nn.silu(g) * u) @ w2
            w = jnp.where(mine, weight_of[at], 0.0)
            return acc.at[token_of[at]].add(w[:, None] * y)

        return jax.lax.fori_loop(0, (count + n - 1) // n, rows, acc), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h), jnp.arange(held))
    w1, w3, w2 = (_f32(p[f"moe.shared.{k}.weight"])
                  for k in ("gate", "up", "down"))
    shared = _by_tokens(
        lambda ab: (jax.nn.silu(ab @ w1) * (ab @ w3)) @ w2, a)
    return h + routed + shared


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, head, eps):
    return _rms_norm(x, gain, eps) @ _f32(head).T


def _layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def stream(params, ids, config):
    """The residual stream after the last layer, float32 `[T, hidden]`, for
    one sequence of token ids. `config` is the configuration file."""
    eps = float(config["rms_norm_eps"])
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed.weight"][jnp.asarray(ids, jnp.int32)])
        for i, kind in enumerate(config["layer_types"]):
            p = _layer_params(params, i)
            h = _attention(h, p, config, kind)
            if i < config["first_k_dense_replace"]:
                h = _dense_ffn(h, p, eps)
            else:
                h = _experts(h, p, config["num_experts_per_tok"],
                             float(config["routed_scaling_factor"]),
                             bool(config["norm_topk_prob"]),
                             int(config.get("experts_held_from", 0)), eps)
    return h


def sequence_logits(params, ids, config, rows=None):
    """Logits of one sequence at the positions `rows` (all when None)."""
    ids = list(ids)
    n = len(ids)
    if n > LENGTH_BUCKET:
        ids = ids + [0] * (-n % LENGTH_BUCKET)
    h = stream(params, ids, config)[:n]
    with jax.default_matmul_precision("highest"):
        return _head(h if rows is None else h[jnp.asarray(rows)],
                     params["norm_f.weight"], params["head.weight"],
                     float(config["rms_norm_eps"]))
