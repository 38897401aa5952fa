"""Plain reference for the hybrid decoder NVIDIA publishes as Nemotron-H /
Nemotron-3 (`model_type` `nemotron_h`; here the sizes of
NVIDIA-Nemotron-3-Super-120B-A12B-BF16): a stack in which every layer is ONE
part behind one RMSNorm, `h = h + part(RMSNorm(h))`, the part chosen by the
layer's character in `hybrid_override_pattern`:

- `M`, a Mamba-2 mixer. `in_proj` gives `z | xBC | dt`; `xBC` goes through a
  causal depthwise convolution over the last `conv_kernel` tokens (with bias)
  and SiLU, and splits into `x` `[heads, head_dim]`, `B` and `C`
  `[n_groups, state]`; head i reads group `i // (heads / n_groups)`. With
  `dt = softplus(dt + dt_bias)` and `A = -exp(A_log)`, a number a head, the
  state a head `S` `[head_dim, state]` follows
  `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, `y_t = S_t C_t + D x_t`. Then
  the gated norm by group, `RMSNorm_group(y * SiLU(z))` with a gain, and
  `out_proj`.
- `*`, attention: causal softmax(q k^T / sqrt(d)) v with fewer key/value
  heads than query heads (query head i reads key/value head
  `i // (heads / kv_heads)`), no bias, NO rotary embedding and no learned
  positions: the state-space layers carry position.
- `E`, a latent mixture of experts. Router in float32 on the normed stream:
  `s = sigmoid(a W_r)`; the `num_experts_per_tok` experts with the largest
  `s + b` (`b` chooses only); weights `routed_scaling_factor * s_e / sum of
  the chosen s`. `u = a W_down` into the latent width; expert e is
  `relu(u W1_e)^2 W2_e`; the routed part is `(sum_e w_e f_e(u)) W_up`; a
  shared expert `relu(a V1)^2 V2` on the full width is added.

Then the final RMSNorm and an untied head.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`; no kernels,
no cache, no batching, no chunked scan (the recurrence a token at a time,
`lax.scan`; the convolution a plain causal window), and no import from the
program. It is fed the program's seeded weights by name.

THE SHARE. A configuration may hold a share of the routed experts: experts
`[experts_held_from, experts_held_from + n_routed_experts)` of the router's
`router_experts`. The router keeps its whole width, its picks and the
normalisation over all of them; the sum runs over the picks that are held;
what the absent experts would have added is left out, and that partial
result goes on to the next layer. With `n_routed_experts == router_experts`
this is the uncut layer.

Departures and assumptions (the configuration file lists them too):

- no network here: written from the published configuration keys and the
  modelling code as known. That the router reads the full-width stream (not
  the latent) and that nothing stands between `W_down`, the experts and
  `W_up` is assumed: the published parameter count bears out the sizes and
  cannot tell those apart;
- `rope_theta` and `partial_rotary_factor` are read by nothing: this
  family's attention applies no rotary embedding;
- the multi-token-prediction module is a drafter beside the model: left out;
- parameter names are the program's; Wq, Wk, Wv stand side by side in one
  matrix `[hidden, Wq | Wk | Wv]`; matrices are `[in, out]` but the head,
  `[vocabulary, hidden]`; the convolution's weight is `[conv_kernel,
  channels]`, its last row the current token's tap; expert weights are
  stacked `[experts held, in, out]`.

Weights arrive in the type they are served in and are widened a layer at a
time inside the jitted layer functions (an expert at a time inside the loop
over experts), so no float32 copy of more than a layer is ever live.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(gain)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "eps"))
def _mamba(h, p, heads, groups, eps):
    """One Mamba-2 layer over one sequence `h` [T, hidden]."""
    T = h.shape[0]
    a = _rms_norm(h, p["norm.weight"], eps)
    A = -jnp.exp(_f32(p["mixer.A_log"]))                       # [heads]
    D = _f32(p["mixer.D"])
    inner = p["mixer.out_proj.weight"].shape[0]
    P = inner // heads
    conv_w = _f32(p["mixer.conv.weight"])                      # [kernel, channels]
    channels = conv_w.shape[1]
    N = (channels - inner) // (2 * groups)
    z, xBC, dt = jnp.split(a @ _f32(p["mixer.in_proj.weight"]),
                           [inner, inner + channels], axis=-1)
    # the causal window: token t sees inputs t - kernel + 1 .. t
    kernel = conv_w.shape[0]
    padded = jnp.concatenate([jnp.zeros((kernel - 1, channels)), xBC])
    conv = sum(padded[k:k + T] * conv_w[k] for k in range(kernel))
    xBC = jax.nn.silu(conv + _f32(p["mixer.conv.bias"]))
    x, B, C = jnp.split(xBC, [inner, inner + groups * N], axis=-1)
    x = x.reshape(T, heads, P)
    per = heads // groups
    B = jnp.repeat(B.reshape(T, groups, N), per, axis=1)  # [T, heads, N]
    C = jnp.repeat(C.reshape(T, groups, N), per, axis=1)
    dt = jax.nn.softplus(dt + _f32(p["mixer.dt_bias"]))         # [T, heads]

    def token(S, inp):
        x_t, B_t, C_t, dt_t = inp
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t) + D[:, None] * x_t

    _, y = jax.lax.scan(token, jnp.zeros((heads, P, N)), (x, B, C, dt))
    g = (y.reshape(T, inner) * jax.nn.silu(z)).reshape(T, groups, -1)
    g = g / jnp.sqrt((g * g).mean(-1, keepdims=True) + eps)
    g = g.reshape(T, inner) * _f32(p["mixer.gate_norm.weight"])
    return h + g @ _f32(p["mixer.out_proj.weight"])


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "eps"))
def _attention(h, p, heads, kv_heads, eps):
    T = h.shape[0]
    a = _rms_norm(h, p["norm.weight"], eps)
    d = p["attn.o.weight"].shape[0] // heads
    q, k, v = jnp.split(a @ _f32(p["attn.qkv.weight"]),
                        [heads * d, (heads + kv_heads) * d], axis=-1)
    q = q.reshape(T, heads, d)
    k = jnp.repeat(k.reshape(T, kv_heads, d), heads // kv_heads, axis=1)
    v = jnp.repeat(v.reshape(T, kv_heads, d), heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return h + o.reshape(T, -1) @ _f32(p["attn.o.weight"])


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
    a = _rms_norm(h, p["norm.weight"], eps)
    picks, weights = route(a, p["moe.router.weight"], p["moe.router.bias"], per_tok,
                           scaling, normed)
    u = a @ _f32(p["moe.latent_down.weight"])
    held = p["moe.experts.w1"].shape[0]

    def expert(acc, e):
        # the weight this expert has for every token: 0 where not picked
        w = jnp.where(picks == held_from + e, weights, 0.0).sum(-1)
        w1 = _f32(jax.lax.dynamic_index_in_dim(p["moe.experts.w1"], e, 0, False))
        w2 = _f32(jax.lax.dynamic_index_in_dim(p["moe.experts.w2"], e, 0, False))
        return acc + w[:, None] * (jnp.square(jax.nn.relu(u @ w1)) @ w2), None

    routed, _ = jax.lax.scan(expert, jnp.zeros_like(u), jnp.arange(held))
    shared = jnp.square(jax.nn.relu(a @ _f32(p["moe.shared.v1.weight"]))) \
        @ _f32(p["moe.shared.v2.weight"])
    return h + routed @ _f32(p["moe.latent_up.weight"]) + shared


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
    eps = float(config["layer_norm_epsilon"])
    with jax.default_matmul_precision("highest"):
        h = _f32(params["embed.weight"][jnp.asarray(ids, jnp.int32)])
        for i, kind in enumerate(config["hybrid_override_pattern"]):
            p = _layer_params(params, i)
            if kind == "M":
                h = _mamba(h, p, config["mamba_num_heads"],
                           config["n_groups"], eps)
            elif kind == "*":
                h = _attention(h, p, config["num_attention_heads"],
                               config["num_key_value_heads"], eps)
            elif kind == "E":
                h = _experts(h, p, config["num_experts_per_tok"],
                             float(config["routed_scaling_factor"]),
                             bool(config["norm_topk_prob"]),
                             int(config.get("experts_held_from", 0)), eps)
            else:
                raise ValueError(f"no layer kind {kind!r} in the pattern")
    return h


def sequence_logits(params, ids, config, rows=None):
    """Logits of one sequence at the positions `rows` (all when None)."""
    h = stream(params, ids, config)
    with jax.default_matmul_precision("highest"):
        return _head(h if rows is None else h[jnp.asarray(rows)],
                     params["norm_f.weight"], params["head.weight"],
                     float(config["layer_norm_epsilon"]))
