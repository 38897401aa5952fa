"""Plain reference for the looped decoder ByteDance publishes as Ouro
("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741;
`modeling_ouro.py` beside the published weights): `num_hidden_layers` layers
whose same weights run `total_ut_steps` times over the sequence, RMSNorm
before and after each sub-layer, rotary positions, a gated SiLU feed-forward,
the model's one final norm at the end of every pass, an exit gate a pass and
an untied output head.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, and no import from the program. It is fed the
program's seeded weights by name. Departures from the published description:

- no network here: the sandwich norms, the final norm at the end of every
  pass and the gate (a biased linear map to one number, then a sigmoid) are
  written from the published modelling code as known (configuration file,
  `assumed`);
- `early_exit_threshold` is 1 in the published file: no pass is skipped and
  the gate changes no logit. Exit by token is not computed;
- the paper's study of sharing one pass's keys and values at decode time is
  an approximation the published model does not make: left out. Keys and
  values are each pass's own here (a full forward has no cache to share);
- parameter names are the program's (`layers.<l>.qkv.weight` ...) and Wq, Wk
  and Wv stand side by side in one matrix `[hidden, Wq | Wk | Wv]`: a layout
  of the checkpoint and not of the mathematics. Matrices are `[in, out]` but
  the head, `[vocabulary, hidden]`.

Weights arrive in the type they are served in and are widened one layer at a
time inside `_layer`, so the float32 copy of a layer is all that is ever
live beside them (the whole model in float32 is 10.7 GB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _f32(x):
    return x.astype(jnp.float32)


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * _f32(gain)


def _rotate(x, theta):
    """Rotary embedding of `x` [T, heads, d] at positions 0..T-1, rotate-half
    over the whole head: dimension i is paired with i + d/2."""
    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("num_heads", "theta", "eps"))
def _layer(x, p, num_heads, theta, eps):
    """One layer over one sequence `x` [T, h]."""
    T = x.shape[0]
    a = _rms_norm(x, p["norm1.weight"], eps)
    q, k, v = (y.reshape(T, num_heads, -1) for y in jnp.split(
        a @ _f32(p["qkv.weight"]), 3, axis=-1))
    q, k = _rotate(q, theta), _rotate(k, theta)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    o = o.reshape(T, -1) @ _f32(p["o.weight"])
    x = x + _rms_norm(o, p["norm2.weight"], eps)
    m = _rms_norm(x, p["norm3.weight"], eps)
    f = jax.nn.silu(m @ _f32(p["gate.weight"])) * (m @ _f32(p["up.weight"]))
    return x + _rms_norm(f @ _f32(p["down.weight"]), p["norm4.weight"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _end_of_pass(x, gain, gate_w, gate_b, eps):
    x = _rms_norm(x, gain, eps)
    return x, jax.nn.sigmoid(x @ _f32(gate_w) + _f32(gate_b))[:, 0]


@jax.jit
def _head(x, head):
    return x @ _f32(head).T


def _layer_params(params, i):
    prefix = f"layers.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def passes(params, ids, config):
    """`(x, gates)` for one sequence of token ids `[T]`: the stream after
    the last pass's final norm, float32 `[T, h]`, and the exit gate's
    probability after every pass, `[total_ut_steps, T]`. `config` is the
    configuration file: depth, heads and passes are its published keys."""
    eps, theta = float(config["rms_norm_eps"]), float(config["rope_theta"])
    gates = []
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed.weight"][jnp.asarray(ids, jnp.int32)])
        for _ in range(config["total_ut_steps"]):
            for i in range(config["num_hidden_layers"]):
                x = _layer(x, _layer_params(params, i),
                           config["num_attention_heads"], theta, eps)
            x, gate = _end_of_pass(x, params["norm_f.weight"],
                                   params["exit_gate.weight"],
                                   params["exit_gate.bias"], eps)
            gates.append(gate)
    return x, jnp.stack(gates)


def sequence_logits(params, ids, config, rows=None):
    """Logits of one sequence at the positions `rows` (all when None)."""
    x, _ = passes(params, ids, config)
    with jax.default_matmul_precision("highest"):
        return _head(x if rows is None else x[jnp.asarray(rows)],
                     params["head.weight"])


def gate_probabilities(params, ids, config):
    """The exit gate's probability after every pass, `[passes, T]`."""
    return passes(params, ids, config)[1]
