"""Plain reference for the GPT-2-style block that Cerebras-GPT publishes
(arXiv:2304.03208, section 2; Radford et al. 2019): learned absolute
positions, pre-LayerNorm, multi-head causal attention, a GELU MLP, a final
LayerNorm and an output head tied to the token embedding.

`jax.numpy`, float32, `jax.default_matmul_precision("highest")`, no kernels,
no cache, no batching, and no import from the program. It is fed the program's
seeded weights by name. Departures from the published description, both
forced by what it is compared with:

- the fused QKV matrix `[h, 3h]` keeps the program's column order, grouped by
  head (`[q_i, k_i, v_i]` for head i). It is a layout of the checkpoint, not
  of the mathematics;
- GELU is the tanh form GPT-2 published; the Cerebras-GPT `config.json` says
  `gelu`, whose erf form the program does not compute (configuration files,
  `changed`).

Weights arrive in the type they are served in and are widened one layer at a
time, so the float32 copy of a layer is all that is ever live beside them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LN_EPS = 1e-5   # `layer_norm_epsilon` of the published config


def _f32(x):
    return x.astype(jnp.float32)


def _layer_norm(x, w, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * _f32(w) + _f32(b)


@functools.partial(jax.jit, static_argnames=("num_heads",))
def _block(x, p, num_heads):
    """One transformer block over one sequence `x` [T, h]."""
    T, h = x.shape
    d = h // num_heads
    y = _layer_norm(x, p["ln1.weight"], p["ln1.bias"])
    qkv = y @ _f32(p["attn.qkv.weight"]) + _f32(p["attn.qkv.bias"])
    qkv = qkv.reshape(T, num_heads, 3, d)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    s = jnp.where(causal[None], s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    x = x + a.reshape(T, h) @ _f32(p["attn.proj.weight"]) \
        + _f32(p["attn.proj.bias"])
    y = _layer_norm(x, p["ln2.weight"], p["ln2.bias"])
    y = jax.nn.gelu(y @ _f32(p["fc1.weight"]) + _f32(p["fc1.bias"]),
                    approximate=True)
    return x + y @ _f32(p["fc2.weight"]) + _f32(p["fc2.bias"])


@jax.jit
def _embed(ids, wte, wpe):
    return _f32(wte[ids]) + _f32(wpe[jnp.arange(ids.shape[0])])


@jax.jit
def _head(x, ln_w, ln_b, wte):
    return _layer_norm(x, ln_w, ln_b) @ _f32(wte).T


def _layer_params(params, i):
    prefix = f"blocks.{i}."
    return {k[len(prefix):]: v for k, v in params.items()
            if k.startswith(prefix)}


def hidden(params, ids, config):
    """Residual stream after the last block for one sequence of token ids
    `[T]`, float32 `[T, h]`. `config` is the configuration file: depth and
    heads are read from its published keys."""
    num_layers, num_heads = config["n_layer"], config["n_head"]
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(ids, jnp.int32), params["wte.weight"],
                   params["wpe.weight"])
        for i in range(num_layers):
            x = _block(x, _layer_params(params, i), num_heads)
    return x


def logits(params, x):
    """Output logits `[rows, vocab]` of residual-stream rows `x`."""
    with jax.default_matmul_precision("highest"):
        return _head(x, params["ln_f.weight"], params["ln_f.bias"],
                     params["wte.weight"])


def sequence_logits(params, ids, config, rows=None):
    """Logits of one sequence at the positions `rows` (all when None)."""
    x = hidden(params, ids, config)
    return logits(params, x if rows is None else x[jnp.asarray(rows)])


def batch_loss(params, ids, labels, config):
    """Mean next-token cross-entropy over a batch `[B, T]`, one sequence at a
    time."""
    total, count = 0.0, 0
    for row_ids, row_labels in zip(ids, labels):
        lg = sequence_logits(params, row_ids, config)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(
            lg, jnp.asarray(row_labels, jnp.int32)[:, None], axis=-1)[:, 0]
        total += float((lse - picked).sum())
        count += len(row_ids)
    return total / count
