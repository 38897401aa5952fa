"""A looped decoder-only LM: one stack of layers run several times a token.

The block ByteDance's Ouro publishes ("Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741): `num_layers` layers whose SAME weights
run `loop_steps` times over every token. In a layer

    a = RMSNorm(h; g1);  q, k, v = a Wq, a Wk, a Wv
    rotary embedding (rotate-half over the whole head) of q and k at the
    token's absolute position;  causal softmax(q k^T / sqrt(d)) v
    h = h + RMSNorm((.) Wo; g2)
    m = RMSNorm(h; g3);  h = h + RMSNorm((silu(m Wg) * (m Wu)) Wd; g4)

("sandwich" norms: one before and one after each sub-layer). Every pass ends
in the model's one final norm, whose output feeds the next pass, and in the
exit gate `sigmoid(h w + b)`, one number a token. The head is untied. No
bias but the gate's, no learned positions.

Keys and values are kept per (pass, layer): pass `t` of layer `l` writes and
reads cache slab ``t * num_layers + l`` (`kv_slabs` of them), so the cache is
`loop_steps` times a plain stack's. On the serving path (`caches` a paged
state, serving/block_pool.py) the loop over passes is ONE `lax.fori_loop` in
the traced program with the slab index a traced scalar: the program holds
each layer's two kernel calls once, not once a pass. The layers inside stay
a Python loop. The block reaches serving through the same three calls GPT's
makes on its layer view (`attend`, `column_parallel`, `row_parallel`).

Precision. The residual stream and the norms are float32; matrix products
take operands in the weights' type and accumulate, and return, float32; keys
and values are stored in the arena's type. Why the stream is float32 even
under bf16 weights: a token's stream takes 2 x `num_layers` x `loop_steps`
additions (384 at 48 layers and four passes) and a bf16 stream would round
it after every one; with post-norm gains near 1 it also grows to an RMS
near sqrt(2 x num_layers) within a pass, so that each rounding is of a
value ten times what is added. And a looped stack feeds a pass's error to
the next pass, which can multiply it (PERF.md section 6, PR 34): the
rounding of the residual, not of the weights, is also what `logit_deficit`
reads on a GPT (PERF.md section 2).

The published `early_exit_threshold` is 1: no pass is ever skipped, the gate
changes no logit, and exit by token is not implemented. `forward(...,
return_gates=True)` returns the gate's probabilities for the tests.
Training this block is not wired (no tape is recorded).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I

# the residual stream's type (the module docstring says why); a test turns it
# to bfloat16 to show that its comparison would catch that
RESIDUAL_DTYPE = jnp.float32


class LoopedConfig:
    def __init__(self, vocab_size=49152, hidden_size=2048, num_layers=48,
                 num_heads=16, head_dim=128, intermediate_size=5632,
                 max_seq_len=2048, loop_steps=4, rope_theta=1e6,
                 rms_eps=1e-6):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.max_seq_len = max_seq_len
        self.loop_steps = loop_steps
        self.rope_theta = float(rope_theta)
        self.rms_eps = float(rms_eps)


class _Gain(nn.Layer):
    """An RMSNorm's gain; `rms_norm` applies it."""

    def __init__(self, size):
        super().__init__()
        self.weight = self.create_parameter(
            [size], default_initializer=I.Constant(1.0))


class _Matrix(nn.Layer):
    """A bias-free projection `[in, out]`: operands in the weight's type,
    float32 accumulation and result. `axes` is the Megatron annotation the
    tensor-parallel placement reads (serving/sharded.py)."""

    bias = None

    def __init__(self, in_features, out_features, axes):
        super().__init__()
        self.weight = self.create_parameter(
            [in_features, out_features],
            default_initializer=I.Normal(0.0, 0.02))
        self.weight.sharding_axes = axes

    def forward(self, x):
        w = self.weight._array
        return Tensor._from_op(jnp.matmul(
            x._array.astype(w.dtype), w,
            preferred_element_type=jnp.float32))


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale * gain.weight._array.astype(jnp.float32)


def rotary_tables(positions, head_dim, theta):
    """`(cos, sin)` `[..., 1, head_dim]` float32 for whole-number
    `positions` `[...]`: frequency i of `head_dim / 2` turns dimensions i
    and i + head_dim / 2 together (the rotate-half pairing)."""
    inv = theta ** (-jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                    / head_dim)
    angle = positions.astype(jnp.float32)[..., None] * inv
    angle = jnp.concatenate([angle, angle], axis=-1)[..., None, :]
    return jnp.cos(angle), jnp.sin(angle)


def apply_rotary(x, cos, sin):
    """`x` `[..., heads, head_dim]` float32, turned by its position."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def causal_attention(q, k, v):
    """Plain causal attention over whole sequences `[b, s, heads, d]`, the
    operands in the type they come in, float32 accumulation."""
    s, d = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def project(x, layer):
    """`layer(x)` on arrays: a `_Matrix` product (operands in the weight's
    type, float32 result)."""
    return layer(Tensor._from_op(x))._array


def _apply(layer, x, name):
    return project(x, layer)


def gated_ffn(x, gate, up, down, column=_apply, row=_apply):
    """The gated SiLU feed-forward `down(silu(gate(x)) * up(x))` on arrays;
    `column` and `row` are how a projection is applied (a paged cache's
    seams, where there is one)."""
    f = jax.nn.silu(column(gate, x, "ffn_gate")) * column(up, x, "ffn_up")
    return row(down, f, "ffn_down")


class LoopedLayer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, A, F = (cfg.hidden_size, cfg.num_heads * cfg.head_dim,
                   cfg.intermediate_size)
        self.norm1 = _Gain(H)
        # Wq, Wk, Wv side by side, `[H, Wq | Wk | Wv]`: a layout of the
        # checkpoint. Apart, the TPU compiler relays each [H, A] matrix for
        # the 8-row products of a decode step and hoists the copies out of
        # the loop over passes: 1.1 GiB live at 48 layers (AOT, PR 34)
        self.qkv = _Matrix(H, 3 * A, (None, "mp"))
        self.o = _Matrix(A, H, ("mp", None))
        self.norm2 = _Gain(H)
        self.norm3 = _Gain(H)
        self.gate = _Matrix(H, F, (None, "mp"))
        self.up = _Matrix(H, F, (None, "mp"))
        self.down = _Matrix(F, H, ("mp", None))
        self.norm4 = _Gain(H)

    def forward(self, h, rotary, cache=None):
        """`h` `[b, s, H]` in the residual's type, an array; returns the
        same. `cache` is the paged layer view of this pass's slab, or None
        for plain causal attention over the whole of `h`."""
        cfg, eps = self.cfg, self.cfg.rms_eps
        b, s, _ = h.shape
        kv_type = self.qkv.weight._array.dtype

        def through(seam, layer, x, name):
            """`layer(x)`, by the cache's `seam` where there is a cache."""
            x = Tensor._from_op(x)
            y = layer(x) if cache is None \
                else getattr(cache, seam)(layer, x, name)
            return y._array

        column = functools.partial(through, "column_parallel")
        row = functools.partial(through, "row_parallel")

        a = rms_norm(h, self.norm1, eps)
        heads = (b, s, cfg.num_heads, cfg.head_dim)
        qkv = column(self.qkv, a, "attn_qkv")
        q, k, v = (x.reshape(heads) for x in jnp.split(qkv, 3, axis=-1))
        with jax.named_scope("rotary"):
            q = apply_rotary(q, *rotary).astype(kv_type)
            k = apply_rotary(k, *rotary).astype(kv_type)
        v = v.astype(kv_type)
        if cache is None:
            mixed = causal_attention(q, k, v)
        else:
            mixed = cache.attend(Tensor._from_op(q), Tensor._from_op(k),
                                 Tensor._from_op(v))._array
        o = row(self.o, mixed.reshape(b, s, -1), "attn_o")
        h = h + rms_norm(o, self.norm2, eps).astype(h.dtype)
        m = rms_norm(h, self.norm3, eps)
        f = gated_ffn(m, self.gate, self.up, self.down, column, row)
        return h + rms_norm(f, self.norm4, eps).astype(h.dtype)


class LoopedLM(nn.Layer):
    # which of `LLMEngine`'s model-shaped options this block takes: none of
    # "tp", "quantize", "lora", all written against GPT's modules (the
    # engine refuses them with a sentence, engine.py `_refuse_option`)
    serving_options = frozenset()

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [LoopedLayer(cfg) for _ in range(cfg.num_layers)])
        self.norm_f = _Gain(cfg.hidden_size)
        self.exit_gate = nn.Linear(cfg.hidden_size, 1)
        self.head = nn.Embedding(cfg.vocab_size, cfg.hidden_size)

    @property
    def kv_slabs(self):
        """Cache slabs a token's keys and values take: one a (pass, layer)."""
        return self.cfg.loop_steps * self.cfg.num_layers

    def forward(self, input_ids, caches=None, return_gates=False):
        """Logits `[b, s, vocab]` float32; with a paged `caches`,
        `(logits, caches)` as GPT's paged path returns them. With
        `return_gates`, the exit gate's probabilities `[loop_steps, b, s]`
        are returned last."""
        cfg = self.cfg
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        if caches is not None and not getattr(caches, "is_paged", False):
            raise ValueError(
                "LoopedLM takes a paged cache (serving) or none: the "
                "contiguous decode cache of GPT.generate is not written "
                "for it")
        positions = (jnp.broadcast_to(jnp.arange(s), (b, s))
                     if caches is None else caches.qpos)
        with jax.named_scope("rotary"):
            rotary = rotary_tables(positions, cfg.head_dim, cfg.rope_theta)
        h = jnp.take(self.embed.weight._array, ids.astype(jnp.int32),
                     axis=0).astype(RESIDUAL_DTYPE)
        gate_w = self.exit_gate.weight._array.astype(jnp.float32)
        gate_b = self.exit_gate.bias._array.astype(jnp.float32)
        L = cfg.num_layers

        def one_pass(t, carry):
            h, gates, arenas = carry
            if caches is not None:
                caches.set_arenas(arenas)
            with jax.named_scope("loop_pass"):
                for l, layer in enumerate(self.layers):
                    h = layer(h, rotary, None if caches is None
                              else caches.layer(t * L + l))
                normed = rms_norm(h, self.norm_f, cfg.rms_eps)
                gate = jax.nn.sigmoid(normed @ gate_w + gate_b)[..., 0]
            gates = jax.lax.dynamic_update_index_in_dim(gates, gate, t, 0)
            return (normed.astype(h.dtype), gates,
                    () if caches is None else caches.arenas())

        gates = jnp.zeros((cfg.loop_steps, b, s), jnp.float32)
        arenas = () if caches is None else caches.arenas()
        h, gates, arenas = jax.lax.fori_loop(
            0, cfg.loop_steps, one_pass, (h, gates, arenas))
        if caches is not None:
            caches.set_arenas(arenas)
        w = self.head.weight._array
        logits = jnp.einsum("bsh,vh->bsv", h.astype(w.dtype), w,
                            preferred_element_type=jnp.float32)
        out = (Tensor._from_op(logits),)
        if caches is not None:
            out += (caches,)
        if return_gates:
            out += (Tensor._from_op(gates),)
        return out[0] if len(out) == 1 else out
