"""Ring attention: context parallelism over the 'sp' mesh axis.

Capability the reference LACKS (SURVEY.md §5 long-context: zero hits for
ring attention / context parallel) — first-class here per the build plan
(§7 step 8). Sequence is sharded over 'sp'; K/V blocks rotate around the ring
with `ppermute` while each device accumulates its queries' online-softmax
state — compute overlaps the ICI transfer, memory per device is O(S/sp).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._compat import shard_map

from jax.sharding import PartitionSpec as P

_NEG_INF = -1e30


def _block_attn(q, k, v, m, l, acc, q_off, k_off, causal, scale):
    """One (q_block x k_block) online-softmax update. q: [B,Sq,H,D]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = q_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = k_off + jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        s = jnp.where((qpos >= kpos)[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v).astype(jnp.float32)
    acc_new = acc * alpha[..., 0][..., None] + pv
    return m_new, l_new, acc_new


def _ring_body(q, k, v, axis_name, causal, scale):
    """Runs on each 'sp' shard: local q stays; k/v rotate around the ring."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    seq_block = sq  # per-device block length
    m = jnp.full((b, h, sq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, sq, 1), jnp.float32)
    acc = jnp.zeros((b, h, sq, d), jnp.float32)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(step, carry):
        k_cur, v_cur, m_, l_, acc_ = carry
        src = (idx - step) % n  # which shard's k/v we hold this step
        q_off = idx * seq_block
        k_off = src * seq_block
        m2, l2, acc2 = _block_attn(q, k_cur, v_cur, m_, l_, acc_, q_off, k_off, causal, scale)
        k_next = jax.lax.ppermute(k_cur, axis_name, perm)
        v_next = jax.lax.ppermute(v_cur, axis_name, perm)
        return k_next, v_next, m2, l2, acc2

    k_f, v_f, m, l, acc = jax.lax.fori_loop(0, n, body, (k, v, m, l, acc))
    out = acc / jnp.maximum(l[..., 0][..., None], 1e-30)
    return jnp.einsum("bhqd->bqhd", out).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _build_ring(mesh_id, axis_name, causal, scale):
    import jax as _jax

    mesh = _MESHES[mesh_id]
    spec = P(None, axis_name, None, None)  # [B, S, H, D] sharded on seq

    fn = functools.partial(_ring_body, axis_name=axis_name, causal=causal, scale=scale)

    return _jax.jit(
        shard_map(
            lambda q, k, v: fn(q, k, v),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )
    )


_MESHES = {}


def ring_attention(q, k, v, mesh=None, axis_name="sp", causal=False):
    """q,k,v: [batch, seq, heads, head_dim] jax arrays (seq % sp == 0)."""
    from ..distributed.mesh import get_mesh

    mesh = mesh or get_mesh()
    if mesh is None or mesh.shape.get(axis_name, 1) == 1:
        from ..ops.pallas.flash_attention import _attention_xla

        return _attention_xla(q, k, v, causal=causal)
    scale = 1.0 / np.sqrt(q.shape[-1])
    _MESHES[id(mesh)] = mesh
    return _build_ring(id(mesh), axis_name, causal, scale)(q, k, v)
