"""Grouped-query attention in the paged arena's two kernels: an arena of
fewer key/value heads than query heads (`[layers, kv_heads, blocks,
block_size, head_dim]`), the ragged walk and the append's block writer
interpreted on the CPU against the XLA paths (the padded gather with the
keys repeated by group; the row scatter), and both against plain attention
over the sequences themselves.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.paged_attention import (paged_attention_xla,
                                                   ragged_paged_attention)
from paddle_tpu.ops.pallas.paged_kv_append import paged_kv_append
from paddle_tpu.serving.block_pool import positions_to_slots

TOL = 1e-3     # the kernel's online softmax against the one-pass reference


def _arena_case(rows, heads, kv_heads, *, bs=8, d=16, seed=0, quant=False):
    """Rows `(total tokens, query tokens)`: each row's queries are the last
    `count` of its `total` positions. Garbage in every block the rows do not
    own and behind their last token."""
    rs = np.random.RandomState(seed)
    blocks = [max(1, -(-t // bs)) for t, _ in rows]
    nb = 1 + sum(blocks)
    shape = (2, kv_heads, nb, bs, d)
    k, v = rs.randn(*shape).astype(np.float32), \
        rs.randn(*shape).astype(np.float32)
    tables = np.zeros((len(rows), max(blocks) + 1), np.int32)
    nxt = 1
    for i, n in enumerate(blocks):
        tables[i, :n] = np.arange(nxt, nxt + n)
        nxt += n
    S = max(c for _, c in rows)
    q = rs.randn(len(rows), S, heads, d).astype(np.float32)
    qpos = np.zeros((len(rows), S), np.int32)
    for i, (t, c) in enumerate(rows):
        qpos[i, :c] = np.arange(t - c, t)
    q_start = np.array([t - c for t, c in rows], np.int32)
    kv_live = np.array([(t - 1) // bs + 1 for t, _ in rows], np.int32)
    q_lens = np.array([c for _, c in rows], np.int32)
    return tuple(map(jnp.asarray, (q, k, v, tables, qpos, q_start, kv_live,
                                   q_lens)))


def _plain(q, k, v, tables, rows, layer, bs):
    """Attention over each row's own keys, gathered by hand and repeated by
    group: no paging code."""
    heads, kv_heads = q.shape[2], k.shape[1]
    out = np.zeros(q.shape, np.float32)
    for i, (t, c) in enumerate(rows):
        ks = np.concatenate([np.asarray(k)[layer, :, b] for b in
                             np.asarray(tables)[i, :-(-t // bs)]], 1)[:, :t]
        vs = np.concatenate([np.asarray(v)[layer, :, b] for b in
                             np.asarray(tables)[i, :-(-t // bs)]], 1)[:, :t]
        ks = np.repeat(ks, heads // kv_heads, 0)      # [heads, t, d]
        vs = np.repeat(vs, heads // kv_heads, 0)
        for j in range(c):
            pos = t - c + j
            s = np.einsum("hd,hkd->hk", np.asarray(q)[i, j], ks[:, :pos + 1])
            s = s / np.sqrt(q.shape[-1])
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[i, j] = np.einsum("hk,hkd->hd", p, vs[:, :pos + 1])
    return out


@pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (2, 2)])
def test_ragged_walk_and_gather_agree_with_plain_attention(heads, kv_heads):
    rows = [(18, 1), (5, 5), (13, 7), (9, 1)]
    q, k, v, tables, qpos, q_start, kv_live, q_lens = _arena_case(
        rows, heads, kv_heads)
    want = _plain(q, k, v, tables, rows, 1, 8)
    xla = np.asarray(paged_attention_xla(q, k, v, 1, tables, qpos))
    kernel = np.asarray(ragged_paged_attention(
        q, k, v, 1, tables, q_start, kv_live, q_lens=q_lens, interpret=True))
    for i, (_, c) in enumerate(rows):
        assert np.abs(xla[i, :c] - want[i, :c]).max() < 1e-5
        assert np.abs(kernel[i, :c] - want[i, :c]).max() < TOL


def test_ragged_walk_dequantizes_an_int8_arena_by_kv_head():
    """Scales are a (layer, K/V head, block): query head i reads those of
    head `i // group`."""
    rows = [(18, 1), (13, 7)]
    q, k, v, tables, qpos, q_start, kv_live, q_lens = _arena_case(
        rows, 4, 2)
    rs = np.random.RandomState(1)
    ksc = jnp.asarray(rs.uniform(0.01, 0.03, k.shape[:3]).astype(np.float32))
    vsc = jnp.asarray(rs.uniform(0.01, 0.03, k.shape[:3]).astype(np.float32))
    k8 = jnp.clip(jnp.round(k * 40), -127, 127).astype(jnp.int8)
    v8 = jnp.clip(jnp.round(v * 40), -127, 127).astype(jnp.int8)
    xla = np.asarray(paged_attention_xla(q, k8, v8, 1, tables, qpos,
                                         k_scale=ksc, v_scale=vsc))
    kernel = np.asarray(ragged_paged_attention(
        q, k8, v8, 1, tables, q_start, kv_live, q_lens=q_lens,
        interpret=True, k_scale=ksc, v_scale=vsc))
    for i, (_, c) in enumerate(rows):
        assert np.abs(kernel[i, :c] - xla[i, :c]).max() < TOL


@pytest.mark.parametrize("width", [1, 8])
def test_block_writer_writes_the_kv_heads_bit_for_bit(width):
    """The writer takes its head count from the tokens it is given: two
    heads of new K/V into a two-head arena, equal bit for bit to the row
    scatter, the rest of the arena untouched."""
    bs, d, kv_heads, nb = 4, 16, 2, 12
    rs = np.random.RandomState(2)
    arena = [jnp.asarray(rs.randn(2, kv_heads, nb, bs, d).astype(np.float32))
             for _ in range(2)]
    rows = [([3, 5, 7], 6, min(width, 5)), ([2, 9], 3, 1), ([], 0, 0)]
    slots = np.zeros((3, width), np.int32)
    offs = np.zeros((3, width), np.int32)
    for i, (blocks, start, count) in enumerate(rows):
        if blocks:
            slots[i], offs[i] = positions_to_slots(bs, blocks, start, count,
                                                   width)
    news = [jnp.asarray(rs.randn(3, width, kv_heads, d).astype(np.float32))
            for _ in range(2)]
    got = paged_kv_append(tuple(arena), 1, tuple(news), jnp.asarray(slots),
                          jnp.asarray(offs), interpret=True)
    heads = jnp.arange(kv_heads)
    for a, new, g in zip(arena, news, got):
        want = a.at[1, heads, slots[..., None], offs[..., None]].set(new)
        # the null block is scratch: the scatter's dead rows collide there
        assert np.array_equal(np.asarray(g)[:, :, 1:],
                              np.asarray(want)[:, :, 1:])
        assert np.array_equal(np.asarray(g)[0], np.asarray(a)[0])
