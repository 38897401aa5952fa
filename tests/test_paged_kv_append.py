"""The append's Pallas block writer (ops/pallas/paged_kv_append.py) against
the row scatter it stands in for, both through `serving/block_pool.py`'s
own dispatch (`_append`, and `_quantize_scatter` for the int8 arena): the
whole arena bit for bit outside the null block, whose content is scratch.
The writer runs interpreted (`PADDLE_TPU_FORCE_PALLAS_INTERPRET`); without
the knob the same call takes the scatter, which is today's append.

Also the host's count of what the writer copies (`append_blocks`, the step
span's argument) against a count by hand.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.paged_kv_append import (
    append_blocks,
    blocks_per_row,
)
from paddle_tpu.serving import build_serving_mesh
from paddle_tpu.serving.block_pool import (
    _append,
    _quantize_scatter,
    positions_to_slots,
)

# width -> (block_size, rows); a row is (blocks, first position, live
# tokens). Between them: starts on and off a block's edge, tokens that
# straddle two blocks, fewer live tokens than the width (the rest go to the
# null block), a decode row riding a wide program, lanes with no request.
ROWS = {
    1: (4, [([3], 2, 1), ([5, 1], 4, 1), ([], 0, 0), ([7, 2], 7, 1)]),
    5: (4, [([2, 6], 0, 5), ([7, 4, 1], 3, 5), ([], 0, 0), ([5], 1, 2)]),
    8: (4, [([2, 6, 4], 3, 8), ([7, 1], 0, 8), ([], 0, 0), ([5, 3], 3, 2)]),
    128: (16, [(list(range(1, 9)), 0, 128), (list(range(9, 19)), 21, 128),
               ([19, 20], 30, 1), ([], 0, 0), (list(range(21, 28)), 17, 77)]),
}
NUM_BLOCKS, LAYERS, HEADS, HEAD_DIM = 28, 3, 2, 8


def _targets(width):
    """`(slots, offs, touched, touch_idx)` as `LLMEngine._fill_row` makes
    them for `ROWS[width]`."""
    bs, rows = ROWS[width]
    slots, offs = map(np.stack, zip(*(
        positions_to_slots(bs, blocks or [0], start, count, width)
        for blocks, start, count in rows)))
    touched = np.zeros((len(rows), blocks_per_row(width, bs) + 1), np.int32)
    touch_idx = np.zeros_like(slots)
    for i, sl in enumerate(slots):
        uniq = np.unique(sl[sl != 0])
        touched[i, 1:1 + len(uniq)] = uniq
        lut = {int(b): j + 1 for j, b in enumerate(uniq)}
        touch_idx[i] = [lut.get(int(s), 0) for s in sl]
    return tuple(map(jnp.asarray, (slots, offs, touched, touch_idx)))


def _both_ways(monkeypatch, fn, *args):
    """`fn(*args)` by the scatter and by the interpreted writer, which is
    seen to run."""
    from paddle_tpu.ops.pallas import paged_kv_append as module

    calls = []
    real = module.paged_kv_append

    def spy(*a, **kw):
        calls.append(kw["interpret"])
        return real(*a, **kw)

    monkeypatch.setattr(module, "paged_kv_append", spy)
    out = []
    for knob in (False, True):
        if knob:
            monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
        else:
            monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET",
                               raising=False)
        # a new function each way: jit's cache does not key on the knob
        out.append(jax.tree_util.tree_map(
            np.asarray, jax.jit(lambda *a: fn(*a))(*args)))
        assert calls == [True] * knob
    return out


CASES = [(w, dt, 1) for w in sorted(ROWS)
         for dt in ("bfloat16", "float32", "int8")]
CASES += [(5, "bfloat16", 2), (128, "float32", 2), (8, "int8", 2)]


@pytest.mark.parametrize("width,dtype,tp", CASES, ids=[
    f"w{w}-{dt}-tp{tp}" for w, dt, tp in CASES])
def test_writer_writes_what_the_scatter_writes(monkeypatch, width, dtype, tp):
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    bs, rows = ROWS[width]
    slots, offs, touched, touch_idx = _targets(width)
    assert (np.asarray(slots) == 0).any()
    rs = np.random.RandomState(width)
    shape = (LAYERS, HEADS, NUM_BLOCKS, bs, HEAD_DIM)
    new_shape = (len(rows), width, HEADS, HEAD_DIM)
    mesh = sharding = None
    if tp > 1:
        smesh = build_serving_mesh(tp)
        mesh, sharding = smesh.mesh, smesh.arena_sharding()
    layer = 1

    if dtype == "int8":
        arena = jnp.asarray(rs.randint(-127, 128, shape), jnp.int8)
        scales = jnp.asarray(rs.uniform(0.01, 0.02, shape[:3]), jnp.float32)
        new = jnp.asarray(rs.normal(size=new_shape), jnp.float32)
        if sharding is not None:
            arena = jax.device_put(arena, sharding)
            scales = jax.device_put(scales, sharding)
        before = (np.asarray(arena), np.asarray(scales))
        want, got = _both_ways(
            monkeypatch,
            lambda a, sc, x: _quantize_scatter(a, sc, layer, x, slots, offs,
                                               touched, touch_idx, mesh),
            arena, scales, new)
    else:
        # K and V in one call, as `PagedLayerView.attend` makes it
        arenas = tuple(jnp.asarray(rs.normal(size=shape), dtype)
                       for _ in range(2))
        news = tuple(jnp.asarray(rs.normal(size=new_shape), jnp.float32)
                     for _ in range(2))
        if sharding is not None:
            arenas = tuple(jax.device_put(a, sharding) for a in arenas)
        before = tuple(np.asarray(a) for a in arenas)
        want, got = _both_ways(
            monkeypatch,
            lambda a, x: _append(a, layer, x, slots, offs, mesh),
            arenas, news)

    touched_blocks = np.unique(np.asarray(slots))
    for w, g, b in zip(want, got, before):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g[:, :, 1:], w[:, :, 1:])
        # the other layers, and the blocks no row names, as they were
        assert np.array_equal(g[[0, 2]], b[[0, 2]])
        rest = np.setdiff1d(np.arange(NUM_BLOCKS), touched_blocks)
        assert np.array_equal(g[:, :, rest], b[:, :, rest])
    # and the scatter did write: the comparison is not of two no-ops
    assert any((w[layer, :, 1:] != b[layer, :, 1:]).any()
               for w, b in zip(want, before))


@pytest.mark.parametrize("width", sorted(ROWS))
def test_append_blocks_counts_the_blocks_a_launch_writes(width):
    slots = np.asarray(_targets(width)[0])
    by_hand = sum(len(set(row[row != 0].tolist())) for row in slots)
    got = append_blocks(slots)
    assert got == by_hand and type(got) is int     # a span arg: plain JSON
    bs, rows = ROWS[width]
    assert got == sum((start + count - 1) // bs - start // bs + 1
                      for _, start, count in rows if count)
    assert all(len(set(row[row != 0].tolist())) <= blocks_per_row(width, bs)
               for row in slots)
