"""The routed experts' grouped matmul (`ops/pallas/grouped_matmul.py`)
through the Pallas interpreter, against `jax.lax.ragged_dot`: float32
accumulations of bfloat16 operands, to 1e-5 of the result's scale. Inside the
groups the two must agree; behind the last group the kernel returns zeros
whatever `x` holds there (NaN is planted). The interpreter checks what the
kernel computes and how it walks; what Mosaic makes of it at the cell's
shapes is `tests/test_mosaic_aot.py`'s, and its speed the chip's."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import _backend
from paddle_tpu.ops.pallas import grouped_matmul as gm

G = 16


def _sizes(at, rows):
    s = np.zeros(G, np.int32)
    s[list(at)] = rows
    return s


# name -> (M, K, N, sizes, (tm, tn) or None for the file's own rule)
CASES = {
    # the decode step's mix: many groups of 1-3 rows, a third of the groups
    # empty, most rows behind the last group
    "decode-mix": (64, 128, 256,
                   _sizes((0, 1, 3, 4, 6, 7, 9, 10, 12, 13, 15),
                          (1, 2, 1, 3, 1, 1, 2, 1, 1, 2, 1)), (16, 128)),
    "every-group-hit": (64, 128, 256, np.full(G, 3, np.int32), (16, 256)),
    "one-group-owns-every-row": (64, 128, 256, _sizes((5,), (64,)),
                                 (16, 256)),
    "no-row-in-any-group": (64, 128, 256, np.zeros(G, np.int32), (16, 128)),
    "only-the-last-group": (64, 128, 256, _sizes((15,), (2,)), (16, 128)),
    "a-group-straddles-two-row-tiles": (
        64, 128, 128, _sizes((2, 3), (10, 12)), (16, 128)),
    "a-group-straddles-three-row-tiles": (
        80, 128, 128, _sizes((2, 3, 9), (5, 37, 3)), (16, 128)),
    # groups that start and end off the sublane (8 rows of float32, 16 of
    # bfloat16), several to a row tile
    "groups-off-the-sublane": (
        64, 128, 256, _sizes((0, 1, 2, 8, 9), (3, 7, 9, 1, 11)), (16, 128)),
    "rows-not-a-multiple-of-the-row-tile": (
        50, 128, 256, _sizes((1, 4, 5, 11), (9, 1, 20, 6)), (16, 128)),
    "every-row-in-a-group-none-behind": (
        48, 128, 128, _sizes((0, 7, 15), (16, 15, 17)), (16, 128)),
    "K-below-N": (64, 128, 384, _sizes((3, 4, 12), (2, 19, 5)), (16, 128)),
    "K-above-N": (64, 384, 128, _sizes((3, 4, 12), (2, 19, 5)), (16, 128)),
    "the-files-own-tiles": (
        96, 256, 384, _sizes((0, 2, 3, 9, 14), (1, 30, 2, 17, 4)), None),
    "a-taller-row-tile-than-any-group": (
        128, 128, 256, _sizes((1, 2, 5, 6), (3, 1, 40, 2)), (64, 128)),
}


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_kernel_is_ragged_dot_inside_the_groups_and_zero_behind(
        case, dtype):
    M, K, N, sizes, tiling = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    total = int(sizes.sum())
    x = rng.standard_normal((M, K)).astype(np.float32)
    w = (0.1 * rng.standard_normal((G, K, N))).astype(np.float32)
    planted = x.copy()
    planted[total:] = np.nan            # nothing may read these rows
    xs, ws = (jnp.asarray(a, dtype) for a in (planted, w))
    got = np.asarray(gm.grouped_matmul_kernel(
        xs, ws, jnp.asarray(sizes), interpret=True, tiling=tiling))
    want = np.asarray(jax.lax.ragged_dot(
        jnp.asarray(x, dtype), ws, jnp.asarray(sizes),
        preferred_element_type=jnp.float32))
    assert got.shape == (M, N) and got.dtype == np.float32
    assert np.array_equal(got[total:], np.zeros((M - total, N), np.float32))
    if total:
        scale = np.abs(want[:total]).max()
        assert np.abs(got[:total] - want[:total]).max() <= 1e-5 * scale


def test_the_walk_visits_live_groups_once_a_row_tile_and_nothing_else():
    """`_visits` by hand at a row tile of 4: groups of 3, 0, 6, 0, 1 rows
    over 16 rows. Group 0 in tile 0; group 2 in tiles 0, 1 and 2 (rows
    3-8); group 4 in tile 2; then the one tile behind, then the grid's
    end, which repeats the last visit and names the last live group."""
    off, group, tile, live = (np.asarray(a) for a in gm._visits(
        jnp.asarray([3, 0, 6, 0, 1], jnp.int32), 16, 4))
    assert off.tolist() == [0, 3, 3, 9, 9, 10]
    assert live.tolist() == [5]
    assert group.tolist() == [0, 2, 2, 2, 4, 4, 4, 4]
    assert tile.tolist() == [0, 0, 1, 2, 2, 3, 3, 3]
    # no row at all: every tile once, to be zeroed, on group 0's block
    off, group, tile, live = (np.asarray(a) for a in gm._visits(
        jnp.zeros((5,), jnp.int32), 16, 4))
    assert live.tolist() == [0] and group.tolist() == [0] * 8
    assert tile.tolist() == [0, 1, 2, 3, 3, 3, 3, 3]


def test_tiles_are_a_function_of_the_shapes_alone():
    # the cell's two products, both widths of step: row tiles of 64, a
    # whole expert matrix a block
    for M in (704, 3520):
        assert gm.tiles(M, 1024, 2688, 128, jnp.bfloat16) == (64, 2688)
        assert gm.tiles(M, 2688, 1024, 128, jnp.bfloat16) == (64, 1024)
    # fewer rows than a row tile: all of them, up to the sublane tile
    assert gm.tiles(12, 128, 256, 4, jnp.bfloat16) == (16, 256)
    assert gm.tiles(12, 128, 256, 4, jnp.float32) == (16, 256)
    assert gm.tiles(40, 128, 256, 4, jnp.float32) == (40, 256)
    # a matrix too large for two copies in VMEM is cut by columns
    tm, tn = gm.tiles(704, 4096, 8192, 8, jnp.bfloat16)
    assert 8192 % tn == 0 and tn % 128 == 0 and tn < 8192
    assert gm.tiles(704, 1 << 20, 128, 8, jnp.bfloat16) is None


def _operands(K, N, dtype="float32"):
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.standard_normal((24, K)), dtype),
            jnp.asarray(0.1 * rng.standard_normal((4, K, N)), dtype),
            jnp.asarray([5, 0, 9, 2], jnp.int32))


@pytest.fixture
def counted(monkeypatch):
    """The dispatch under a backend that calls itself `tpu`: the XLA path
    is counted there, and nowhere else."""
    monkeypatch.setattr(_backend, "XLA_FALLBACKS", type(
        _backend.XLA_FALLBACKS)())
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", raising=False)
    return _backend.XLA_FALLBACKS


@pytest.mark.parametrize("K,N,forced,why", [
    (128, 256, False, "kernels off"),                # off the chip
    (96, 256, True, "a width that is no multiple of 128"),
    (128, 200, True, "a width that is no multiple of 128"),
])
def test_the_dispatch_takes_the_xla_path_and_a_tpu_backend_counts_it(
        K, N, forced, why, counted, monkeypatch):
    x, w, sizes = _operands(K, N)
    if forced:
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(gm, "grouped_matmul_kernel", None)   # not reached
    want = jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)
    # a cpu backend: the XLA path is the only one there is, nothing counted
    assert np.array_equal(np.asarray(gm.grouped_matmul(x, w, sizes)), want)
    assert not counted
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if not forced:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert np.array_equal(np.asarray(gm.grouped_matmul(x, w, sizes)), want)
    assert counted == {f"grouped_matmul: {why}": 1}


def test_the_dispatch_takes_the_kernel_where_it_can(counted, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    x, w, sizes = _operands(128, 256)
    seen = []
    kernel = gm.grouped_matmul_kernel

    def spy(*args, **kw):
        seen.append(kw)
        return kernel(*args, **kw)

    monkeypatch.setattr(gm, "grouped_matmul_kernel", spy)
    got = np.asarray(jax.jit(gm.grouped_matmul)(x, w, sizes))
    want = np.asarray(jax.lax.ragged_dot(
        x, w, sizes, preferred_element_type=jnp.float32))
    assert seen == [{"interpret": True}] and not counted
    assert np.abs(got[:16] - want[:16]).max() <= 1e-5 * np.abs(want).max()
    assert not got[16:].any()
