"""The full layers' key selection without a sort (`ops/pallas/topk_select.py`,
PR 41) against `jax.lax.top_k`, here on the CPU with the kernel interpreted:
`select_keys` gives the same SET of positions (ties to the lower position,
`-0.0` below `+0.0`, `-inf` as the invisible slots), in ascending order, and
the same `ok` and `visible`; the shapes and types that fall back; attention
over the new `chosen` against the old; the model and a served engine whose
full layers take the kernel, against the reference."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.models.dots3 as dots3
from paddle_tpu.models.dots3 import FULL
from paddle_tpu.ops.pallas import _backend
from paddle_tpu.ops.pallas import topk_select as ts

from _dots3_common import VOCAB, build, load_reference, params_of, \
    published_keys
from _serving_common import Served, prompts_of

TOL = 1e-4


@pytest.fixture
def kernels(monkeypatch):
    """The kernel interpreted wherever it applies; its calls counted."""
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    calls = []
    inner = ts.topk_select_kernel

    def spy(*a, **kw):
        calls.append(a[0].shape)
        return inner(*a, **kw)

    monkeypatch.setattr(ts, "topk_select_kernel", spy)
    return calls


def _scores(kind, Q, K, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.normal(size=(Q, K)).astype(np.float32)
    qpos = rng.integers(0, K, Q)
    qpos[0] = K - 1
    if kind == "quantised":
        s = np.round(s * 2) / 2                    # nine values or so
    elif kind == "equal":
        s = np.full((Q, K), 0.25)
    elif kind == "signed_zeros":
        # a score is -0.0 where every relu is 0 under a negative weight
        s = np.where(rng.random((Q, K)) < 0.5, 0.0, -0.0)
        s = np.where(rng.random((Q, K)) < 0.03, 1.0, s)
        s = np.where(rng.random((Q, K)) < 0.02, -np.inf, s)
    elif kind == "short":
        qpos = rng.integers(0, 40, Q)              # qpos + 1 < k
    return (jnp.asarray(s, jnp.float32),
            jnp.asarray(np.minimum(qpos, K - 1), jnp.int32))


def _top_k(scores, qpos, k):
    visible = jnp.arange(scores.shape[-1])[None, :] <= qpos[:, None]
    return np.asarray(jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                                    k)[1])


@pytest.mark.parametrize("kind,Q,K,k", [
    ("random", 64, 2048, 256),          # a chunk tile
    ("random", 32, 2048, 256),          # the decode group
    ("random", 5, 384, 100),            # rows padded to a block of 8
    ("quantised", 64, 1024, 200),
    ("equal", 8, 512, 100),
    ("signed_zeros", 16, 512, 128),
    ("short", 24, 1024, 300),
    ("random", 64, 17408, 2048),        # the cell's tile
], ids=lambda x: str(x))
def test_select_keys_takes_top_ks_set(kernels, kind, Q, K, k):
    scores, qpos = _scores(kind, Q, K)
    chosen, ok, visible = jax.jit(dots3.select_keys, static_argnums=2)(
        scores, qpos, k)
    assert kernels == [(Q, K)]
    chosen = np.asarray(chosen)
    want = _top_k(scores, qpos, k)
    assert chosen.shape == (Q, k)
    for got, row in zip(chosen, want):
        assert np.all(np.diff(got) > 0)
        np.testing.assert_array_equal(got, np.sort(row))
    np.testing.assert_array_equal(ok, chosen <= np.asarray(qpos)[:, None])
    np.testing.assert_array_equal(
        visible, np.arange(K)[None, :] <= np.asarray(qpos)[:, None])


@pytest.mark.parametrize("dtype,K,k,why", [
    (jnp.bfloat16, 256, 16, "bfloat16 scores"),
    (jnp.float32, 96, 16, "a context that is no multiple of 128"),
    (jnp.float32, 256, 256, None),      # every slot: not a fallback
    (jnp.float32, 128, 300, None),
], ids=["bfloat16", "narrow", "k=K", "k>K"])
def test_other_shapes_take_top_k_and_a_tpu_counts_it(
        kernels, monkeypatch, dtype, K, k, why):
    scores, qpos = _scores("random", 8, K)
    scores = scores.astype(dtype)
    want = np.asarray(jax.lax.top_k(
        jnp.where(jnp.arange(K)[None, :] <= qpos[:, None], scores,
                  -jnp.inf), min(k, K))[1])
    # a counter of this test's own: the process-wide one stays empty for
    # the checks that demand it (`chip_smoke.kernel_phase`)
    fallbacks = type(_backend.XLA_FALLBACKS)()
    monkeypatch.setattr(_backend, "XLA_FALLBACKS", fallbacks)
    with monkeypatch.context() as m:
        m.setattr(jax, "default_backend", lambda: "tpu")
        got = ts.topk_select(scores, qpos, k)
    assert kernels == []
    np.testing.assert_array_equal(got, want)
    assert fallbacks == ({} if why is None else {f"topk_select: {why}": 1})


def test_attention_over_the_new_chosen_is_the_olds():
    """The same rows in another order: the softmax and its sums differ in
    the order of float32 additions only."""
    model = build(layer_types=(FULL, FULL))
    attn, z = model.layers[1].attn, model.layers[1].attn.z
    rng = np.random.default_rng(4)
    Q, K, k = 16, 256, 64
    scores, qpos = _scores("random", Q, K, seed=4)
    rows = jnp.pad(jnp.asarray(rng.normal(size=(K, z.r_kv + z.d_r)),
                               jnp.float32),
                   ((0, 0), (0, z.row - z.r_kv - z.d_r)))
    q_nope = jnp.asarray(rng.normal(size=(Q, z.heads, z.d_n)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(size=(Q, z.heads, z.d_r)), jnp.float32)
    old = jnp.asarray(_top_k(scores, qpos, k))
    new = ts.topk_select_kernel(scores, qpos, k, interpret=True)

    def out(chosen):
        return np.asarray(attn.attend_absorbed(
            q_nope, q_rope, rows[chosen], chosen <= qpos[:, None]))

    assert not np.array_equal(old, new)
    assert np.abs(out(old)).max() > 0.1
    assert np.abs(out(new) - out(old)).max() < 1e-5


def test_the_forward_with_the_kernel_gives_the_references_logits(kernels):
    """Contexts of 256, a multiple of 128 and 32 times `index_topk`: the
    plain path's full layers select through the kernel."""
    ref = load_reference()
    model = build(layer_types=(FULL, FULL), max_seq_len=256)
    ids = np.random.default_rng(8).integers(0, VOCAB, (1, 256))
    got = np.asarray(model(ids)._array)[0]
    want = np.asarray(ref.sequence_logits(params_of(model), ids[0],
                                          published_keys(model)))
    assert kernels and all(shape == (256, 256) for shape in kernels)
    assert np.abs(got - want).max() < TOL


def test_a_served_engine_with_the_kernel_gives_the_references_logits(
        kernels):
    """A row's table names 128 slots (32 blocks of 4): the decode group
    selects in one call of `[lanes, 128]`, a chunk row's tile in one of
    `[8, 128]`; prompts longer than `index_topk` (8)."""
    ref = load_reference()
    served = Served(build(experts_held=4, max_seq_len=128), block_size=4,
                    max_batch=3, max_seq_len=128, prefill_chunk=8)
    prompts = prompts_of((29, 5, 21), VOCAB, 2)
    rids = served.serve(prompts, 6)
    assert (3, 128) in kernels and (8, 128) in kernels
    for rid, prompt in zip(rids, prompts):
        assert served.worst(
            lambda m, ids: ref.sequence_logits(params_of(m), ids,
                                               published_keys(m)),
            rid, prompt) < TOL
