"""The latent-attention / gated-expert block (`paddle_tpu/models/dots3.py`)
against its plain reference (`benchmark/reference/dots3_block.py`) on seeded
weights, on the CPU at a small size: the whole forward on logits with both
layer kinds and contexts longer than the tiny `index_topk` and window; the
absorbed order of products against the expanded one on the same cache rows;
the selection (none where `index_topk` covers the context, and a planted
"mask nothing" caught where it does not); the headwise gate; the expert
layer's shares against the uncut reference layer; and two lower precisions
that the comparison has to catch.

Tolerance. Float32 weights, float32 everywhere: model and reference differ
in the order of float32 sums only (the reference runs a group of heads and a
block of queries at a time). 1e-4 on logits whose spread is about 0.65;
measured 2e-6.
"""
import numpy as np
import pytest

import jax.numpy as jnp

import paddle_tpu.models.dots3 as dots3
import paddle_tpu.models.experts as experts
from paddle_tpu.models.dots3 import FULL, WINDOW
from paddle_tpu.models.looped import gated_ffn

from _dots3_common import (SLIDING, TOPK, VOCAB, build, load_reference,
                           params_of, published_keys)

TOL = 1e-4
ref = load_reference()


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, shape)


def _logits(model, ids):
    return np.asarray(model(ids)._array)


def _reference(model, ids):
    return np.asarray(ref.sequence_logits(params_of(model), ids,
                                          published_keys(model)))


def _gap(model, ids):
    got = _logits(model, ids)
    return max(np.abs(got[b] - _reference(model, ids[b])).max()
               for b in range(len(ids)))


@pytest.mark.parametrize("layer_types", [
    (FULL, FULL, WINDOW, WINDOW), (FULL, FULL), (WINDOW, WINDOW),
    (WINDOW, FULL, WINDOW)], ids=lambda t: "".join(k[0] for k in t))
def test_the_forward_gives_the_references_logits(layer_types):
    """Contexts of 40: five times `index_topk` (8) and four windows (9), so
    both masks cut; a dense first layer and expert layers behind it."""
    model = build(layer_types=layer_types)
    ids = _ids((2, 40))
    assert 40 > TOPK and 40 > SLIDING
    assert _gap(model, ids) < TOL
    assert np.abs(_reference(model, ids[0])).max() > 1.0   # no idle stack


def test_absorbed_products_equal_expanded_ones_on_the_same_cache_rows():
    """Decode multiplies `q_nope W_kvb,K^T` against the cached `c_kv` and
    `(sum p c_kv) W_kvb,V`; prefill may expand `k_nope` and `v`. The same
    numbers by another order of float32 products: 1e-5 of outputs near 0.3
    (measured 1e-7), for both kinds of layer, rows shared and a query's own."""
    model = build()
    rng = np.random.default_rng(3)
    for layer in (model.layers[1], model.layers[2]):
        attn, z = layer.attn, layer.attn.z
        q_nope = jnp.asarray(rng.normal(size=(6, z.heads, z.d_n)), jnp.float32)
        q_rope = jnp.asarray(rng.normal(size=(6, z.heads, z.d_r)), jnp.float32)
        rows = jnp.pad(jnp.asarray(rng.normal(size=(11, z.r_kv + z.d_r)),
                                   jnp.float32),
                       ((0, 0), (0, z.row - z.r_kv - z.d_r)))
        mask = jnp.asarray(rng.random((6, 11)) < 0.7).at[:, 0].set(True)
        want = np.asarray(attn.attend_expanded(q_nope, q_rope, rows, mask))
        got = np.asarray(attn.attend_absorbed(q_nope, q_rope, rows, mask))
        assert np.abs(want).max() > 0.1
        assert np.abs(got - want).max() < 1e-5
        own = np.asarray(attn.attend_absorbed(
            q_nope, q_rope, jnp.broadcast_to(rows, (6,) + rows.shape), mask))
        assert np.abs(own - want).max() < 1e-5


def _see_everything(scores, qpos, topk):
    """A planted fault: every causal key chosen, nothing masked out."""
    n = scores.shape[-1]
    chosen = jnp.broadcast_to(jnp.arange(n), scores.shape)
    return chosen, chosen <= qpos[:, None], chosen <= qpos[:, None]


def test_a_selection_that_masks_nothing_fails_the_comparison(monkeypatch):
    """With `index_topk` no smaller than the context a full layer IS dense
    latent attention (the planted fault changes nothing); with it smaller
    the same fault fails the comparison that the sound program passes."""
    ids = _ids((1, 40), seed=5)
    wide = build(layer_types=(FULL, FULL), index_topk=64)
    narrow = build(layer_types=(FULL, FULL))
    assert _gap(wide, ids) < TOL and _gap(narrow, ids) < TOL
    dense = _logits(wide, ids)
    assert np.abs(dense - _logits(narrow, ids)).max() > 100 * TOL
    monkeypatch.setattr(dots3, "select_keys", _see_everything)
    # `_plain` takes the selection only where the context is longer
    assert np.abs(_logits(wide, ids) - dense).max() == 0.0
    assert np.abs(_logits(narrow, ids) - dense).max() < TOL
    assert _gap(narrow, ids) > 100 * TOL


def test_a_skipped_gate_fails_the_comparison(monkeypatch):
    model = build()
    ids = _ids((1, 24), seed=6)
    assert _gap(model, ids) < TOL
    monkeypatch.setattr(dots3, "headwise_gate", jnp.ones_like)
    assert _gap(model, ids) > 100 * TOL


def test_bfloat16_index_scores_fail_the_comparison(monkeypatch):
    """The selection is a discrete choice: scores rounded to bfloat16 tie
    and swap keys near the k-th, and each swap exchanges a key's value."""
    model = build(layer_types=(FULL, FULL), index_n_heads=16)
    ids = _ids((1, 64), seed=7)
    assert _gap(model, ids) < TOL
    monkeypatch.setattr(dots3, "INDEX_SCORE_DTYPE", jnp.bfloat16)
    assert _gap(model, ids) > 10 * TOL


def test_a_bfloat16_router_fails_the_comparison(monkeypatch):
    model = build(layer_types=(FULL, WINDOW, WINDOW), router_experts=64,
                  experts_per_tok=8)
    ids = _ids((1, 64), seed=13)
    assert _gap(model, ids) < TOL
    monkeypatch.setattr(experts, "ROUTER_DTYPE", jnp.bfloat16)
    assert _gap(model, ids) > 10 * TOL


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of 4 experts each: the routed parts of the four shares and
    the shared expert ONCE are the uncut reference's layer (16 experts, 4 a
    token), and each share alone is the reference's share."""
    whole = build(layer_types=(FULL, WINDOW))
    keys, p = published_keys(whole), params_of(whole)
    layer = {k[len("layers.1."):]: v for k, v in p.items()
             if k.startswith("layers.1.")}
    h = jnp.asarray(np.random.default_rng(1).normal(size=(24, 64)),
                    jnp.float32)
    args = (keys["num_experts_per_tok"], keys["routed_scaling_factor"],
            keys["norm_topk_prob"])
    want = np.asarray(ref._experts(h, layer, *args, 0, keys["rms_norm_eps"])
                      - h)
    a = dots3.rms_norm(h, whole.layers[1].norm2, whole.cfg.rms_eps)
    moe = whole.layers[1].moe
    shared = np.asarray(gated_ffn(a, moe.shared.gate, moe.shared.up,
                                  moe.shared.down))
    total = shared.copy()
    for start in range(0, 16, 4):
        part = build(layer_types=(FULL, WINDOW), experts_held=4,
                     experts_from=start)
        mine = part.layers[1].moe
        for name in ("w13", "w2"):
            getattr(mine.experts, name)._array = \
                getattr(moe.experts, name)._array[start:start + 4]
        out, stats, _ = mine(a, jnp.ones(24, bool))
        total += np.asarray(out) - shared
        cut = {**layer, "moe.experts.w13": mine.experts.w13._array,
               "moe.experts.w2": mine.experts.w2._array}
        alone = np.asarray(ref._experts(h, cut, *args, start,
                                        keys["rms_norm_eps"]) - h)
        assert np.abs(np.asarray(out) - alone).max() < TOL
        assert int(stats[0]) == 24 * 4 and 0 < int(stats[1]) < 24 * 4
    assert np.abs(want).max() > 0.1
    assert np.abs(total - want).max() < TOL
