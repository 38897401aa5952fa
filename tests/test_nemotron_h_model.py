"""The hybrid block (`paddle_tpu/models/nemotron_h.py`) against its plain
reference (`benchmark/reference/nemotron_h_block.py`) on seeded weights, on
the CPU at a small size: the whole forward on logits, the serving form of a
Mamba layer (one chunk of the chunked scan) against the recurrence, the
expert layer's share against the uncut layer, dropless under skew, and two
lower precisions that the comparison has to catch.

Tolerance. Float32 weights, float32 everywhere: model and reference differ
in the order of float32 sums only (the chunk form sums a chunk's tokens by a
matrix product, the reference a token at a time). 1e-4 on logits whose
spread is about 0.6; measured 2e-6.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.models.experts as experts
import paddle_tpu.models.nemotron_h as nh

from _nemotron_common import (VOCAB, build, load_reference, params_of,
                              published_keys)

TOL = 1e-4
ref = load_reference()


def _ids(shape, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, shape)


def _logits(model, ids):
    return np.asarray(model(jnp.asarray(ids))._array)


def _reference(model, ids, **keys):
    return np.asarray(ref.sequence_logits(
        params_of(model), list(ids), {**published_keys(model), **keys}))


@pytest.mark.parametrize("pattern,held,first", [
    ("MEM*E", None, 0),       # every kind of layer, the router's experts all
    ("MEM*E", 4, 8),          # a share: experts 8-11 of 16
    ("M", None, 0), ("*", None, 0), ("E", 4, 0),
])
def test_plain_forward_gives_the_references_logits(pattern, held, first):
    """21 tokens are two whole chunks of 8 and one of 5: the state and the
    convolution's window cross chunk edges."""
    model = build(pattern=pattern, experts_held=held, experts_from=first)
    ids = _ids((2, 21))
    got = _logits(model, ids)
    for b in range(2):
        assert np.abs(got[b] - _reference(model, ids[b])).max() < TOL


def _mixer_inputs(model, b, w, seed=1):
    cfg = model.cfg
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(k1, (b, w, cfg.hidden_size))
    S = 0.3 * jax.random.normal(k2, (b, cfg.mamba_num_heads,
                                     cfg.mamba_head_dim, cfg.ssm_state_size))
    window = 0.3 * jax.random.normal(k3, (b, cfg.conv_kernel - 1,
                                          cfg.conv_channels))
    return a, S, window


def test_one_chunk_is_the_recurrence_a_token_at_a_time():
    """A chunk of 8 through the serving form equals 8 steps of width 1
    through the same form (width 1 IS the recurrence: no sum over tokens),
    in outputs, state and window, from a state that is not zero."""
    model = build(pattern="M")
    mixer = model.layers[0].mixer
    a, S, window = _mixer_inputs(model, 3, 8)
    live, n = jnp.ones((3, 8), bool), jnp.full((3,), 8, jnp.int32)
    out, S_out, w_out = mixer(a, S, window, live, n)
    S1, w1, outs = S, window, []
    for t in range(8):
        o, S1, w1 = mixer(a[:, t:t + 1], S1, w1, jnp.ones((3, 1), bool),
                          jnp.ones((3,), jnp.int32))
        outs.append(o)
    assert np.abs(np.asarray(out - jnp.concatenate(outs, 1))).max() < 1e-5
    assert np.abs(np.asarray(S_out - S1)).max() < 1e-5
    assert np.array_equal(np.asarray(w_out), np.asarray(w1))


def test_dead_slots_and_idle_lanes_leave_the_state_bit_equal():
    """Row 0 has 5 live tokens of 8, row 1 none (an idle lane), row 2 all.
    Row 0's state and window are those of a 5-token step; row 1's are the
    ones it came with, bit for bit; slots past a row's live tokens change
    nothing whatever they hold."""
    model = build(pattern="M")
    mixer = model.layers[0].mixer
    a, S, window = _mixer_inputs(model, 3, 8)
    n = jnp.array([5, 0, 8], jnp.int32)
    live = jnp.arange(8)[None, :] < n[:, None]
    out, S_out, w_out = mixer(a, S, window, live, n)
    # garbage in the dead slots: nothing moves
    noisy = jnp.where(live[..., None], a, 1e3)
    out2, S_out2, w_out2 = mixer(noisy, S, window, live, n)
    assert np.array_equal(np.asarray(S_out), np.asarray(S_out2))
    assert np.array_equal(np.asarray(w_out), np.asarray(w_out2))
    assert np.array_equal(np.asarray(out)[0, :5], np.asarray(out2)[0, :5])
    # the idle lane: bit-equal
    assert np.array_equal(np.asarray(S_out[1]), np.asarray(S[1]))
    assert np.array_equal(np.asarray(w_out[1]), np.asarray(window[1]))
    # the short row: what five tokens alone give
    o5, S5, w5 = mixer(a[:1, :5], S[:1], window[:1], jnp.ones((1, 5), bool),
                       jnp.full((1,), 5, jnp.int32))
    assert np.abs(np.asarray(S_out[0] - S5[0])).max() < 1e-6
    assert np.array_equal(np.asarray(w_out[0]), np.asarray(w5[0]))
    assert np.abs(np.asarray(out[0, :5] - o5[0])).max() < 1e-5


def _moe_parts(model, a):
    """`(routed part through W_up, shared part)` of the model's first expert
    layer for the normed stream `a`: `forward` returns their sum, and the
    shared part is what a layer that holds no pick returns."""
    moe = next(l.moe for l in model.layers if l.kind == "E")
    live = jnp.ones((a.shape[0],), bool)
    both, stats = moe(a, live)
    shared = nh._project(nh._relu2(nh._project(a, moe.shared.v1)),
                         moe.shared.v2)
    return np.asarray(both - shared), np.asarray(shared), stats


def test_four_shares_add_up_to_the_uncut_layer():
    """32 experts over four chips, 8 a chip. The four shares' routed parts,
    with the shared expert (and router, `W_down`, `W_up`: every chip computes
    them alike) counted once, add up to what the uncut layer of 32 gives; so
    does the reference's, share by share."""
    whole = build(pattern="E", router_experts=32)
    a = jax.random.normal(jax.random.PRNGKey(3), (24, whole.cfg.hidden_size))
    routed_whole, shared, _ = _moe_parts(whole, a)
    total, picked = np.zeros_like(routed_whole), 0
    for chip in range(4):
        share = build(pattern="E", router_experts=32, experts_held=8,
                      experts_from=8 * chip)
        p, pw = params_of(share), params_of(whole)
        for k in p:     # the same weights, the share's slice of the experts
            src = pw[k]
            if ".experts." in k:
                src = src[8 * chip:8 * chip + 8]
            share.named_parameters_dict()[k]._array = src
        routed, shared_again, stats = _moe_parts(share, a)
        assert np.array_equal(shared_again, shared)
        total += routed
        picked += int(stats[1])
    assert picked == 24 * whole.cfg.experts_per_tok   # each pick on one chip
    assert np.abs(total - routed_whole).max() < 1e-5
    # and the whole model against the reference, given the same share
    share_ids = _ids((1, 13), seed=5)
    assert np.abs(_logits(share, share_ids)[0]
                  - _reference(share, share_ids[0])).max() < TOL


def test_dropless_under_skew():
    """A router bias that sends every token to expert 2 first: all 40
    tokens' picks of it are computed (`max_load` 40), and the result is
    still the reference's."""
    model = build(pattern="E")
    moe = model.layers[0].moe
    moe.router.bias._array = moe.router.bias._array.at[2].set(10.0)
    ids = _ids((1, 40), seed=7)
    a = jax.random.normal(jax.random.PRNGKey(4), (40, model.cfg.hidden_size))
    _, stats = moe(a, jnp.ones((40,), bool))
    pairs, local, hit, max_load = map(int, stats)
    assert (pairs, local, max_load) == (160, 160, 40) and hit >= 4
    assert np.abs(_logits(model, ids)[0]
                  - _reference(model, ids[0])).max() < TOL
    # dead slots are not dispatched
    _, stats = moe(a, jnp.arange(40) < 7)
    assert tuple(map(int, stats))[:2] == (28, 28)
    assert int(stats[3]) == 7


def test_a_bfloat16_state_fails_the_comparison(monkeypatch):
    """The recurrent state kept in bfloat16 is rounded once a step, and at
    decays near 1 (here exp(-0.035)) the roundings of the last thirty steps
    all stand in it: over 96 tokens in steps of 2 the logits miss the
    tolerance ten times over (measured 2.7e-3), where the float32 state
    keeps it a hundred times over (1.3e-6)."""
    model = build(pattern="MM", chunk_size=2)
    for layer in model.layers:
        layer.mixer.dt_bias._array = jnp.zeros_like(
            layer.mixer.dt_bias._array)
        layer.mixer.A_log._array = jnp.full_like(
            layer.mixer.A_log._array, -3.0)
    ids = _ids((1, 96), seed=11)
    want = _reference(model, ids[0])
    assert np.abs(_logits(model, ids)[0] - want).max() < TOL
    monkeypatch.setattr(nh, "SSM_STATE_DTYPE", jnp.bfloat16)
    assert np.abs(_logits(model, ids)[0] - want).max() > 10 * TOL


def test_a_bfloat16_router_fails_the_comparison(monkeypatch):
    """Top-k is a discrete choice: scores rounded to bfloat16 swap picks
    near the k-th, and each swap exchanges an expert's output."""
    model = build(pattern="EE", router_experts=64, experts_per_tok=8)
    ids = _ids((1, 64), seed=13)
    want = _reference(model, ids[0])
    assert np.abs(_logits(model, ids)[0] - want).max() < TOL
    monkeypatch.setattr(experts, "ROUTER_DTYPE", jnp.bfloat16)
    assert np.abs(_logits(model, ids)[0] - want).max() > 10 * TOL
