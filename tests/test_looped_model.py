"""The looped decoder's equations (`paddle_tpu/models/looped.py`) against the
plain float32 reference (`benchmark/reference/ouro_loop.py`), small sizes,
seeded weights, the model's plain causal forward (no cache).

Tolerances. With float32 weights the model and the reference differ only in
the order float32 sums are taken (the model's matrix products accumulate in
float32 as the reference's do): 5e-5 on logits whose spread is about 0.5,
measured 4e-6. A dropped pass moves logits by tenths and a bf16 residual
stream by hundredths, and both are shown to fail it below."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.core.functional import functional_call, state_dict_arrays
from paddle_tpu.models import looped
from paddle_tpu.models.gpt import GPT, GPTConfig

from _looped_common import (VOCAB, build, load_reference, params_of,
                            published_keys)

F32_TOL = 5e-5
ref = load_reference()


def _ids(rows=2, length=24, seed=1):
    return np.random.default_rng(seed).integers(0, VOCAB, (rows, length))


def _reference(model, ids, **keys):
    config = published_keys(model, **keys)
    params = params_of(model)
    logits = np.stack([np.asarray(ref.sequence_logits(
        params, row.tolist(), config)) for row in ids])
    gates = np.stack([np.asarray(ref.gate_probabilities(
        params, row.tolist(), config)) for row in ids], axis=1)
    return logits, gates


@pytest.mark.parametrize("loop_steps", [2, 4])
def test_plain_forward_is_the_references(loop_steps):
    model, ids = build(loop_steps), _ids()
    logits, gates = model(paddle.to_tensor(ids), return_gates=True)
    want, want_gates = _reference(model, ids)
    assert want.std() > 0.1
    np.testing.assert_allclose(np.asarray(logits._array), want, rtol=0,
                               atol=F32_TOL)
    # one probability a pass a token, each pass's its own
    assert tuple(gates.shape) == (loop_steps, *ids.shape)
    np.testing.assert_allclose(np.asarray(gates._array), want_gates, rtol=0,
                               atol=F32_TOL)
    assert np.abs(np.diff(want_gates, axis=0)).max() > 1e-3


def test_a_dropped_pass_fails_the_comparison():
    model, ids = build(2), _ids()
    got = np.asarray(model(paddle.to_tensor(ids))._array)
    one_pass, _ = _reference(model, ids, loop_steps=1)
    assert np.abs(got - one_pass).max() > 1000 * F32_TOL


def test_a_bf16_residual_stream_fails_the_comparison(monkeypatch):
    model, ids = build(4), _ids()
    want, _ = _reference(model, ids)
    monkeypatch.setattr(looped, "RESIDUAL_DTYPE", jnp.bfloat16)
    got = np.asarray(model(paddle.to_tensor(ids))._array)
    assert np.abs(got - want).max() > 20 * F32_TOL


def test_bf16_weights_stay_within_the_operands_rounding():
    """bf16 weights, as served: operands are rounded to 8 bits of mantissa
    before every product and the stream stays float32. Through 2 x 2 layers
    that moved a logit by at most 0.02 at this size; the bound is 0.05, a
    tenth of the logits' spread."""
    model, ids = build(2, dtype=jnp.bfloat16), _ids()
    got = model(paddle.to_tensor(ids))
    assert got._array.dtype == jnp.float32
    want, _ = _reference(model, ids)
    assert np.abs(np.asarray(got._array) - want).max() < 0.05


def test_one_pass_is_the_stack_run_once():
    """`loop_steps` 1: embedding, the layers once, the final norm, the
    head; and two passes are that stack applied to its own normed output."""
    once, twice = build(1), build(2)
    ids = _ids()
    cfg = once.cfg
    h = jnp.take(once.embed.weight._array, jnp.asarray(ids), axis=0)
    rotary = looped.rotary_tables(
        jnp.broadcast_to(jnp.arange(ids.shape[1]), ids.shape), cfg.head_dim,
        cfg.rope_theta)
    by_pass = []
    for _ in range(2):
        for layer in once.layers:
            h = layer(h, rotary)
        h = looped.rms_norm(h, once.norm_f, cfg.rms_eps)
        by_pass.append(np.asarray(h @ once.head.weight._array.T))
    for model, want in zip((once, twice), by_pass):
        np.testing.assert_allclose(
            np.asarray(model(paddle.to_tensor(ids))._array), want, rtol=0,
            atol=F32_TOL)


def test_forward_under_jit_is_the_eager_one():
    model, ids = build(2), _ids()
    params, buffers = state_dict_arrays(model)

    @jax.jit
    def run(params, ids):
        out, _ = functional_call(model, params, buffers, args=(ids,),
                                 training=False)
        return out

    np.testing.assert_allclose(
        np.asarray(run(params, jnp.asarray(ids))),
        np.asarray(model(paddle.to_tensor(ids))._array), rtol=0, atol=1e-5)


def test_rotary_turns_by_position_and_keeps_lengths():
    rng = np.random.default_rng(0)
    q, k = (jnp.asarray(rng.normal(size=(1, 1, 64)), jnp.float32)
            for _ in range(2))

    def at(x, pos):
        cos, sin = looped.rotary_tables(jnp.asarray([pos]), 64, 1e4)
        return looped.apply_rotary(x, cos, sin)

    np.testing.assert_allclose(np.linalg.norm(at(q, 37)),
                               np.linalg.norm(q), rtol=1e-5)
    np.testing.assert_allclose(at(q, 0), q, atol=1e-6)
    # a score depends on the distance between the two positions alone
    near = float((at(q, 5) * at(k, 2)).sum())
    far = float((at(q, 103) * at(k, 100)).sum())
    other = float((at(q, 5) * at(k, 3)).sum())
    assert abs(near - far) < 1e-3 < abs(near - other)


def test_models_state_their_cache_slabs():
    assert build(4, num_layers=3).kv_slabs == 12
    gpt = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=3,
                        num_heads=2, max_seq_len=32))
    assert gpt.kv_slabs == 3


def test_a_contiguous_decode_cache_is_refused_with_a_sentence():
    model = build(1)
    with pytest.raises(ValueError, match="paged cache"):
        model(paddle.to_tensor(_ids()), caches=[None])
