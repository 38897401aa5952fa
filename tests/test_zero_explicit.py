"""Explicit ZeRO weight-update path (parallel/spmd.py, arXiv:2004.13336).

The parity matrix the PR 19 acceptance bar names: loss trajectories for
zero_stage {0, 2, 3} x gradient_merge {1, k} x remat {on, off} must agree
BIT-IDENTICALLY with the stage-0 GSPMD reference on the fake 8-device CPU
mesh (greedy-deterministic f32 — dropout 0, one key), int8 quantized
gradients sit behind a tolerance gate (the PR 17 AdaRound-NLL-gate
discipline), per-chip optimizer-state sharding is asserted on the PLACED
arrays, and a seeded trip test proves a silently-disabled reduce-scatter
busts the IR001 train budget — the regression hlolint exists to catch.
"""
import numpy as np
import pytest

import paddle_tpu as paddle

DP = 4
STEPS = 4


def _mesh():
    from paddle_tpu.distributed.mesh import init_mesh

    return init_mesh({"dp": DP})


def teardown_module():
    from paddle_tpu.distributed.mesh import set_mesh

    set_mesh(None)


def _batch():
    rs = np.random.RandomState(0)
    return (rs.randint(0, 64, (8, 16), dtype=np.int32),
            rs.randint(0, 64, (8, 16), dtype=np.int32))


def _run(zero_stage, gm=1, remat=False, quant=False, steps=STEPS,
         optimizer="AdamW", **kw):
    """Train `steps` steps; returns (losses, step, params, opt_state)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.analysis.ir import tiny_gpt_config
    from paddle_tpu.models.gpt import GPT, gpt_loss_fn
    from paddle_tpu.parallel.spmd import make_sharded_train_step

    mesh = _mesh()
    paddle.seed(0)
    model = GPT(tiny_gpt_config())
    opt = getattr(paddle.optimizer, optimizer)(
        learning_rate=0.01, parameters=model.parameters())
    step = make_sharded_train_step(
        model, gpt_loss_fn, opt, mesh, zero_stage=zero_stage,
        gradient_merge_k=gm, remat=remat, quant_grads=quant, **kw)
    params, buffers, opt_state = step.init_state()
    ids, labels = _batch()
    batch = step.shard_batch(ids, labels)
    key = jax.random.PRNGKey(0)
    losses = []
    for _ in range(steps):
        loss, params, buffers, opt_state = step(
            params, buffers, opt_state, jnp.float32(0.01), key, *batch)
        losses.append(float(np.asarray(loss)))
    return losses, step, params, opt_state


# one stage-0 GSPMD reference trajectory per (gm, remat) cell, computed
# lazily and shared across the matrix (4 compiles instead of 8)
_REFS = {}


def _reference(gm, remat):
    key = (gm, remat)
    if key not in _REFS:
        _REFS[key] = _run(0, gm=gm, remat=remat)
    return _REFS[key]


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("gm", [1, 2])
@pytest.mark.parametrize("zs", [2, 3])
def test_explicit_path_matches_stage0_bit_identical(zs, gm, remat):
    """The acceptance-bar parity gate: the explicit reduce-scatter +
    shard-local update + gather-updated-shards program replays the
    stage-0 GSPMD loss trajectory BIT-identically (deterministic f32),
    across gradient-merge and remat."""
    ref, _, _, _ = _reference(gm, remat)
    got, step, _, _ = _run(zs, gm=gm, remat=remat)
    assert step.explicit_update, "pure-dp zs>=2 must take the explicit path"
    assert got == ref, (zs, gm, remat, got, ref)


def test_quantized_grads_within_tolerance_and_converging():
    """int8 gradient reduce-scatter (EQuARX wire format) is opt-in and
    tolerance-gated, PR 17 AdaRound-gate style: the trajectory must track
    the f32 reference closely AND actually descend — a quantizer bug that
    zeroed or saturated gradients would stall the loss and trip this even
    inside the tolerance band."""
    ref, _, _, _ = _reference(1, False)
    got, step, _, _ = _run(2, quant=True)
    assert step.quant_grads
    drift = max(abs(a - b) for a, b in zip(got, ref))
    assert drift < 0.02, (drift, got, ref)
    assert got[-1] < got[0] - 0.5, got


@pytest.mark.parametrize("opt", ["Lamb", "Lars"])
@pytest.mark.parametrize("zs", [2, 3])
def test_trust_ratio_optimizers_on_explicit_path(zs, opt):
    """ROADMAP 5(b): Lars/Lamb per-tensor trust ratios on the explicit
    shard-local update. Each norm is a psum of shard-local partial
    squared sums (`optimizer.optimizers.sharded_norms`), so the 1/dp
    flat shards see FULL-tensor norms: the trajectory tracks the
    stage-0 GSPMD reference to reduction-order noise (Lars lands bit-
    identical; Lamb's moment normalization amplifies 1-ulp sum-order
    differences) and actually descends."""
    ref, _, _, _ = _run(0, optimizer=opt)
    got, step, _, _ = _run(zs, optimizer=opt)
    assert step.explicit_update
    drift = max(abs(a - b) for a, b in zip(got, ref))
    assert drift < 1e-5, (drift, got, ref)
    assert got[-1] < got[0], got


def test_optimizer_state_shards_dp_fold_on_placed_arrays():
    """The placed init_state arrays, not specs: every param-shaped AdamW
    slot holds 1/dp of its elements per chip, scalars replicate, and the
    per-chip byte total drops ~dp-fold vs the stage-0 replicated state."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.spmd import per_chip_opt_state_bytes

    _, _, _, state0 = _reference(1, False)
    _, step, params, state2 = _run(2)
    for name, slots in state2.items():
        for slot, arr in slots.items():
            shard = arr.addressable_shards[0]
            if arr.ndim == 0:       # beta pows replicate
                assert shard.data.size == arr.size, (name, slot)
            else:                   # flat [n_pad] leaves, 1/dp per chip
                assert arr.sharding.spec == P("dp"), (name, slot)
                assert shard.data.size * DP == arr.size, (name, slot)
    b0 = per_chip_opt_state_bytes(state0)
    b2 = per_chip_opt_state_bytes(state2)
    # padding + replicated scalars keep it shy of exactly dp-fold
    assert b2 * (DP - 1) < b0, (b0, b2)


def test_stage3_params_stay_sharded_and_gather_round_trips():
    """Stage 3: params live as padded-flat dp-sharded leaves (1/dp per
    chip, never re-materialized), and gather_params reconstructs natural
    shapes that track the stage-0 reference."""
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    _, _, p0, _ = _reference(1, False)
    _, step, p3, _ = _run(3)
    for k, v in p3.items():
        assert v.ndim == 1 and v.sharding.spec == P("dp"), k
        assert v.addressable_shards[0].data.size * DP == v.size, k
    nat = step.gather_params(p3)
    for k in p0:
        assert nat[k].shape == p0[k].shape, k
        # losses are bit-identical; params agree to reduction-order noise
        # (Adam normalizes near-zero grads, amplifying 1-ulp sum-order
        # differences between all-reduce and reduce-scatter)
        np.testing.assert_allclose(np.asarray(nat[k]), np.asarray(p0[k]),
                                   atol=1e-2, rtol=0)


def test_explicit_path_guards():
    """Misconfigurations fail loudly at construction: quant_grads off the
    explicit path, explicit_update on a dp x mp mesh, grad_clip and
    per-tensor-reduction optimizers without the sharded-norm bridge
    (DGC's top-k) on the shard-local update — Lars/Lamb are ADMITTED
    now (their norms psum via `sharded_norms`)."""
    from paddle_tpu.analysis.ir import tiny_gpt_config
    from paddle_tpu.distributed.mesh import init_mesh
    from paddle_tpu.models.gpt import GPT, gpt_loss_fn
    from paddle_tpu.parallel.spmd import make_sharded_train_step

    paddle.seed(0)
    model = GPT(tiny_gpt_config())
    mesh = _mesh()
    mk = lambda opt, **kw: make_sharded_train_step(
        model, gpt_loss_fn, opt, mesh, **kw)
    sgd = lambda **kw: paddle.optimizer.SGD(
        learning_rate=0.1, parameters=model.parameters(), **kw)
    with pytest.raises(ValueError, match="quant_grads"):
        mk(sgd(), zero_stage=0, quant_grads=True)
    with pytest.raises(ValueError, match="grad_clip"):
        mk(sgd(grad_clip=paddle.nn.ClipGradByGlobalNorm(1.0)), zero_stage=2)
    from paddle_tpu.optimizer.optimizers import DGCMomentum

    with pytest.raises(ValueError, match="per-tensor"):
        mk(DGCMomentum(learning_rate=0.01,
                       parameters=model.parameters()),
           zero_stage=2)
    # Lamb/Lars declare _sharded_norm_ready: construction succeeds and
    # takes the explicit path (trajectory parity is its own test)
    assert mk(paddle.optimizer.Lamb(
        learning_rate=0.01, parameters=model.parameters()),
        zero_stage=2).explicit_update
    with pytest.raises(ValueError, match="pure-dp"):
        make_sharded_train_step(
            model, gpt_loss_fn, sgd(), init_mesh({"dp": 2, "mp": 2}),
            zero_stage=2, explicit_update=True)
    # dp x mp at zero_stage>=2 silently keeps the GSPMD path (the legacy
    # 'sharding'-axis meshes in test_distributed_spmd.py rely on this)
    step = make_sharded_train_step(
        model, gpt_loss_fn, sgd(), init_mesh({"dp": 2, "mp": 2}),
        zero_stage=2)
    assert not step.explicit_update


def test_disabled_reduce_scatter_trips_ir001_train_budget(monkeypatch):
    """The seeded hlolint regression: if the explicit path's
    reduce-scatter silently degrades to a full-size all-reduce (here:
    `jax.lax.psum_scatter` monkeypatched to psum + local slice — same
    numerics, wrong collective), the train/* IR001 budget must bust on
    the missing reduce-scatter of the COMPILED program. The surplus
    all-reduces are witnessed on the LOWERED program, one a parameter
    leaf beside the loss's: XLA's combiner folds them into one op when it
    compiles (on the CPU since jax 0.9.0, on the TPU as well), so the
    compiled all-reduce count cannot tell the two programs apart."""
    import jax

    from paddle_tpu.analysis import contracts
    from paddle_tpu.analysis.ir import train_artifact
    from paddle_tpu.parallel.spmd import ShardedTrainStep

    real_axis_index = jax.lax.axis_index

    def fake_psum_scatter(x, axis_name, *, scatter_dimension=0, tiled=True):
        full = jax.lax.psum(x, axis_name)
        shard = x.shape[scatter_dimension] // DP
        return jax.lax.dynamic_slice_in_dim(
            full, real_axis_index(axis_name) * shard, shard,
            axis=scatter_dimension)

    lowered_texts = []
    real_lower_step = ShardedTrainStep.lower_step

    def recording_lower_step(self, *args):
        lowered, donation = real_lower_step(self, *args)
        lowered_texts.append(lowered.as_text())
        return lowered, donation

    monkeypatch.setattr(jax.lax, "psum_scatter", fake_psum_scatter)
    monkeypatch.setattr(ShardedTrainStep, "lower_step", recording_lower_step)
    art = train_artifact({"dp": DP}, zero_stage=2, optimizer="AdamW",
                         name="train/dp4/zs2")
    budget = art.expected["collective_budget"]
    (text,) = lowered_texts
    assert text.count("stablehlo.reduce_scatter") == 0
    assert (text.count("stablehlo.all_reduce")
            == budget["all-reduce"] + budget["reduce-scatter"]), budget
    assert art.collectives["reduce-scatter"] == 0, art.collectives
    violations = contracts.evaluate([art], select=["IR001"])
    msgs = "\n".join(v.format() for v in violations)
    assert "reduce-scatter count 0 != budget" in msgs, msgs
