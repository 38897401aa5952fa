"""jit.to_static, amp, DataLoader, PyLayer, recompute tests."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


def test_to_static_function():
    calls = []

    @paddle.jit.to_static
    def f(x, y):
        calls.append(1)
        return x * 2 + y

    a = paddle.to_tensor(np.ones(4, np.float32))
    b = paddle.to_tensor(np.ones(4, np.float32))
    out1 = f(a, b)
    out2 = f(a, b)
    assert np.allclose(out1.numpy(), 3.0)
    assert np.allclose(out2.numpy(), 3.0)
    assert len(calls) == 1  # traced once, cached executable reused


def test_to_static_layer():
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    x = paddle.randn([3, 4])
    ref = net(x).numpy()
    snet = paddle.jit.to_static(net)
    out = snet(x)
    assert np.allclose(out.numpy(), ref, atol=1e-5)


def test_dataloader_basics():
    from paddle_tpu.io import DataLoader, TensorDataset

    xs = paddle.randn([20, 3])
    ys = paddle.arange(20)
    ds = TensorDataset([xs, ys])
    loader = DataLoader(ds, batch_size=6, shuffle=False, drop_last=False)
    batches = list(loader)
    assert len(batches) == 4
    x0, y0 = batches[0]
    assert x0.shape == [6, 3]
    assert y0.numpy().tolist() == [0, 1, 2, 3, 4, 5]


def test_dataloader_shuffle_and_drop():
    from paddle_tpu.io import DataLoader, TensorDataset

    ds = TensorDataset([paddle.arange(10)])
    loader = DataLoader(ds, batch_size=3, shuffle=True, drop_last=True)
    batches = list(loader)
    assert len(batches) == 3


def test_distributed_batch_sampler():
    from paddle_tpu.io import DistributedBatchSampler, TensorDataset

    ds = TensorDataset([paddle.arange(10)])
    s0 = DistributedBatchSampler(ds, batch_size=2, num_replicas=2, rank=0)
    s1 = DistributedBatchSampler(ds, batch_size=2, num_replicas=2, rank=1)
    i0 = [i for b in s0 for i in b]
    i1 = [i for b in s1 for i in b]
    assert len(i0) == len(i1) == 5
    assert set(i0) | set(i1) == set(range(10))


def test_amp_autocast_flags():
    from paddle_tpu.amp.auto_cast import amp_state

    assert not amp_state().enabled
    with paddle.amp.auto_cast():
        assert amp_state().enabled
        assert amp_state().dtype == "bfloat16"
    assert not amp_state().enabled


def test_grad_scaler_noop_flow():
    w = paddle.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])
    scaler = paddle.amp.GradScaler(enable=False)
    loss = (w * 2.0).sum()
    scaled = scaler.scale(loss)
    scaled.backward()
    scaler.step(opt)
    assert abs(w.numpy()[0] - 0.8) < 1e-6


def test_grad_scaler_dynamic():
    scaler = paddle.amp.GradScaler(init_loss_scaling=4.0, incr_every_n_steps=1)
    w = paddle.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])
    loss = (w * 1.0).sum()
    scaler.scale(loss).backward()
    scaler.step(opt)
    scaler.update()
    assert scaler._scale == 8.0  # grew after a good step


def test_grad_scaler_single_unscale_with_clip():
    # documented pattern: unscale_ -> clip -> step must divide by scale ONCE
    scaler = paddle.amp.GradScaler(init_loss_scaling=4.0, use_dynamic_loss_scaling=False)
    w = paddle.Parameter(np.array([1.0], np.float32))
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w])
    loss = (w * 2.0).sum()  # dL/dw = 2
    scaler.scale(loss).backward()  # grad = 8
    scaler.unscale_(opt)  # grad = 2 (back to true)
    scaler.step(opt)  # must NOT unscale again
    scaler.update()
    assert abs(w.numpy()[0] - (1.0 - 0.1 * 2.0)) < 1e-6

    # double unscale_ raises
    loss2 = (w * 2.0).sum()
    scaler.scale(loss2).backward()
    scaler.unscale_(opt)
    import pytest

    with pytest.raises(RuntimeError):
        scaler.unscale_(opt)
    scaler.step(opt)
    with pytest.raises(RuntimeError):
        scaler.step(opt)
    scaler.update()  # resets state machine
    opt.clear_grad()


def test_grad_scaler_two_optimizers_independent_inf():
    """One optimizer's inf grads must not be erased by another's clean
    unscale_: opt1 skips its step, opt2 still steps, the scale backs off."""
    scaler = paddle.amp.GradScaler(init_loss_scaling=2.0)
    w1 = paddle.Parameter(np.array([1.0], np.float32))
    w2 = paddle.Parameter(np.array([1.0], np.float32))
    opt1 = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w1])
    opt2 = paddle.optimizer.SGD(learning_rate=0.1, parameters=[w2])
    import jax.numpy as jnp

    w1._grad = jnp.asarray(np.array([np.inf], np.float32))
    w2._grad = jnp.asarray(np.array([2.0], np.float32))
    scaler.unscale_(opt1)
    scaler.unscale_(opt2)  # clean — must not clear opt1's inf
    scaler.step(opt1)
    scaler.step(opt2)
    scaler.update()
    assert w1.numpy()[0] == 1.0  # skipped
    assert abs(w2.numpy()[0] - (1.0 - 0.1 * 1.0)) < 1e-6  # grad 2/scale 2
    assert scaler._scale == 1.0  # backed off from 2.0


def test_pylayer():
    from paddle_tpu.autograd import PyLayer

    class Double(PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * 2

        @staticmethod
        def backward(ctx, grad):
            return grad * 2

    x = paddle.to_tensor(np.array([3.0], np.float32), stop_gradient=False)
    y = Double.apply(x)
    y.backward()
    assert np.allclose(y.numpy(), [6.0])
    assert np.allclose(x.grad.numpy(), [2.0])


def test_recompute():
    from paddle_tpu.distributed.fleet.utils import recompute

    lin = nn.Linear(4, 4)
    x = paddle.randn([2, 4])
    x.stop_gradient = False
    y = recompute(lin, x).sum()
    y.backward()
    assert lin.weight.grad is not None
    assert x.grad is not None


def test_jacobian_hessian():
    x = paddle.to_tensor(np.array([1.0, 2.0], np.float32), stop_gradient=False)
    jac = paddle.autograd.jacobian(lambda v: (v * v).sum(), x)
    assert np.allclose(jac.numpy(), [2.0, 4.0])
    hes = paddle.autograd.hessian(lambda v: (v * v).sum(), x)
    assert np.allclose(hes.numpy(), 2 * np.eye(2))


def test_sdpa_matches_manual():
    q = paddle.randn([1, 4, 2, 8])
    out = F.scaled_dot_product_attention(q, q, q)
    qn = q.numpy().transpose(0, 2, 1, 3)  # b h s d
    s = (qn @ qn.transpose(0, 1, 3, 2)) / np.sqrt(8)
    p = np.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = (p @ qn).transpose(0, 2, 1, 3)
    assert np.allclose(out.numpy(), ref, atol=1e-4)


def test_sdpa_causal_grad():
    q = paddle.randn([1, 4, 2, 8])
    q.stop_gradient = False
    out = F.scaled_dot_product_attention(q, q, q, is_causal=True)
    out.sum().backward()
    assert q.grad is not None


def test_flash_attention_pallas_interpret():
    """Run the actual Pallas kernel in interpret mode on CPU."""
    import os

    os.environ["PADDLE_TPU_FORCE_PALLAS_INTERPRET"] = "1"
    try:
        from paddle_tpu.ops.pallas.flash_attention import (
            _attention_xla,
            flash_attention_array,
        )
        import jax.numpy as jnp

        q = np.random.rand(1, 128, 2, 16).astype(np.float32)
        out = flash_attention_array(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q), causal=True)
        ref = _attention_xla(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q), causal=True)
        assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-3)
    finally:
        del os.environ["PADDLE_TPU_FORCE_PALLAS_INTERPRET"]


@pytest.mark.slow
def test_flash_attention_mask_grad_matches_xla():
    """Pallas path must differentiate an additive mask (e.g. a trainable
    relative-position bias) identically to the XLA fallback."""
    import os

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import (
        _attention_xla,
        flash_attention_array,
    )

    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.rand(2, 128, 2, 16).astype(np.float32))
    k = jnp.asarray(rs.rand(2, 128, 2, 16).astype(np.float32))
    v = jnp.asarray(rs.rand(2, 128, 2, 16).astype(np.float32))

    q1, k1, v1 = q[:1], k[:1], v[:1]  # batch-1: a (1,H) mask reaches the
    # kernel un-broadcast (mask_b=1, mask_h=H)
    cases = [
        (q, k, v, (1, 1, 128, 128)),
        (q, k, v, (2, 2, 128, 128)),
        (q, k, v, (2, 1, 128, 128)),
        (q, k, v, (1, 2, 128, 128)),
        (q1, k1, v1, (1, 2, 128, 128)),
    ]
    for qq, kk, vv, mshape in cases:
        mask = jnp.asarray(rs.randn(*mshape).astype(np.float32) * 0.5)

        def loss_pallas(m):
            os.environ["PADDLE_TPU_FORCE_PALLAS_INTERPRET"] = "1"
            try:
                return flash_attention_array(qq, kk, vv, mask=m).sum()
            finally:
                del os.environ["PADDLE_TPU_FORCE_PALLAS_INTERPRET"]

        def loss_xla(m):
            return _attention_xla(qq, kk, vv, mask=m).sum()

        g_pallas = jax.grad(loss_pallas)(mask)
        g_xla = jax.grad(loss_xla)(mask)
        assert g_pallas.shape == mask.shape
        assert np.abs(np.asarray(g_xla)).max() > 1e-4  # non-trivial gradient
        assert np.allclose(np.asarray(g_pallas), np.asarray(g_xla), atol=2e-3), mshape
