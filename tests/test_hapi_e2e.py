"""End-to-end hapi Model tests (the reference's north-star config 1:
LeNet/MNIST via Model.fit)."""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.metric import Accuracy
from paddle_tpu.vision.datasets import MNIST
from paddle_tpu.vision.models import LeNet


def _fit_lenet(epochs=3, compiled=True):
    paddle.seed(0)
    net = LeNet()
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss(), Accuracy(), compiled=compiled)
    train = MNIST(mode="train")
    train.n = 256
    model.fit(train, epochs=epochs, batch_size=64, verbose=0)
    test = MNIST(mode="test")
    test.n = 128
    return model, model.evaluate(test, batch_size=64, verbose=0)


def test_lenet_mnist_convergence():
    model, res = _fit_lenet(epochs=4)
    assert res["acc"] > 0.9, res
    assert res["loss"] < 0.5


def test_eager_adapter_matches():
    model, res = _fit_lenet(epochs=2, compiled=False)
    # mechanism test (tape path), not a convergence benchmark: 2 epochs on
    # 256 samples must beat chance (0.1) clearly
    assert res["acc"] > 0.45, res


def test_train_batch_api():
    net = LeNet()
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.01, parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    x = np.random.rand(8, 1, 28, 28).astype(np.float32)
    y = np.random.randint(0, 10, (8, 1))
    loss1 = model.train_batch([x], [y])
    loss2 = model.train_batch([x], [y])
    assert loss2[0] < loss1[0]  # learning on a fixed batch


def test_predict():
    net = LeNet()
    model = paddle.Model(net)
    model.prepare()
    test = MNIST(mode="test")
    test.n = 32
    out = model.predict(test, batch_size=16, verbose=0)
    assert len(out) == 1
    assert out[0][0].shape == (16, 10)


def test_save_load(tmp_path):
    model, res = _fit_lenet(epochs=1)
    path = str(tmp_path / "ck" / "model")
    model.save(path)
    assert os.path.exists(path + ".pdparams")

    net2 = LeNet()
    model2 = paddle.Model(net2)
    opt2 = paddle.optimizer.Adam(parameters=net2.parameters())
    model2.prepare(opt2, nn.CrossEntropyLoss(), Accuracy())
    model2.load(path)
    for (k1, v1), (k2, v2) in zip(
        model.network.state_dict().items(), net2.state_dict().items()
    ):
        assert np.allclose(v1.numpy(), v2.numpy(), atol=1e-6)


def test_save_resume_matches_uninterrupted_trajectory(tmp_path):
    """Resume parity: fit -> save -> load -> fit must equal the uninterrupted
    run exactly, including Adam moments (reference optimizer state round-trip,
    python/paddle/hapi/model.py:1732 + optimizer.state_dict)."""

    def make():
        paddle.seed(7)
        net = nn.Sequential(nn.Linear(4, 16), nn.ReLU(), nn.Linear(16, 2))
        model = paddle.Model(net)
        opt = paddle.optimizer.Adam(learning_rate=1e-2, parameters=net.parameters())
        model.prepare(opt, nn.MSELoss())
        return model

    rs = np.random.RandomState(0)
    xs = [rs.rand(8, 4).astype(np.float32) for _ in range(6)]
    ys = [rs.rand(8, 2).astype(np.float32) for _ in range(6)]

    # uninterrupted: 6 steps
    m_ref = make()
    ref_losses = [m_ref.train_batch([x], [y])[0] for x, y in zip(xs, ys)]

    # interrupted: 3 steps, save, fresh model+optimizer, load, 3 more steps
    m1 = make()
    for x, y in zip(xs[:3], ys[:3]):
        m1.train_batch([x], [y])
    path = str(tmp_path / "resume" / "ck")
    m1.save(path)

    m2 = make()
    m2.load(path)
    resumed = [m2.train_batch([x], [y])[0] for x, y in zip(xs[3:], ys[3:])]
    for a, b in zip(resumed, ref_losses[3:]):
        assert np.allclose(a, b, rtol=1e-5, atol=1e-7), (resumed, ref_losses[3:])

    # the saved .pdopt must contain real (non-empty) slots after compiled training
    opt_sd = paddle.load(path + ".pdopt")
    slot_keys = [k for k in opt_sd if not k.startswith("@") and k != "LR_Scheduler"]
    assert slot_keys, "optimizer state_dict is empty after compiled training"
    moment1 = [k for k in slot_keys if "moment1" in k]
    assert moment1
    assert any(np.abs(opt_sd[k].numpy()).max() > 0 for k in moment1)


def test_paddle_save_load_tensors(tmp_path):
    obj = {"a": paddle.to_tensor(np.random.rand(3, 3).astype(np.float32)), "b": [1, 2]}
    p = str(tmp_path / "obj.pdt")
    paddle.save(obj, p)
    back = paddle.load(p)
    assert np.allclose(back["a"].numpy(), obj["a"].numpy())
    assert back["b"] == [1, 2]


def test_bf16_save_load(tmp_path):
    t = paddle.to_tensor(np.random.rand(4).astype(np.float32)).astype("bfloat16")
    p = str(tmp_path / "bf16.pdt")
    paddle.save({"t": t}, p)
    back = paddle.load(p)
    assert np.dtype(back["t"].dtype).name == "bfloat16"


def test_callbacks_early_stopping():
    from paddle_tpu.hapi.callbacks import EarlyStopping

    paddle.seed(0)
    net = LeNet()
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3, parameters=net.parameters())
    model.prepare(opt, nn.CrossEntropyLoss(), Accuracy())
    train = MNIST(mode="train")
    train.n = 128
    es = EarlyStopping(monitor="acc", mode="max", patience=0)
    model.fit(train, eval_data=train, epochs=3, batch_size=64, verbose=0, callbacks=[es])
    # just ensure it ran and the flag machinery works
    assert isinstance(model.stop_training, bool)


def test_summary():
    from paddle_tpu.hapi.summary import summary

    info = summary(LeNet())
    assert info["total_params"] > 40000


def test_model_fit_static_mode_matches_dynamic():
    """hapi StaticGraphAdapter (VERDICT r3 item 10): Model.prepare under
    paddle.enable_static() drives a captured Program; the fit loss
    trajectory must match dynamic mode exactly."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.vision.models import LeNet

    rs = np.random.RandomState(0)
    X = rs.rand(64, 1, 28, 28).astype(np.float32)
    Y = rs.randint(0, 10, (64, 1))

    def run(static):
        paddle.seed(0)
        net = LeNet()
        model = paddle.Model(net)
        if static:
            paddle.enable_static()
        try:
            model.prepare(
                paddle.optimizer.Adam(learning_rate=1e-3,
                                      parameters=net.parameters()),
                nn.CrossEntropyLoss(),
            )
            assert (model._static_adapter is not None) == static
            losses = []
            for ep in range(2):
                for i in range(0, 64, 32):
                    out = model.train_batch(
                        [paddle.to_tensor(X[i:i + 32])],
                        [paddle.to_tensor(Y[i:i + 32])],
                    )
                    loss = out[0] if not isinstance(out, tuple) else out[0][0]
                    losses.append(float(np.asarray(loss)))
        finally:
            if static:
                paddle.disable_static()
        return losses

    dyn = run(False)
    st = run(True)
    np.testing.assert_allclose(st, dyn, rtol=2e-4, err_msg=f"{(st, dyn)}")
    assert st[-1] < st[0]
