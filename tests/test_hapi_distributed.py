"""Model.fit over a fleet mesh (hapi + Fleet
sharding; reference hapi/model.py auto fleet integration). 8-device CPU
mesh via conftest."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.distributed.mesh import init_mesh, set_mesh


@pytest.fixture
def clean_mesh():
    yield
    set_mesh(None)


def _data(n=32, din=8, dout=4, seed=0):
    rs = np.random.RandomState(seed)
    return rs.rand(n, din).astype(np.float32), rs.rand(n, dout).astype(np.float32)


def _fit(mesh_degrees, steps=4, bs=8, mp_annotate=False):
    if mesh_degrees:
        init_mesh(mesh_degrees)
    else:
        set_mesh(None)
    paddle.seed(4)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    if mp_annotate:
        net[0].weight.sharding_axes = (None, "mp")
        net[2].weight.sharding_axes = ("mp", None)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2, parameters=net.parameters())
    model.prepare(opt, nn.MSELoss())
    xs, ys = _data(steps * bs)
    losses = []
    for i in range(steps):
        out = model.train_batch([xs[i * bs:(i + 1) * bs]], [ys[i * bs:(i + 1) * bs]])
        losses.append(out[0] if isinstance(out, list) else out)
    return [float(l[0]) if isinstance(l, list) else float(l) for l in losses], model


def test_model_fit_dp_sharding_matches_single_device(clean_mesh):
    ref, _ = _fit(None)
    dp, _ = _fit({"dp": 4, "sharding": 2})
    np.testing.assert_allclose(dp, ref, rtol=1e-4, atol=1e-6)


def test_model_fit_dp_mp_matches_single_device(clean_mesh):
    ref, _ = _fit(None, mp_annotate=False)
    mp, _ = _fit({"dp": 2, "mp": 2}, mp_annotate=True)
    np.testing.assert_allclose(mp, ref, rtol=1e-4, atol=1e-6)


def test_model_save_after_distributed_fit(clean_mesh, tmp_path):
    losses, model = _fit({"dp": 2, "sharding": 2}, steps=3)
    assert np.isfinite(losses).all()
    path = str(tmp_path / "dist_hapi" / "ck")
    model.save(path)
    sd = paddle.load(path + ".pdopt")
    assert any("moment1" in k for k in sd)  # real slots from the sharded step


def test_bert_model_fit_sharded(clean_mesh):
    """BERT-tiny via Model.fit on a dp x sharding mesh — the ERNIE-pretrain
    shape at test scale."""
    from paddle_tpu.models.bert import Bert, BertConfig

    init_mesh({"dp": 2, "sharding": 2, "mp": 2})
    paddle.seed(0)
    cfg = BertConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                     max_position_embeddings=32, dropout=0.0)
    net = Bert(cfg)

    class MLMLoss(nn.Layer):
        def forward(self, logits, nsp_logits, labels):
            from paddle_tpu.ops.loss_ops import cross_entropy

            return cross_entropy(
                logits.reshape([-1, cfg.vocab_size]), labels.reshape([-1])
            )

    model = paddle.Model(net)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3, parameters=net.parameters())
    model.prepare(opt, MLMLoss())
    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, (8, 16)).astype(np.int64)
    labels = rs.randint(0, 128, (8, 16)).astype(np.int64)
    losses = [
        model.train_batch([ids], [labels])[0] for _ in range(4)
    ]
    losses = [l[0] if isinstance(l, list) else l for l in losses]
    assert losses[-1] < losses[0], losses  # training under dp+zero+mp
    assert np.isfinite(losses).all()


def test_model_fit_ragged_dataset(clean_mesh):
    """fit with a dataset whose tail batch is ragged: auto drop_last under a
    mesh; DataLoader-committed arrays are re-placed on the mesh."""
    init_mesh({"dp": 4, "sharding": 2})
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    model.prepare(
        paddle.optimizer.Adam(learning_rate=1e-2, parameters=net.parameters()),
        nn.MSELoss(),
    )
    rs = np.random.RandomState(0)

    class DS(paddle.io.Dataset):
        def __len__(self):
            return 30  # not a multiple of batch 8

        def __getitem__(self, i):
            return rs.rand(8).astype(np.float32), rs.rand(4).astype(np.float32)

    model.fit(DS(), epochs=2, batch_size=8, verbose=0)  # must not raise

    # direct train_batch with an indivisible batch raises a CLEAR error
    import pytest as _pytest

    with _pytest.raises(ValueError, match="divisible"):
        model.train_batch([rs.rand(6, 8).astype(np.float32)],
                          [rs.rand(6, 4).astype(np.float32)])


def test_evaluate_sees_all_samples_under_mesh(clean_mesh):
    """eval/predict are unsharded: a ragged tail must NOT be dropped."""
    init_mesh({"dp": 4})
    paddle.seed(0)
    net = nn.Linear(8, 4)
    model = paddle.Model(net)
    model.prepare(None)
    rs = np.random.RandomState(0)

    class DS(paddle.io.Dataset):
        def __len__(self):
            return 10  # ragged vs batch 4

        def __getitem__(self, i):
            return (rs.rand(8).astype(np.float32),)

    outs = model.predict(DS(), batch_size=4, stack_outputs=True, verbose=0)
    assert outs[0].shape[0] == 10  # every sample predicted
