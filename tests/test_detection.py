"""Detection vertical: ops parity (matrix_nms / generate_proposals /
distribute_fpn_proposals / box_coder vs straightforward numpy references of
the reference-op semantics), the PP-YOLOE-class model, and the inference
predictor end-to-end with shape buckets.

Reference: /root/reference/paddle/fluid/operators/detection/*.cc (semantics),
python/paddle/vision/ops.py (API shapes).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.vision import ops as vops


# ---------------------------------------------------------------------------
# numpy references (reimplement semantics, not the reference code)
# ---------------------------------------------------------------------------

def _np_iou(a, b):
    lt = np.maximum(a[:2], b[:2])
    rb = np.minimum(a[2:], b[2:])
    wh = np.clip(rb - lt, 0, None)
    inter = wh[0] * wh[1]
    ar = lambda x: (x[2] - x[0]) * (x[3] - x[1])
    return inter / max(ar(a) + ar(b) - inter, 1e-10)


def _np_matrix_nms_class(boxes, scores, score_thr, post_thr, top_k, gaussian, sigma):
    """Decay NMS for one class, sorted-descending semantics."""
    idx = np.argsort(-scores)
    idx = [i for i in idx if scores[i] > score_thr][:top_k]
    out = []
    for r, i in enumerate(idx):
        decay = 1.0
        for rj in range(r):
            j = idx[rj]
            iou_ij = _np_iou(boxes[i], boxes[j])
            comp_j = max(
                (_np_iou(boxes[j], boxes[idx[rl]]) for rl in range(rj)), default=0.0
            )
            if gaussian:
                # reference kernel formula: exp((max_iou^2 - iou^2) * sigma)
                decay = min(decay, np.exp((comp_j**2 - iou_ij**2) * sigma))
            else:
                decay = min(decay, (1 - iou_ij) / max(1 - comp_j, 1e-10))
        ds = scores[i] * decay
        if ds > post_thr:
            out.append((i, ds))
    return out


class TestMatrixNMS:
    def test_matches_numpy_reference(self):
        rs = np.random.RandomState(0)
        M, C = 24, 3
        boxes = rs.rand(M, 4).astype(np.float32) * 50
        boxes[:, 2:] = boxes[:, :2] + 5 + rs.rand(M, 2).astype(np.float32) * 40
        scores = rs.rand(C, M).astype(np.float32)
        for gaussian in (False, True):
            out, idx, num = vops.matrix_nms(
                boxes[None], scores[None], 0.15, 0.25, 16, 32,
                use_gaussian=gaussian, gaussian_sigma=2.0,
                background_label=0, return_index=True,
            )
            got = np.asarray(out.numpy())
            n = int(num.numpy()[0])
            expect = []
            for c in range(1, C):  # class 0 = background, excluded
                for i, ds in _np_matrix_nms_class(
                    boxes, scores[c], 0.15, 0.25, 16, gaussian, 2.0
                ):
                    expect.append((c, ds, i))
            expect.sort(key=lambda t: -t[1])
            expect = expect[:32]
            assert n == len(expect), (n, len(expect))
            for r, (c, ds, i) in enumerate(expect):
                assert int(got[r, 0]) == c
                assert abs(got[r, 1] - ds) < 1e-4
                np.testing.assert_allclose(got[r, 2:], boxes[i], rtol=1e-5)
                assert int(idx.numpy()[r]) == i

    def test_padding_is_marked(self):
        boxes = np.array([[0, 0, 10, 10.0]], np.float32)
        scores = np.array([[0.9], [0.8]], np.float32)
        out, num = vops.matrix_nms(boxes[None], scores[None], 0.5, 0.5, 10, 8,
                                   background_label=-1)
        assert int(num.numpy()[0]) == 2
        got = np.asarray(out.numpy())
        assert (got[2:, 0] == -1).all()  # pad rows carry label -1


class TestGreedyNMS:
    def test_matches_host_nms(self):
        import jax.numpy as jnp

        rs = np.random.RandomState(1)
        n = 30
        boxes = rs.rand(n, 4).astype(np.float32) * 60
        boxes[:, 2:] = boxes[:, :2] + 4 + rs.rand(n, 2).astype(np.float32) * 30
        scores = rs.rand(n).astype(np.float32)
        keep, num = vops.nms_padded_array(
            jnp.asarray(boxes), jnp.asarray(scores), 0.4, n
        )
        ref = np.asarray(vops.nms(boxes, 0.4, scores=scores).numpy())
        got = np.asarray(keep)[: int(num)]
        np.testing.assert_array_equal(got, ref)


class TestBoxCoder:
    def test_encode_decode_roundtrip(self):
        rs = np.random.RandomState(2)
        P_, T_ = 5, 7
        priors = rs.rand(P_, 4).astype(np.float32) * 50
        priors[:, 2:] = priors[:, :2] + 10 + rs.rand(P_, 2).astype(np.float32) * 20
        targets = rs.rand(T_, 4).astype(np.float32) * 50
        targets[:, 2:] = targets[:, :2] + 10 + rs.rand(T_, 2).astype(np.float32) * 20
        enc = vops.box_coder(priors, None, targets, "encode_center_size")
        dec = vops.box_coder(priors, None, enc.numpy(), "decode_center_size")
        d = np.asarray(dec.numpy())  # [T,P,4]; diagonal-free: every prior decodes
        for t in range(T_):
            for p in range(P_):
                np.testing.assert_allclose(d[t, p], targets[t], rtol=1e-4, atol=1e-3)


class TestGenerateProposals:
    def _anchors(self, H, W, A, stride=8):
        a = np.zeros((H, W, A, 4), np.float32)
        for y in range(H):
            for x in range(W):
                for k in range(A):
                    cs = stride * (k + 1)
                    a[y, x, k] = [x * stride - cs / 2, y * stride - cs / 2,
                                  x * stride + cs / 2, y * stride + cs / 2]
        return a

    def test_invariants(self):
        rs = np.random.RandomState(3)
        N, A, H, W = 2, 3, 8, 8
        scores = rs.rand(N, A, H, W).astype(np.float32)
        deltas = (rs.rand(N, 4 * A, H, W).astype(np.float32) - 0.5) * 0.3
        anchors = self._anchors(H, W, A)
        var = np.ones_like(anchors) * 0.5
        img = np.array([[64, 64], [48, 56]], np.float32)
        rois, nums = vops.generate_proposals(
            scores, deltas, img, anchors, var,
            pre_nms_top_n=60, post_nms_top_n=12, nms_thresh=0.5, min_size=2.0,
        )
        r = np.asarray(rois.numpy()).reshape(N, 12, 4)
        ns = np.asarray(nums.numpy())
        for i in range(N):
            k = int(ns[i])
            assert 0 < k <= 12
            valid = r[i, :k]
            # clipped to the per-image size
            assert (valid[:, 0] >= 0).all() and (valid[:, 2] <= img[i, 1]).all()
            assert (valid[:, 1] >= 0).all() and (valid[:, 3] <= img[i, 0]).all()
            # min-size respected
            assert ((valid[:, 2] - valid[:, 0]) >= 2.0 - 1e-4).all()
            # pairwise IoU below the NMS threshold
            for a_ in range(k):
                for b_ in range(a_ + 1, k):
                    assert _np_iou(valid[a_], valid[b_]) <= 0.5 + 1e-5
            # padding rows are zero
            assert (r[i, k:] == 0).all()


class TestDistributeFPN:
    def test_levels_and_restore(self):
        rs = np.random.RandomState(4)
        R = 20
        rois = rs.rand(R, 4).astype(np.float32) * 80
        sizes = np.array([16, 32, 64, 128, 256] * 4, np.float32)[:R]
        rois[:, 2] = rois[:, 0] + sizes
        rois[:, 3] = rois[:, 1] + sizes
        multi, restore, nums = vops.distribute_fpn_proposals(rois, 2, 5, 4, 224)
        ns = np.asarray(nums.numpy())
        assert ns.sum() == R
        # expected level from the reference formula
        areas = sizes * sizes
        lvl = np.clip(
            np.floor(np.log2(np.sqrt(areas) / 224 + 1e-8)) + 4, 2, 5
        ).astype(int)
        for li in range(4):
            level_rois = np.asarray(multi[li].numpy())[: ns[li]]
            mine = rois[lvl == li + 2]
            np.testing.assert_allclose(level_rois, mine, rtol=1e-6)
        # restore index maps the level-concat back to input order
        concat = np.concatenate(
            [np.asarray(multi[li].numpy())[: ns[li]] for li in range(4)]
        )
        ri = np.asarray(restore.numpy())[:, 0]
        np.testing.assert_allclose(concat[ri], rois, rtol=1e-6)


class TestPPYOLOE:
    @pytest.mark.slow  # tier-1 headroom (PR 19): heaviest always-on case; tier-2 covers it
    def test_predict_shapes_and_validity(self):
        from paddle_tpu.vision.models import ppyoloe_s

        paddle.seed(0)
        m = ppyoloe_s(num_classes=4)
        m.eval()
        x = np.random.RandomState(0).rand(2, 3, 64, 64).astype(np.float32)
        out, nums = m.predict(x, keep_top_k=10)
        o = np.asarray(out.numpy()).reshape(2, 10, 6)
        ns = np.asarray(nums.numpy())
        assert ns.shape == (2,)
        for i in range(2):
            valid = o[i, : ns[i]]
            if len(valid):
                assert (valid[:, 2] >= 0).all() and (valid[:, 4] <= 64).all()
                assert (valid[:, 1] >= 0).all() and (valid[:, 1] <= 1).all()

    @pytest.mark.slow
    def test_simple_loss_trains(self):
        from paddle_tpu.vision.models import ppyoloe_s

        paddle.seed(0)
        m = ppyoloe_s(num_classes=3)
        opt = paddle.optimizer.Adam(learning_rate=5e-4, parameters=m.parameters())
        rs = np.random.RandomState(0)
        x = paddle.to_tensor(rs.rand(2, 3, 64, 64).astype(np.float32))
        gt_boxes = paddle.to_tensor(
            np.array([[[8, 8, 24, 24]], [[30, 30, 50, 50]]], np.float32)
        )
        gt_labels = paddle.to_tensor(np.array([[1], [2]]))
        losses = []
        for _ in range(3):
            cls, reg = m(x)
            loss = m.simple_loss(cls, reg, gt_boxes, gt_labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0], losses


class TestPredictorDetection:
    def test_shape_buckets_e2e(self):
        """Dynamic-shape inference: variable batch through the
        bucket-AOT predictor on a real detection model."""
        from paddle_tpu.inference import Config, create_predictor
        from paddle_tpu.vision.models import ppyoloe_s

        paddle.seed(0)

        built = {}

        def factory():
            m = ppyoloe_s(num_classes=4)
            m.eval()
            built["m"] = m
            return m

        cfg = Config()
        cfg.set_model_factory(factory)
        cfg.set_batch_buckets([2, 4])
        pred = create_predictor(cfg)
        rs = np.random.RandomState(0)
        for n in (1, 2, 3):
            outs = pred.run([rs.rand(n, 3, 64, 64).astype(np.float32)])
            # raw head outputs, truncated back to the real batch
            assert all(np.asarray(o).shape[0] == n for o in outs)
        # only two buckets -> at most two compiled signatures
        assert len(pred._compiled) <= 2


def test_box_coder_2d_decode_pairs_rows():
    """[T,4] deltas decode row t against prior t (not prior 0)."""
    rs = np.random.RandomState(5)
    n = 6
    priors = rs.rand(n, 4).astype(np.float32) * 50
    priors[:, 2:] = priors[:, :2] + 10 + rs.rand(n, 2).astype(np.float32) * 20
    targets = rs.rand(n, 4).astype(np.float32) * 50
    targets[:, 2:] = targets[:, :2] + 10 + rs.rand(n, 2).astype(np.float32) * 20
    enc = np.asarray(
        vops.box_coder(priors, None, targets, "encode_center_size").numpy()
    )
    deltas = enc[np.arange(n), np.arange(n)]  # row t encoded vs prior t
    dec = np.asarray(
        vops.box_coder(priors, None, deltas, "decode_center_size").numpy()
    )
    np.testing.assert_allclose(dec, targets, rtol=1e-4, atol=1e-3)


def test_distribute_fpn_respects_rois_num():
    """Pad rows (index >= rois_num) route to NO level and restore maps them
    past the valid rows (padded-capacity contract)."""
    rois = np.array(
        [[0, 0, 16, 16], [0, 0, 600, 600], [0, 0, 0, 0], [0, 0, 0, 0]],
        np.float32,
    )
    multi, restore, nums = vops.distribute_fpn_proposals(
        rois, 2, 5, 4, 224, rois_num=np.array([2], np.int32)
    )
    ns = np.asarray(nums.numpy())
    assert ns.sum() == 2  # pads counted nowhere
    assert ns[0] == 1 and ns[-1] == 1  # small -> level 2, big -> level 5
    ri = np.asarray(restore.numpy())[:, 0]
    assert set(ri[2:]) == {2, 3}  # pad rows sit past the valid rows
