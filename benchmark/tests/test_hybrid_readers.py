"""The hybrid decoder's counts (`reduce/hybrid.py`) and the six readers PR 36
added, each on a hand-made `Run`: the numbers asserted were worked out by
hand at small sizes. A program whose spans carry no `moe_*` arguments and
whose gauges lack `state_bytes_per_lane` (the parent of that PR, or any run
of another model) reads as None and never raises."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.record import Run  # noqa: E402
from benchmark.reduce import hybrid  # noqa: E402

# hidden 4; Mamba 2 heads x 3 = inner 6, groups 1, state 5: xBC 6 + 10 = 16,
# in_proj 4 x (6 + 16 + 2) = 96, out_proj 24: 120. Attention 2 heads over 1
# K/V head of 3: 4 x (2 + 2) x 3 + 2 x 3 x 4 = 72. Expert layer outside its
# experts: router 4 x 8, latent 2 x 4 x 2, shared 2 x 4 x 7: 32 + 16 + 56 =
# 104. An expert: 2 x 2 x 3 = 12. Head 9 x 4 = 36.
CONFIG = {"hidden_size": 4, "hybrid_override_pattern": "MEM*E",
          "mamba_num_heads": 2, "mamba_head_dim": 3, "n_groups": 1,
          "ssm_state_size": 5, "conv_kernel": 4, "num_attention_heads": 2,
          "num_key_value_heads": 1, "head_dim": 3, "moe_latent_size": 2,
          "moe_intermediate_size": 3,
          "moe_shared_expert_intermediate_size": 7, "router_experts": 8,
          "n_routed_experts": 2, "vocab_size": 9, "dtype": "bfloat16",
          "engine": {"max_batch": 4}}
DENSE = 2 * 120 + 72 + 2 * 104
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _read(name, run):
    return spec.load_reader("per_layer", name).read(run)


def _run(steps, gauges=None, config=CONFIG, t0=0.0, t1=10.0):
    run = Run({}, {"name": "cell"}, config, {}, None, V5E, None, 0.0)
    run.t0, run.t1, run.engine_steps, run.gauges = t0, t1, steps, gauges
    return run


def _step(i, kind, start, end, **args):
    return {"id": i, "kind": kind, "start": start, "end": end,
            "phases": {}, "args": {"step": i, "kind": kind, **args}}


def _decode(i, start, end, **kw):
    args = dict(fed_tokens=4, emitted_tokens=4, moe_pairs=16,
                moe_pairs_local=4, moe_experts_hit=3, moe_max_load=2,
                state_rows=4, kv_tokens=40)
    args.update(kw)
    return _step(i, "decode", start, end, **args)


def test_counts_follow_the_published_keys():
    assert hybrid.layers(CONFIG) == (2, 1, 2)
    assert hybrid.mamba_elements(CONFIG) == 120
    assert hybrid.attention_elements(CONFIG) == 72
    assert hybrid.moe_shared_elements(CONFIG) == 104
    assert hybrid.expert_elements(CONFIG) == 12
    assert hybrid.serve_flops(CONFIG, 10, 2, 7) == \
        2 * (10 * DENSE + 7 * 12 + 2 * 36)
    # a lane: 2 layers x (6 x 5 floats + 3 x 16 bf16)
    assert hybrid.state_bytes_per_lane(CONFIG) == 2 * (120 + 96)
    assert hybrid.kv_bytes_per_token(CONFIG) == 2 * 1 * 3 * 2
    # dense and head in bf16, the two routers 2 bytes an element more; 3
    # experts hit; 4 rows' state twice; 40 tokens of K/V
    assert hybrid.decode_stream_bytes(CONFIG, 4, 3, 40) == \
        (DENSE + 36) * 2 + 2 * 32 * 2 + 3 * 12 * 2 + 2 * 4 * 432 + 40 * 12


def test_the_published_sizes_give_the_published_model():
    """The counts at the catalog row's own keys: 120.67 B parameters in all,
    12.77 B active a token (the model's name says 120B-A12B)."""
    config = spec.load_json(spec.HERE / "configs/nemotron3s-ep4-serve.json")
    pub = {**config, **config["published"], "router_experts": 512}
    n_m, n_a, n_e = hybrid.layers(pub)
    assert (n_m, n_a, n_e) == (40, 8, 40)
    outside = (n_m * hybrid.mamba_elements(pub)
               + n_a * hybrid.attention_elements(pub)
               + n_e * hybrid.moe_shared_elements(pub)
               + 2 * pub["vocab_size"] * pub["hidden_size"])
    expert = hybrid.expert_elements(pub)
    assert round((outside + n_e * 512 * expert) / 1e9, 2) == 120.67
    assert round((outside + n_e * 22 * expert) / 1e9, 2) == 12.77
    assert expert == 5505024
    # and the cut: what one lane's state and one token's K/V take
    assert hybrid.state_bytes_per_lane(config) == 21278720
    assert hybrid.kv_bytes_per_token(config) == 1024


def test_hybrid_mfu_counts_the_pairs_the_steps_computed():
    steps = [_step(1, "mixed", 0.0, 1.0, fed_tokens=40, emitted_tokens=1,
                   moe_pairs=160, moe_pairs_local=50),
             _decode(2, 1.0, 2.0),
             _decode(3, 9.5, 10.5)]        # ends after the window
    value, n = _read("step.hybrid_mfu", _run(steps))
    assert n == 2
    assert value == pytest.approx(
        100 * hybrid.serve_flops(CONFIG, 44, 5, 54) / (10.0 * 197e12))


def test_decode_stream_share_is_the_median_over_decode_steps():
    steps = [_decode(1, 0.0, 1.0), _decode(2, 1.0, 3.0),
             _decode(3, 3.0, 7.0),
             _step(4, "mixed", 7.0, 8.0, fed_tokens=9, emitted_tokens=1,
                   moe_pairs=36, moe_pairs_local=9, moe_experts_hit=4,
                   moe_max_load=5, state_rows=2, kv_tokens=9)]
    value, n = _read("hybrid.decode_stream_share", _run(steps))
    assert n == 3
    assert value == pytest.approx(
        100 * hybrid.decode_stream_bytes(CONFIG, 4, 3, 40) / 819e9 / 2.0)


def test_routing_readers():
    steps = [_decode(1, 0.0, 1.0),                       # 4 of 16, hit 3
             _decode(2, 1.0, 2.0, moe_pairs_local=8, moe_experts_hit=4,
                     moe_max_load=3),
             _step(3, "mixed", 2.0, 3.0, fed_tokens=10, emitted_tokens=1,
                   moe_pairs=40, moe_pairs_local=8, moe_experts_hit=4,
                   moe_max_load=6, state_rows=2, kv_tokens=10)]
    run = _run(steps, gauges={"experts_held": 2})
    assert _read("moe.local_pair_share", run) == \
        (pytest.approx(100 * 20 / 72), 3)
    # 2 held x 2 expert layers = 4 slots; decode steps hit 3 and 4
    assert _read("moe.experts_hit_share", run) == \
        (pytest.approx(100 * 7 / 8), 2)
    # max over mean a step: 2 / (4/4), 3 / (8/4), 6 / (8/4): median 2
    assert _read("moe.load_max_over_mean", run) == (pytest.approx(2.0), 3)


def test_state_hbm_share_reads_the_gauge():
    run = _run([], gauges={"state_bytes_per_lane": 21278720})
    assert _read("pool.state_hbm_share", run) == \
        pytest.approx(100 * 4 * 21278720 / 16e9)


@pytest.mark.parametrize("name", [
    "step.hybrid_mfu", "hybrid.decode_stream_share", "moe.local_pair_share",
    "moe.experts_hit_share", "moe.load_max_over_mean",
    "pool.state_hbm_share"])
def test_a_program_without_the_counts_reads_as_none(name):
    """The parent's spans and gauges: nothing to read, nothing raised."""
    plain = [_step(1, "decode", 0.0, 1.0, fed_tokens=4, emitted_tokens=4,
                   kv_tokens=40, loop_steps=1, cache_slabs=2)]
    assert _read(name, _run(plain, gauges={"kv_slabs": 2})) is None
    assert _read(name, _run([], gauges=None)) is None
