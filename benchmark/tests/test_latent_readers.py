"""The latent-attention / gated-expert decoder's counts (`reduce/latent.py`)
and the readers PR 40 added, each on a hand-made `Run`: the numbers asserted
were worked out by hand at small sizes. A program whose spans carry no
`attn_*` arguments and whose gauges lack `window_cache_bytes_per_lane` (any
run of another model) reads as None and never raises."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.record import Run  # noqa: E402
from benchmark.reduce import latent  # noqa: E402

# hidden 4. Full layer: 2 heads, r_q 3, r_kv 2, d_n 2, d_r 2, d_v 1; indexer
# 2 heads of 3: a-side 4 x (3 + 2 + 2 + 2 + 3 + 2) = 56, W_qb 3 x 2 x 4 = 24,
# W_kvb 2 x 2 x 3 = 12, W_Iq 3 x 2 x 3 = 18, W_o 2 x 1 x 4 = 8: 118.
# Window layer: 1 head, r_q 2, r_kv 3, d_n 2, d_r 2, d_v 2: a-side 4 x (2 + 3
# + 2 + 1) = 32, W_qb 2 x 4 = 8, W_kvb 3 x 4 = 12, W_o 2 x 4 = 8: 60.
# Dense FFN 3 x 4 x 5 = 60. Expert layer outside its experts: router 4 x 8 +
# shared 3 x 4 x 3 = 68. An expert 3 x 4 x 3 = 36. Head 9 x 4 = 36.
CONFIG = {"hidden_size": 4,
          "layer_types": ["full_attention", "full_attention",
                          "sliding_attention"],
          "first_k_dense_replace": 1, "intermediate_size": 5,
          "num_attention_heads": 2, "q_lora_rank": 3, "kv_lora_rank": 2,
          "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 1,
          "index_n_heads": 2, "index_head_dim": 3, "index_topk": 4,
          "swa_num_attention_heads": 1, "swa_q_lora_rank": 2,
          "swa_kv_lora_rank": 3, "swa_qk_nope_head_dim": 2,
          "swa_qk_rope_head_dim": 2, "swa_v_head_dim": 2,
          "sliding_window_size": 5, "moe_intermediate_size": 3,
          "router_experts": 8, "n_routed_experts": 2, "vocab_size": 9,
          "dtype": "bfloat16", "engine": {"max_batch": 4}}
DENSE = 2 * 118 + 60 + 60 + 2 * 68
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
    args = dict(fed_tokens=4, emitted_tokens=4, moe_pairs=32,
                moe_pairs_local=6, moe_experts_hit=3, moe_max_load=2,
                attn_keys_visible=80, attn_keys_selected=32,
                window_keys_read=20, attn_keys_visible_decode=80,
                attn_keys_selected_decode=32, moe_experts_hit_decode=3,
                kv_tokens=40)
    args.update(kw)
    return _step(i, "decode", start, end, **args)


def test_counts_follow_the_published_keys():
    assert latent.layers(CONFIG) == (2, 1, 1, 2)
    assert latent.attention_elements(CONFIG, "full_attention") == 118
    assert latent.attention_elements(CONFIG, "sliding_attention") == 60
    assert latent.moe_shared_elements(CONFIG) == 68
    assert latent.expert_elements(CONFIG) == 36
    assert latent.dense_elements(CONFIG) == DENSE
    # absorbed: 2 heads x (2 + 2 + 2); indexer 2 x 3; window expanded 1 x 6
    assert latent.selected_key_flops(CONFIG) == 2 * 2 * 6
    assert latent.index_key_flops(CONFIG) == 2 * 2 * 3
    assert latent.window_key_flops(CONFIG) == 2 * 1 * 6
    assert latent.serve_flops(CONFIG, 10, 2, 7, 30, 50, 11) == \
        2 * (10 * DENSE + 7 * 36 + 2 * 36) + 30 * 24 + 50 * 12 + 11 * 12
    # one window layer's row [c_kv 3 | k_r 2] in bf16
    assert latent.window_bytes_per_token(CONFIG) == 10


def test_the_published_sizes_give_the_published_model():
    """The counts at the catalog row's own keys: 279.6 B parameters in the
    language model, 16.3 B active a token (published as 288B-A17B with the
    towers and MTP), and the cut's 4.087 B."""
    config = spec.load_json(spec.HERE / "configs/dots3-ep8-serve.json")
    assert latent.attention_elements(config, "full_attention") == 144048128
    assert latent.attention_elements(config, "sliding_attention") == 90832896
    assert latent.expert_elements(config) == 23592960
    pub = {**config, **config["published"], "router_experts": 256}
    assert latent.layers(pub) == (13, 33, 1, 45)
    outside = latent.dense_elements(pub) \
        + 2 * pub["vocab_size"] * pub["hidden_size"]
    expert = latent.expert_elements(pub)
    assert round((outside + 45 * 256 * expert) / 1e9, 1) == 279.6
    assert round((outside + 45 * 8 * expert) / 1e9, 1) == 16.3
    cut = latent.dense_elements(config) + 4 * 32 * expert \
        + 2 * config["vocab_size"] * config["hidden_size"]
    assert round(cut / 1e9, 3) == 4.087
    assert latent.window_bytes_per_token(config) == 3 * 2176


def test_latent_mfu_counts_the_keys_the_steps_counted():
    steps = [_step(1, "mixed", 0.0, 1.0, fed_tokens=40, emitted_tokens=1,
                   moe_pairs=320, moe_pairs_local=50, attn_keys_visible=900,
                   attn_keys_selected=300, window_keys_read=190),
             _decode(2, 1.0, 2.0),
             _decode(3, 9.5, 10.5)]        # ends after the window
    value, n = _read("step.latent_mfu", _run(steps))
    assert n == 2
    assert value == pytest.approx(100 * latent.serve_flops(
        CONFIG, 44, 5, 56, 332, 980, 210) / (10.0 * 197e12))


def test_selection_and_routing_readers_read_the_decode_group_of_every_step():
    steps = [_decode(1, 0.0, 1.0),
             _decode(2, 1.0, 2.0, attn_keys_visible_decode=120,
                     moe_experts_hit_decode=4, moe_max_load=3),
             # a chunk row beside decode rows: the step's counts hold the
             # chunk's keys and hits, the decode group's do not
             _step(3, "mixed", 2.0, 3.0, fed_tokens=10, emitted_tokens=1,
                   moe_pairs=80, moe_pairs_local=8, moe_experts_hit=4,
                   moe_max_load=6, attn_keys_visible=155,
                   attn_keys_selected=72, window_keys_read=30,
                   attn_keys_visible_decode=100,
                   attn_keys_selected_decode=32, moe_experts_hit_decode=2),
             # a chunk row alone: no decode group to read
             _step(4, "mixed", 3.0, 4.0, fed_tokens=8, emitted_tokens=0,
                   moe_pairs=64, moe_pairs_local=0, moe_experts_hit=0,
                   moe_max_load=0, attn_keys_visible=36,
                   attn_keys_selected=30, window_keys_read=20,
                   attn_keys_visible_decode=0, attn_keys_selected_decode=0,
                   moe_experts_hit_decode=0)]
    run = _run(steps, gauges={"experts_held": 2})
    # 96 selected of 300 visible, over the three steps with a decode group
    assert _read("attn.selected_key_share", run) == \
        (pytest.approx(100 * 96 / 300), 3)
    # 2 held x 2 expert layers = 4 slots; the decode groups hit 3, 4 and 2
    assert _read("moe.gated_experts_hit_share", run) == \
        (pytest.approx(100 * 9 / 12), 3)
    # largest load over mean load, the three steps that computed a pair:
    # 2 / (6 / 4), 3 / (6 / 4), 6 / (8 / 4)
    assert _read("moe.gated_load_max_over_mean", run) == \
        (pytest.approx(2.0), 3)


def test_window_cache_share_reads_the_gauges():
    run = _run([], gauges={"window_cache_bytes_per_lane": 80,
                           "num_running": 3, "tokens_in_flight": 120})
    # 3 lanes x 80 B against 120 tokens x one window layer's 10 B
    assert _read("pool.window_cache_share", run) == \
        pytest.approx(100 * 240 / 1200)


@pytest.mark.parametrize("name", [
    "step.latent_mfu", "attn.selected_key_share", "pool.window_cache_share",
    "moe.gated_experts_hit_share", "moe.gated_load_max_over_mean"])
def test_a_program_without_the_counts_reads_as_none(name):
    """Another model's spans and gauges: nothing to read, nothing raised."""
    plain = [_step(1, "decode", 0.0, 1.0, fed_tokens=4, emitted_tokens=4,
                   kv_tokens=40, loop_steps=1, cache_slabs=2)]
    hybrid = {k: v for k, v in CONFIG.items() if k != "layer_types"}
    assert _read(name, _run(plain, gauges={"kv_slabs": 2},
                            config=hybrid)) is None
    assert _read(name, _run([], gauges=None)) is None
