"""The latent-attention / gated-expert block served through `LLMEngine`:
prefill in chunks and then decode through its three kinds of cache (a full
layer's latent rows and index keys in blocks, a window layer's latent rows
in a ring a lane), against the plain reference's full forward
(`benchmark/reference/dots3_block.py`), on logits kept as
`tests/test_serving_nemotron_h.py` keeps them (`_serving_common.Served`).

Tolerance. Float32 weights and caches: served logits and the reference's
differ in the order of float32 sums (absorbed products at width 1, a tile of
queries at a time, the ring). 1e-4 on logits whose spread is about 0.65;
measured 2e-6.
"""
import hashlib
import json
from pathlib import Path

import pytest

from paddle_tpu.serving import LLMEngine

import _nemotron_common as hybrid
from _dots3_common import (SLIDING, TOPK, VOCAB, build, load_reference,
                           params_of, published_keys)
from _serving_common import Served, prompts_of

REPO = Path(__file__).resolve().parents[1]
TOL = 1e-4
ref = load_reference()
KW = dict(block_size=4, max_batch=3, max_seq_len=96, prefill_chunk=8)


def _reference(model, ids):
    return ref.sequence_logits(params_of(model), ids, published_keys(model))


def _prompts(lengths, seed=0):
    return prompts_of(lengths, VOCAB, seed)


def _worst(served, rid, prompt):
    return served.worst(_reference, rid, prompt)


def _serve(served, prompts, max_new_tokens):
    return served.serve(prompts, max_new_tokens)


def test_chunked_prefill_then_decode_gives_the_references_logits():
    """Four requests through three lanes in chunks of 8: prompts of 29, 5,
    21 and 13 tokens. The top-k boundary (8 keys) and the window's (9) are
    crossed INSIDE the second chunk (positions 8-15), the ring of 8 wraps
    three times under the longest prompt, decode rows ride beside chunk
    rows, and the fourth request takes a lane another left."""
    assert TOPK == 8 and SLIDING == 9
    served = Served(build(experts_held=4, experts_from=4), **KW)
    prompts = _prompts((29, 5, 21, 13))
    rids = _serve(served, prompts, 9)
    for rid, prompt in zip(rids, prompts):
        assert _worst(served, rid, prompt) < TOL
    assert [p for p in served.plans
            if any(c > 1 for *_, c in p) and any(c == 1 and s > 0
                                                 for _, _, s, c in p)], \
        "no step held a chunk row beside a decode row"
    m = served.engine.metrics
    assert m.counters["jit_traces"] == 2 and m.gauges["jit_retraces"] == 0
    assert m.gauges["experts_held"] == 4 and m.gauges["experts_total"] == 16
    # float32 here: a latent row (16 + 8, and a window layer's 24 + 8) is
    # stored in whole lanes of 128, an index key is 16 wide; two full
    # layers, two window layers, a ring of 8
    assert m.gauges["cache_bytes_per_token_latent"] == 2 * 128 * 4
    assert m.gauges["cache_bytes_per_token_index_key"] == 2 * 16 * 4
    assert m.gauges["window_cache_bytes_per_lane"] == 2 * 8 * 128 * 4


def test_preemption_by_recompute_gives_the_references_logits():
    """An arena too small for three requests at once: the youngest is
    preempted, re-admitted (over whatever its lane's ring holds) and
    replayed from position 0; its logits are the reference's all the same."""
    served = Served(build(), num_blocks=14, **KW)
    prompts = _prompts((17, 14, 12), seed=3)
    rids = _serve(served, prompts, 12)
    assert served.engine.metrics.counters["preemptions"] >= 1
    for rid, prompt in zip(rids, prompts):
        assert _worst(served, rid, prompt) < TOL
    assert served.engine.pool.num_free == served.engine.pool.num_blocks - 1


def test_the_window_layers_cache_does_not_grow_with_the_sequence():
    """Twice the context: the kinds that live the sequence take twice the
    bytes, the window layers' rings the same (their size is the window's
    and the chunk's, whichever is larger)."""
    short = LLMEngine(build(), **KW)
    long = LLMEngine(build(max_seq_len=192), **{**KW, "max_seq_len": 192})
    assert long.pool.num_blocks - 1 == 2 * (short.pool.num_blocks - 1)
    for name in ("latent", "index_key"):
        assert long.pool.paged[name].shape[1] == long.pool.num_blocks
    assert long.pool.window_bytes_per_lane() \
        == short.pool.window_bytes_per_lane() == 2 * 8 * 128 * 4
    assert short.pool.lane_state["window_latent"].shape == (2, 3, 8, 128)
    wide = LLMEngine(build(), **{**KW, "prefill_chunk": 16})
    assert wide.pool.lane_state["window_latent"].shape == (2, 3, 16, 128)
    assert set(short.pool.paged) == {"latent", "index_key"}
    # a byte budget buys blocks at what a block costs in the kinds that
    # live in blocks (the rings cost a lane, not a block)
    sized = LLMEngine(build(), **KW,
                      kv_hbm_bytes=40 * short.pool.bytes_per_block())
    assert sized.pool.num_blocks == 40
    assert len(short.step_program_spec()["arena_param_indices"]) == 3


def _step_args(served):
    return [ev["args"] for ev in
            served.engine.tracer.chrome_trace()["traceEvents"]
            if ev.get("name", "").startswith("step[")]


def test_step_spans_carry_the_selection_and_routing_counts():
    served = Served(build(experts_held=4), trace=True, **KW)
    _serve(served, _prompts((20, 6)), 4)
    spans = _step_args(served)
    assert spans
    for args in spans:
        assert args["moe_pairs"] == 3 * 4 * args["fed_tokens"]  # 3 MoE layers
        assert 0 <= args["moe_pairs_local"] <= args["moe_pairs"]
        # two full layers, two window layers: every live query token sees
        # its context, reads at most `index_topk` of it / the window
        assert args["attn_keys_visible"] >= 2 * args["fed_tokens"]
        assert args["attn_keys_selected"] <= min(
            args["attn_keys_visible"], 2 * TOPK * args["fed_tokens"])
        assert args["window_keys_read"] <= 2 * SLIDING * args["fed_tokens"]
        # the decode group's part of the step's counts
        for name in ("attn_keys_visible", "attn_keys_selected",
                     "moe_experts_hit"):
            assert 0 <= args[name + "_decode"] <= args[name]
    mixed = [a for a in spans if 0 < a["attn_keys_visible_decode"]
             < a["attn_keys_visible"]]
    assert mixed, "no step held a chunk row beside a decode row"
    last = spans[-1]                       # a decode step at contexts > 8
    assert last["attn_keys_selected"] == 2 * TOPK * last["fed_tokens"]
    assert last["attn_keys_visible"] > last["attn_keys_selected"]
    assert last["window_keys_read"] == 2 * SLIDING * last["fed_tokens"]
    for name in ("attn_keys_visible", "attn_keys_selected",
                 "moe_experts_hit"):
        assert last[name + "_decode"] == last[name]


def test_the_counts_are_what_the_program_read_not_what_the_traffic_allows(
        monkeypatch):
    """The selected keys are counted from the mask attention is handed: a
    program whose selection keeps every causal key (the planted fault of
    tests/test_dots3_model.py) reports every visible key as read, where
    the sound program reports `index_topk` a query."""
    from paddle_tpu.models import dots3

    def see_everything(scores, qpos, topk):
        chosen = dots3.jnp.broadcast_to(
            dots3.jnp.arange(scores.shape[-1]), scores.shape)
        ok = chosen <= qpos[:, None]
        return chosen, ok, ok

    monkeypatch.setattr(dots3, "select_keys", see_everything)
    served = Served(build(experts_held=4), trace=True, **KW)
    _serve(served, _prompts((20, 6)), 4)
    spans = _step_args(served)
    assert spans[-1]["attn_keys_visible"] > 2 * TOPK * spans[-1]["fed_tokens"]
    for args in spans:
        assert args["attn_keys_selected"] == args["attn_keys_visible"]
        assert args["attn_keys_selected_decode"] \
            == args["attn_keys_visible_decode"]


@pytest.mark.parametrize("kw,sentence", [
    (dict(prefix_cache=True), "keeps state a lane"),
    (dict(spec_decoding=True), "keeps state a lane"),
    (dict(host_kv_blocks=8), "keeps state a lane"),
    (dict(mesh=2), "not available for Dots3"),
    (dict(quantize="int8"), "not available for Dots3"),
    (dict(lora_slots=2), "not available for Dots3"),
    (dict(kv_dtype="int8"), "kv_dtype='int8' is not available for Dots3"),
], ids=lambda x: next(iter(x)) if isinstance(x, dict) else None)
def test_options_this_model_cannot_serve_are_refused_with_a_sentence(
        kw, sentence):
    with pytest.raises(ValueError, match=sentence):
        LLMEngine(build(), **KW, **kw)
    engine = LLMEngine(build(), **KW)
    assert engine.prefix_cache is False


@pytest.mark.parametrize("setup,kw", [
    ("default", {}), ("held", {"experts_held": 4, "experts_from": 4})])
def test_hybrid_step_programs_are_textually_the_parents(setup, kw):
    """PR 40 made the pool own an arena a cache kind and moved the hybrid's
    router and dispatch into `models/experts.py`: a model that declares no
    kinds keeps the pair `k`, `v`, and every lowered hybrid step program has
    the text it had at PR 39 (sha256 of `as_text()`, recorded on the parent
    commit with this jax). GPT's and the looped decoder's are held by
    `tests/test_serving_looped.py` and `tests/test_append_dispatch.py`.
    Every program moved once since, when a step took the previous step's
    result as one more operand (`tests/step_programs_prev_operand.json`).
    The hybrid's chunk-carrying program moved again when its chunk group
    came to read only its rows' lanes of the state leaf (the record before,
    when it sliced the whole layer first:
    `tests/hybrid_step_programs_layer_slice.json`). The decode-only program
    keeps its text here, where the decode group's state update is the XLA
    form (off the chip the kernel `ssm_state_update` is not taken)."""
    parents = json.loads(
        (REPO / "tests/hybrid_step_programs_pr39.json").read_text())[setup]
    before = json.loads((REPO / "tests/hybrid_step_programs_layer_slice.json")
                        .read_text())[setup]
    want = json.loads((REPO / "tests/step_programs_prev_operand.json")
                      .read_text())["hybrid"][setup]
    engine = LLMEngine(hybrid.build(**kw), **KW)
    got = {name: hashlib.sha256(low.as_text().encode()).hexdigest()
           for name, low in engine.lowered_step_programs().items()}
    assert got == want
    assert sorted(parents) == sorted(got) == sorted(before)
    assert all(parents[name] != h for name, h in got.items())
    assert got["w1"] == before["w1"] and got["w8"] != before["w8"]


def test_the_benchmark_has_the_configuration_and_its_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == "dots3-ep8-serve"]
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "n_routed_experts", "vocab_size"]
    cell, = [w for w in bench["workloads"]
             if w["name"] == "dots3-ep8-serve.notes"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("dots3-ep8-serve", "notes", 1)
    assert bench["workloads"][-1] is cell and bench["configs"][-1] is entry
    config = json.loads((REPO / entry["file"]).read_text())
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == [cell["name"]]]
    assert sorted(mine) == sorted([
        "step.latent_mfu", "attn.selected_key_share",
        "pool.window_cache_share", "moe.gated_experts_hit_share",
        "moe.gated_load_max_over_mean",
        # PR 41: the full layers' selection kernel, by its own name
        "kernel.select_time_share"])
    # one chunk row a step, what the sweep on the chip settled (PERF.md
    # section 6): every step of the window then carries a chunk, and the
    # readers of pure decode steps find none and do not list the cell
    assert config["engine"]["token_budget"] \
        == config["engine"]["prefill_chunk"] == 512
    for name in ("step.decode_ms_p50", "step.device_ms_p50"):
        reader, = [m for m in bench["per_layer"] if m["name"] == name]
        assert cell["name"] not in reader["workloads"]
    for named in (entry["file"], config["reference"],
                  "benchmark/traffic/notes.json",
                  "benchmark/reduce/latent.py",
                  "benchmark/init_rules_dots3.py",
                  *(f"benchmark/layer_metrics/{m}.py" for m in mine)):
        assert (REPO / named).is_file(), named
    # every width as published: the catalog row's numbers stand in the file
    # under their keys, but the four that `reduced` lists
    published = {
        "hidden_size": 5120, "intermediate_size": 13824,
        "num_attention_heads": 128, "q_lora_rank": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "index_n_heads": 64, "index_head_dim": 128, "index_topk": 2048,
        "swa_num_attention_heads": 64, "swa_q_lora_rank": 1024,
        "swa_kv_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
        "swa_qk_rope_head_dim": 64, "swa_v_head_dim": 128,
        "sliding_window_size": 513, "moe_intermediate_size": 1536,
        "num_experts_per_tok": 8, "n_shared_experts": 1,
        "routed_scaling_factor": 1, "first_k_dense_replace": 1}
    assert {k: config[k] for k in published} == published
    assert config["published"]["n_routed_experts"] == 256 \
        and config["router_experts"] == 256 \
        and config["n_routed_experts"] == 32
    assert config["layer_types"] == \
        config["published"]["layer_types"][:5] \
        and len(config["layer_types"]) == config["num_hidden_layers"] == 5
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    # the mix's longest request is the engine's longest sequence
    mix = json.loads((REPO / "benchmark/traffic/notes.json").read_text())
    assert mix["prompt"]["max"] + mix["output"]["max"] \
        == config["engine"]["max_seq_len"] == config["n_positions"]
    assert mix["prompt"]["min"] > config["index_topk"]
    assert mix["loop"]["clients"] == config["engine"]["max_batch"] == 32
