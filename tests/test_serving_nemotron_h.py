"""The hybrid block served through `LLMEngine`: prefill in chunks and then
decode, through the paged arena of its attention layer AND the state a lane
its Mamba layers keep in the pool, against the plain reference's full
forward (`benchmark/reference/nemotron_h_block.py`), on logits.

The logits are the step programs' own, kept as `tests/test_serving_looped.py`
keeps them: the model's forward hands every step's logits to the host, and
the planned rows, with where the engine placed them, say which request and
position each belongs to; a row of the decode group is the request's LANE
here.

Tolerance. Float32 weights, arena and state: served logits and the
reference's differ in the order of float32 sums (the chunk form, the paged
walk): 1e-4 on logits whose spread is about 0.6, measured 3e-6.
"""
import json
from pathlib import Path

import numpy as np
import pytest

from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.engine import step_input_layout

from _nemotron_common import (VOCAB, build, load_reference, params_of,
                              published_keys)
from _serving_common import (WAVE, Served, check_wave_plans, prompts_of,
                             rectangle, serve, wave_of)

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
    """Four requests through three lanes: prompts of 19, 5, 11 and 9 tokens
    in chunks of 8 (the state and the window cross chunk edges), rows in
    different phases in one step (a prompt's second chunk beside decode
    rows), and the fourth request in a lane the second left."""
    served = Served(build(experts_held=4, experts_from=4), **KW)
    prompts = _prompts((19, 5, 11, 9))
    rids = _serve(served, prompts, 7)
    for rid, prompt in zip(rids, prompts):
        assert _worst(served, rid, prompt) < TOL
    mixed = [p for p in served.plans
             if any(c > 1 for *_, c in p) and any(c == 1 and s > 0
                                                  for _, _, s, c in p)]
    assert mixed, "no step held a chunk row beside a decode row"
    lanes = {}
    for plan in served.plans:
        for rid, lane, *_ in plan:
            lanes.setdefault(rid, set()).add(lane)
    assert all(len(v) == 1 for v in lanes.values())     # bound to a lane
    assert lanes[rids[3]] <= lanes[rids[0]] | lanes[rids[1]] | lanes[rids[2]]
    m = served.engine.metrics
    assert m.counters["jit_traces"] == 2 and m.gauges["jit_retraces"] == 0
    assert m.gauges["kv_heads"] == 2 and m.gauges["state_layers"] == 2
    assert m.gauges["experts_held"] == 4 and m.gauges["experts_total"] == 16
    cfg = served.model.cfg
    assert m.gauges["state_bytes_per_lane"] == 2 * 4 * (
        cfg.mamba_num_heads * cfg.mamba_head_dim * cfg.ssm_state_size
        + (cfg.conv_kernel - 1) * cfg.conv_channels)


# -- the shape of a step that carries a chunk (PR 37) ------------------------
# as tests/test_serving_chunked.py holds GPT's: the same engine steered back
# to the one rectangle is the reference. Here the chunk group's rows gather
# the state of the lanes they name and scatter it back, while the decode
# group uses the lanes' state in place.

GROUPED = dict(block_size=4, max_batch=4, max_seq_len=96, prefill_chunk=8,
               token_budget=16, seed=11)


@pytest.mark.parametrize("knobs", [
    dict(temperature=0.0), dict(temperature=0.8, top_k=20, top_p=0.95)],
    ids=["greedy", "seeded"])
def test_a_grouped_step_is_the_rectangles_step(knobs):
    """Tokens, greedy and sampled, the arena, AND the state every lane is
    left with (recurrent state and the convolution's window), as the one
    rectangle gave them; every lane's state was written."""
    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=4))
    grouped = LLMEngine(build(experts_held=4, experts_from=4), **GROUPED)
    parent = rectangle(LLMEngine(build(experts_held=4, experts_from=4),
                                 **GROUPED))
    assert grouped.step_groups(8) == ((4, 1), (2, 8))
    assert "lanes" in grouped._step_layout(8)[0][1]
    got, plans = serve(grouped, wave, **knobs)
    want, parents_plans = serve(parent, wave, **knobs)
    check_wave_plans(plans)
    assert plans == parents_plans and got == want
    for name in ("k", "v"):
        x, y = (np.asarray(getattr(e.pool, name))[:, :, 1:]
                for e in (grouped, parent))
        assert np.abs(x - y).max() <= 1e-5, name
    for name, state in grouped.pool.lane_state.items():
        x, y = np.asarray(state), np.asarray(parent.pool.lane_state[name])
        assert np.abs(x - y).max() <= 1e-5, name
        assert all(np.abs(x[:, lane]).max() > 0 for lane in range(4)), name
    assert grouped.metrics.counters["jit_traces"] == \
        grouped.expected_program_count() == 2


def test_a_grouped_steps_logits_are_the_references():
    """The wave's five kinds of step against the reference's full forward:
    a chunk row's state crosses from the chunk group (its first chunks) to
    the decode group (a last chunk of one token, then decoding)."""
    served = Served(build(experts_held=4, experts_from=4), **GROUPED)
    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=5))
    engine = served.engine
    rids = [engine.add_request(p, max_new_tokens=n) for _, p, n in wave[:2]]
    while engine.step_count < 2:            # steps 0 and 1 launched
        served.step()
    rids += [engine.add_request(p, max_new_tokens=n) for _, p, n in wave[2:]]
    reqs = [engine.get_request(r) for r in rids]
    served.run()
    assert [[c for *_, c in plan] for plan in served.plans[:5]] == [
        [8, 5], [8, 1], [3, 1, 8], [1, 1, 8, 8], [1, 1, 1, 1]]
    served.outputs = {r: list(q.output_ids) for r, q in zip(rids, reqs)}
    for rid, (_, prompt, _) in zip(rids, wave):
        assert _worst(served, rid, prompt) < TOL


def test_the_grouped_matmul_kernel_inside_the_step_serves_the_xla_paths_tokens(
        monkeypatch):
    """Expert widths the kernel takes (latent 128, expert 256) and the
    interpreter forced: both step programs hold the routed experts' two
    products as `grouped_matmul` kernels (`ops/pallas/grouped_matmul.py`),
    which zero the rows behind the groups where `jax.lax.ragged_dot` leaves
    what it leaves. The wave's tokens, greedy and sampled, are those of the
    same engine on the XLA path."""
    from paddle_tpu.ops.pallas import grouped_matmul as gm

    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=7))
    sizes = dict(experts_held=4, experts_from=4, moe_latent_size=128,
                 moe_intermediate_size=256)
    calls, kernel = [], gm.grouped_matmul_kernel
    monkeypatch.setattr(
        gm, "grouped_matmul_kernel",
        lambda x, w, s, **kw: calls.append(w.shape) or kernel(x, w, s, **kw))
    want = [serve(LLMEngine(build(**sizes), **GROUPED), wave, **knobs)[0]
            for knobs in (dict(temperature=0.0),
                          dict(temperature=0.8, top_k=20, top_p=0.95))]
    assert not calls                      # off the chip: the XLA path
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    got = [serve(LLMEngine(build(**sizes), **GROUPED), wave, **knobs)[0]
           for knobs in (dict(temperature=0.0),
                         dict(temperature=0.8, top_k=20, top_p=0.95))]
    assert got == want
    # two expert layers, two products each, traced once a program (widths 1
    # and 8), for each of the two engines
    assert calls == [(4, 128, 256), (4, 256, 128)] * 8


def test_a_third_chunk_row_waits_and_a_wider_budget_has_a_row_a_lane():
    prompts = _prompts((12, 11, 10, 9), seed=6)
    burst = [(0, p, 3) for p in prompts]
    narrow = LLMEngine(build(), **GROUPED)
    assert narrow.scheduler.chunk_rows == 2
    got, plans = serve(narrow, burst)
    assert [[(r, c) for r, _, c, _ in rows] for _, rows in plans[:4]] == [
        [(0, 8), (1, 8)], [(0, 4), (1, 3)],
        [(0, 1), (1, 1), (2, 8), (3, 8)], [(0, 1), (1, 1), (2, 2), (3, 1)]]
    wide = LLMEngine(build(), **{**GROUPED, "token_budget": 4 * 8})
    assert wide.step_groups(8) == ((4, 1), (4, 8))
    wants, plans = serve(wide, burst)
    assert [[c for *_, c, _ in rows] for _, rows in plans[:2]] == [
        [8, 8, 8, 8], [4, 3, 2, 1]]
    assert got == wants


def test_preemption_by_recompute_starts_the_state_from_zeros():
    """An arena too small for three requests at once: the youngest is
    preempted, re-admitted (into whatever lane is free, over whatever state
    that lane's last tenant left) and replayed from position 0; its logits
    are the reference's all the same."""
    served = Served(build(), num_blocks=14, **KW)
    prompts = _prompts((17, 14, 12), seed=3)
    rids = _serve(served, prompts, 12)
    assert served.engine.metrics.counters["preemptions"] >= 1
    for rid, prompt in zip(rids, prompts):
        assert _worst(served, rid, prompt) < TOL
    assert served.engine.pool.num_free == served.engine.pool.num_blocks - 1


def test_a_held_still_lane_keeps_its_state():
    """`step(only=...)`, the supervisor's probe: a request that is not in the
    probe keeps its lane and gets no row for a step, and goes on from the
    state it had (an idle lane moves nothing)."""
    served = Served(build(), **KW)
    prompts = _prompts((9, 10), seed=5)
    a, b = (served.engine.add_request(p, max_new_tokens=6) for p in prompts)
    reqs = {r: served.engine.get_request(r) for r in (a, b)}
    for _ in range(3):
        served.step()
    for _ in range(2):
        served.step(only={a})              # b holds still
        assert [rid for rid, *_ in served.plans[-1]] == [a]
    served.run()
    served.outputs = {r: list(q.output_ids) for r, q in reqs.items()}
    ids = (prompts[1] + served.outputs[b])[:-1]
    want = np.asarray(ref.sequence_logits(
        params_of(served.model), ids, published_keys(served.model)))
    got = served.logits[b]
    assert max(np.abs(got[p] - want[p]).max() for p in got) < TOL


@pytest.mark.parametrize("pattern,lane_state", [
    ("MEM*E", True),
    # a stack with expert layers and no state-space layer keeps no state a
    # lane; its counts still ride the packed result to the spans
    ("E*E", False)])
def test_step_spans_carry_the_routing_and_state_counts(pattern, lane_state):
    served = Served(build(pattern=pattern, experts_held=4), trace=True, **KW)
    assert bool(served.engine.pool.lane_state) == lane_state
    _serve(served, _prompts((12, 6)), 4)
    spans = [ev["args"] for ev in
             served.engine.tracer.chrome_trace()["traceEvents"]
             if ev.get("name", "").startswith("step[")]
    assert spans
    for args in spans:
        assert args["h2d_arrays"] == 1
        assert args["moe_pairs"] == 2 * 4 * args["fed_tokens"]  # 2 E layers
        assert 0 <= args["moe_pairs_local"] <= args["moe_pairs"]
        assert args["moe_experts_hit"] <= 2 * 4
        assert args["moe_max_load"] <= args["fed_tokens"]
        assert args["state_rows"] == args["rows"]
    assert sum(a["moe_pairs_local"] for a in spans) > 0


def test_the_step_input_gains_one_section_and_only_for_lane_state():
    plain, n = step_input_layout(3, 8, 24)
    lane, m = step_input_layout(3, 8, 24, None, True)
    assert set(lane) - set(plain) == {"fresh"} and m == n + 3
    engine = LLMEngine(build(), **KW)
    (decode,), _ = engine._step_layout(1)
    assert "fresh" in decode and "lanes" not in decode
    # behind the decode group, whose row i is lane i, the chunk group's
    # rows say whose state they carry
    (decode, chunk), _ = engine._step_layout(8)
    assert "fresh" in decode and "lanes" not in decode
    assert "fresh" in chunk and chunk["lanes"][1] == (1,)   # one chunk row
    assert [a.shape for a in engine.pool.lane_state.values()] == [
        (2, 3, 8, 16, 16), (2, 3, 3, 192)]
    assert len(engine.step_program_spec()["arena_param_indices"]) == 4


@pytest.mark.parametrize("option,kw", [
    ("prefix_cache=True", dict(prefix_cache=True)),
    ("spec_decoding=True", dict(spec_decoding=True)),
    ("host_kv_blocks", dict(host_kv_blocks=8)),
])
def test_options_that_carry_no_state_are_refused_with_a_sentence(option, kw):
    with pytest.raises(ValueError, match="keeps state a lane"):
        LLMEngine(build(), **KW, **kw)


@pytest.mark.parametrize("option,kw", [
    ("mesh=", dict(mesh=2)),
    ("quantize=", dict(quantize="int8")),
    ("lora_slots", dict(lora_slots=2)),
])
def test_options_written_for_gpt_are_refused_with_a_sentence(option, kw):
    with pytest.raises(ValueError, match="not available for NemotronH"):
        LLMEngine(build(), **KW, **kw)


def test_prefix_cache_is_off_by_default_for_this_model():
    engine = LLMEngine(build(), **KW)
    assert engine.prefix_cache is False
    assert engine.scheduler.prefix_cache is False


def test_tp_above_the_kv_heads_is_refused_with_a_sentence():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.sharded import build_serving_mesh

    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=8, max_seq_len=32)
    smesh = build_serving_mesh(4)
    smesh.validate_model(cfg)                 # 8 heads of cache: fine
    with pytest.raises(ValueError, match="2 key/value heads"):
        smesh.validate_model(cfg, kv_heads=2)


def test_the_benchmark_has_the_configuration_and_its_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"]
              if c["name"] == "nemotron3s-ep4-serve"]
    assert entry["reduced"] == ["num_hidden_layers",
                                "hybrid_override_pattern",
                                "n_routed_experts", "vocab_size"]
    cell, = [w for w in bench["workloads"]
             if w["name"] == "nemotron3s-ep4-serve.turns"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("nemotron3s-ep4-serve", "turns", 1)
    config = json.loads((REPO / entry["file"]).read_text())
    # the metrics this cell brought: it is the first they list (a later
    # cell of another configuration may read two of them too)
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads", [None])[0] == cell["name"]]
    assert sorted(mine) == sorted([
        "step.hybrid_mfu", "hybrid.decode_stream_share",
        "moe.local_pair_share", "moe.experts_hit_share",
        "moe.load_max_over_mean", "pool.state_hbm_share",
        # PR 39: the routed experts' grouped matmul, by its own name
        "kernel.grouped_time_share", "kernel.grouped_decode_roofline",
        # the decode group's Mamba state update, by its own name
        "kernel.state_update_time_share",
        "kernel.state_update_decode_roofline"])
    for named in (entry["file"], config["reference"],
                  "benchmark/traffic/turns.json",
                  "benchmark/reduce/hybrid.py",
                  "benchmark/init_rules_hybrid.py",
                  *(f"benchmark/layer_metrics/{m}.py" for m in mine)):
        assert (REPO / named).is_file(), named
    # every width as published: the catalog row's numbers stand in the file
    # under their keys, but the four that `reduced` lists
    published = {"hidden_size": 4096, "mamba_num_heads": 128,
                 "mamba_head_dim": 64, "ssm_state_size": 128, "n_groups": 8,
                 "conv_kernel": 4, "chunk_size": 128,
                 "num_attention_heads": 32, "num_key_value_heads": 2,
                 "head_dim": 128, "moe_latent_size": 1024,
                 "moe_intermediate_size": 2688,
                 "moe_shared_expert_intermediate_size": 5376,
                 "num_experts_per_tok": 22, "routed_scaling_factor": 5}
    assert {k: config[k] for k in published} == published
    assert config["published"]["n_routed_experts"] == 512 \
        and config["router_experts"] == 512 \
        and config["n_routed_experts"] == 128
    assert len(config["hybrid_override_pattern"]) == \
        config["num_hidden_layers"] == 11
    assert config["hybrid_override_pattern"] == \
        config["published"]["hybrid_override_pattern"][27:38]
