"""The looped decoder served through `LLMEngine`: prefill in chunks and then
decode through the paged arena, against the plain reference's full forward
(`benchmark/reference/ouro_loop.py`), on logits and not on tokens.

The logits are the step programs' own: the model's forward is wrapped so
that every step hands its logits (and the exit gate's probabilities) to the
host in the step's layout, and the rows the scheduler planned, with where
the engine placed them (`_serving_common.row_origins`), say which request and
position each belongs to.

Tolerance. Float32 weights and a float32 arena, so the served logits and
the reference's differ only in the order of float32 sums (the paged path
attends block by block): 1e-4 on logits whose spread is about 0.5, measured
1e-5. `tests/test_looped_model.py` shows that a dropped pass and a bf16
residual stream both fail a tolerance of that size.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.serving import LLMEngine

from _looped_common import (VOCAB, build, load_reference, params_of,
                            published_keys)
from _serving_common import (WAVE, check_wave_plans, rectangle, row_origins,
                             serve, spy_plans, wave_of)

REPO = Path(__file__).resolve().parents[1]
TOL = 1e-4
ref = load_reference()


class Served:
    """An engine over `model` whose every step's logits are kept:
    `logits[request][position]` is the `[vocab]` row that chose the token at
    `position + 1`, `gates[request][position]` the exit gate's probability
    after every pass."""

    def __init__(self, model, **engine_kw):
        self.model = model
        self.logits, self.gates, self._plans, self._seen = {}, {}, [], []
        plain = model.forward

        def forward(ids, caches=None):
            logits, caches, gates = plain(ids, caches=caches,
                                          return_gates=True)
            jax.debug.callback(
                lambda lg, g: self._seen.append((np.asarray(lg),
                                                 np.asarray(g))),
                logits._array, gates._array)
            return logits, caches

        model.forward = forward
        self.engine = LLMEngine(model, **engine_kw)
        run_rows = self.engine._run_rows

        def planned(rows, W, step_id=0):
            self._plans.append([
                (r.req.request_id, r.start, r.count, at) for r, at in
                zip(rows, row_origins(self.engine, rows, W))])
            return run_rows(rows, W, step_id)

        self.engine._run_rows = planned

    def step(self, only=None):
        outs = self.engine.step(only=only)
        jax.effects_barrier()
        for plan, (lg, gates) in zip(self._plans, self._seen):
            for rid, start, count, (b, s) in plan:
                for j in range(count):
                    self.logits.setdefault(rid, {})[start + j] = lg[b, s + j]
                    self.gates.setdefault(rid, {})[start + j] = \
                        gates[:, b, s + j]
        assert len(self._plans) == len(self._seen)
        self._plans, self._seen = [], []
        return outs

    def run(self):
        while self.engine.has_unfinished():
            self.step()


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, n).tolist() for n in lengths]


def _held_to_the_reference(served, rid, prompt):
    """Largest gap between the served logits of one request and the
    reference's full forward over its prompt and answer."""
    model = served.model
    ids = prompt + list(served.engine.get_request(rid).output_ids)
    want = np.asarray(ref.sequence_logits(params_of(model), ids[:-1],
                                          published_keys(model)))
    got = served.logits[rid]
    # every position fed (the last sampled token is never fed)
    assert sorted(got) == list(range(len(ids) - 1))
    return max(np.abs(got[p] - want[p]).max() for p in got)


@pytest.mark.parametrize("path,loop_steps", [
    ("xla", 2), ("xla", 4), ("interpret", 2)])
def test_chunked_prefill_then_decode_is_the_references_forward(
        monkeypatch, path, loop_steps):
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    if path == "interpret":
        monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    else:
        monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET",
                           raising=False)
    served = Served(build(loop_steps), block_size=8, max_batch=3,
                    max_seq_len=96, prefill_chunk=16, prefix_cache=False)
    engine = served.engine
    assert engine.pool.k.shape[0] == loop_steps * 2 == engine.kv_slabs
    # 37 tokens: three chunks; 5: one; a third joins the decoding two
    prompts = _prompts((37, 5, 20))
    rids = [engine.add_request(p, max_new_tokens=6) for p in prompts[:2]]
    for _ in range(4):
        served.step()
    rids.append(engine.add_request(prompts[2], max_new_tokens=6))
    served.run()
    for rid, prompt in zip(rids, prompts):
        assert _held_to_the_reference(served, rid, prompt) < TOL
    # one program a width, each traced once, whatever the passes
    assert engine.metrics.counters["jit_traces"] == 2
    assert engine.metrics.gauges["kv_slabs"] == loop_steps * 2
    assert engine.metrics.gauges["kv_bytes_per_token"] == \
        2 * loop_steps * 2 * 2 * 64 * 4


@pytest.mark.parametrize("corrupt_pass", [0, 1, 2])
def test_pass_t_reads_its_own_slabs_and_no_other(corrupt_pass):
    """Three passes over two layers: six slabs. With the keys of slab
    `(corrupt_pass, layer 1)` spoiled after the prefill, the next decode
    step's exit gate is bit for bit the clean run's after every pass before
    `corrupt_pass` and differs after that pass and every later one (a pass
    feeds the next): pass t of layer l reads slab t * L + l, no pass reads
    another's."""
    layers, prompt = 2, _prompts((19,))[0]

    def decode_gates(spoil):
        served = Served(build(3, num_layers=layers), block_size=8,
                        max_batch=2, max_seq_len=64, prefill_chunk=32,
                        prefix_cache=False)
        rid = served.engine.add_request(prompt, max_new_tokens=4)
        # a probe (`only`) is read before the next step is planned
        served.step(only={rid})             # the prompt, one chunk
        if spoil is not None:
            pool = served.engine.pool
            pool.k = pool.k.at[spoil].multiply(-3.0)
        served.step(only={rid})             # one decode step
        return served.gates[rid][len(prompt)]

    clean = decode_gates(None)
    spoiled = decode_gates(corrupt_pass * layers + 1)
    for t in range(3):
        if t < corrupt_pass:
            assert spoiled[t] == clean[t], t
        else:
            assert abs(spoiled[t] - clean[t]) > 1e-6, t


def test_a_preempted_request_resumes_to_the_same_logits():
    prompt = _prompts((21,))[0]
    straight = Served(build(2), block_size=8, max_batch=2, max_seq_len=64,
                      prefill_chunk=16)
    rid = straight.engine.add_request(prompt, max_new_tokens=8)
    straight.run()
    resumed = Served(build(2), block_size=8, max_batch=2, max_seq_len=64,
                     prefill_chunk=16)
    rid2 = resumed.engine.add_request(prompt, max_new_tokens=8)
    for _ in range(5):
        resumed.step()
    assert resumed.engine.requeue(rid2)     # its blocks go back to the pool
    assert resumed.engine.get_request(rid2).preemptions == 1
    resumed.run()
    assert resumed.engine.get_request(rid2).output_ids == \
        straight.engine.get_request(rid).output_ids
    # the replay recomputes prompt and answer in chunks: float32 sums in
    # another order, nothing more
    assert _held_to_the_reference(resumed, rid2, prompt) < TOL
    for pos, row in straight.logits[rid].items():
        assert np.abs(resumed.logits[rid2][pos] - row).max() < TOL
    pool = resumed.engine.pool
    assert pool.num_free == pool.num_blocks - 1


def test_an_int8_arena_carries_its_scales_through_the_passes():
    """The loop carries whatever arrays the paged state has: with an int8
    arena the scale sidecars too. Held to the reference at int8's own
    error: a cached value keeps 7 bits, which moved a logit by 0.05 here;
    the bound is 0.1, a fifth of the logits' spread, and scales lost
    between passes or slabs would read several times that."""
    served = Served(build(2), block_size=8, max_batch=2, max_seq_len=64,
                    prefill_chunk=16, kv_dtype="int8", prefix_cache=False)
    prompt = _prompts((21,))[0]
    rid = served.engine.add_request(prompt, max_new_tokens=6)
    served.run()
    assert served.engine.pool.k_scale.shape[0] == 4
    assert _held_to_the_reference(served, rid, prompt) < 0.1


def test_speculation_gives_the_plain_engines_tokens():
    """Drafted tokens are only more fed positions of a row: the looped
    block sees nothing of them, and greedy output stays token for token."""
    prompts = [[1, 2, 3] * 7, list(range(5, 25))]
    kw = dict(block_size=8, max_batch=2, max_seq_len=96, prefill_chunk=16)
    plain = LLMEngine(build(2), **kw).generate(prompts, max_new_tokens=12)
    drafting = LLMEngine(build(2), spec_decoding=True, **kw)
    assert drafting.generate(prompts, max_new_tokens=12) == plain
    assert drafting.metrics.counters["spec_proposed_tokens"] > 0


@pytest.mark.parametrize("option,kw", [
    ("mesh=", dict(mesh=2)),
    ("quantize=", dict(quantize="int8")),
    ("lora_slots", dict(lora_slots=2)),
])
def test_options_written_for_gpt_are_refused_with_a_sentence(option, kw):
    with pytest.raises(ValueError, match="not available for LoopedLM"):
        LLMEngine(build(2), block_size=8, max_batch=2, max_seq_len=64, **kw)


# -- the shape of a step that carries a chunk (PR 37) ------------------------
# as tests/test_serving_chunked.py holds GPT's: the same engine steered back
# to the one rectangle is the reference, over the wave that puts every kind
# of row beside every other

GROUPED = dict(block_size=8, max_batch=4, max_seq_len=96, prefill_chunk=8,
               token_budget=16, prefix_cache=False, seed=11)


@pytest.mark.parametrize("knobs", [
    dict(temperature=0.0), dict(temperature=0.8, top_k=20, top_p=0.95)],
    ids=["greedy", "seeded"])
def test_a_grouped_step_is_the_rectangles_step(knobs):
    """Four passes over 8 slabs of arena, the loop carrying the arenas
    through both groups' appends: tokens, greedy and sampled, and every
    slab of the arena as the one rectangle left them (the logits against
    the reference: the next test)."""
    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=4))
    grouped = LLMEngine(build(4), **GROUPED)
    parent = rectangle(LLMEngine(build(4), **GROUPED))
    assert grouped.step_groups(8) == ((4, 1), (2, 8))
    got, plans = serve(grouped, wave, **knobs)
    want, parents_plans = serve(parent, wave, **knobs)
    check_wave_plans(plans)
    assert plans == parents_plans and got == want
    for name in ("k", "v"):
        x, y = (np.asarray(getattr(e.pool, name))[:, :, 1:]
                for e in (grouped, parent))
        assert x.shape[0] == 8 and np.abs(x - y).max() <= 1e-5, name
    assert grouped.metrics.counters["jit_traces"] == \
        grouped.expected_program_count() == 2


def test_a_grouped_steps_logits_are_the_references():
    served = Served(build(2), **GROUPED)
    wave = wave_of(_prompts([n for _, n, _ in WAVE], seed=5))
    plans = spy_plans(served.engine)
    rids = [served.engine.add_request(p, max_new_tokens=n)
            for _, p, n in wave[:2]]
    while served.engine.step_count < 2:     # steps 0 and 1 launched
        served.step()
    rids += [served.engine.add_request(p, max_new_tokens=n)
             for _, p, n in wave[2:]]
    served.run()
    assert [[c for *_, c, _ in rows] for _, rows in plans[:5]] == [
        [8, 5], [8, 1], [3, 1, 8], [1, 1, 8, 8], [1, 1, 1, 1]]
    for rid, (_, prompt, _) in zip(rids, wave):
        assert _held_to_the_reference(served, rid, prompt) < TOL


def test_a_third_chunk_row_waits_and_a_wider_budget_has_a_row_a_lane():
    prompts = _prompts((12, 11, 10, 9), seed=6)
    burst = [(0, p, 3) for p in prompts]
    narrow = LLMEngine(build(2), **GROUPED)
    assert narrow.scheduler.chunk_rows == 2
    got, plans = serve(narrow, burst)
    assert [[(r, c) for r, _, c, _ in rows] for _, rows in plans[:4]] == [
        [(0, 8), (1, 8)], [(0, 4), (1, 3)],
        [(0, 1), (1, 1), (2, 8), (3, 8)], [(0, 1), (1, 1), (2, 2), (3, 1)]]
    wide = LLMEngine(build(2), **{**GROUPED, "token_budget": 4 * 8})
    assert wide.step_groups(8) == ((4, 1), (4, 8))
    wants, plans = serve(wide, burst)
    assert [[c for *_, c, _ in rows] for _, rows in plans[:2]] == [
        [8, 8, 8, 8], [4, 3, 2, 1]]
    assert got == wants


def test_gpt_step_programs_are_textually_the_parents():
    """PR 32's six set-ups (`tests/test_append_dispatch.py`): every lowered
    GPT step program has the text written down for it (sha256 of
    `as_text()`, with this jax). PR 34 kept PR 33's thirteen hashes: the
    arena's leading axis became the model's to state and no program moved.
    **PR 35 wrote all thirteen down anew**, because the signature changed
    (one packed host input and the key in place of 14 or 16 arguments, the
    new key a last output), so every hash moved. Below the unpacking the
    text is PR 34's: with value numbers and the helper functions' numeric
    suffixes folded, every line of each parent program stands in its
    successor but `main`'s signature and its `return`, and what the
    successor adds is 127-132 lines: the slices, reshapes and two
    `bitcast_convert`s of `unpack_step_input` and the key's split, which
    PR 34 ran as a program of its own (compared on all thirteen, PR 35).
    **PR 37: a program that holds one group of rows is the parent's, and
    keeps its hash** (`w1` everywhere, `w5` of the drafting engine: the
    rectangle is the one-group case of the same code and emits no new
    operation); **a program that holds a chunk group behind the decode
    group has a text of its own** (`tests/gpt_step_programs_pr37.json`, the
    six `w8`): its tokens lie on one flat axis, and the append and the
    attention run once a group. **Every program has moved since, and is
    recorded in `tests/step_programs_prev_operand.json`**: a step takes the
    previous step's result as one more operand and gathers from it the
    decode rows' tokens the host has not read (section `src`)."""
    from test_append_dispatch import SETUPS, _engine

    assert sorted(_wanted("gpt")) == sorted(SETUPS)
    for setup in sorted(SETUPS):
        _hashes_are_the_recorded(_engine(setup), "gpt", setup)


def _recorded(kind):
    """`{setup: {program: sha256}}` of the programs as they are: each takes
    the previous step's result (`tests/step_programs_prev_operand.json`)."""
    return json.loads((REPO / "tests/step_programs_prev_operand.json")
                      .read_text())[kind]


def _wanted(kind):
    """`{setup: {program: sha256}}`: PR 35's record with the programs PR 37
    gave a text of their own laid over it."""
    want = json.loads((REPO / f"tests/{kind}_step_programs_pr35.json")
                      .read_text())
    for setup, wide in json.loads(
            (REPO / f"tests/{kind}_step_programs_pr37.json")
            .read_text()).items():
        assert wide.keys() <= want[setup].keys()
        assert all(want[setup][name] != h for name, h in wide.items())
        want[setup].update(wide)
    return want


def _hashes_are_the_recorded(engine, kind, setup):
    got = {name: hashlib.sha256(low.as_text().encode()).hexdigest()
           for name, low in engine.lowered_step_programs().items()}
    assert got == _recorded(kind)[setup], setup
    # every program took one more operand, so every text moved
    parents = _wanted(kind)[setup]
    assert sorted(parents) == sorted(got)
    assert all(parents[name] != h for name, h in got.items())
    # what PR 37 recorded anew is the programs of more than one group
    new = json.loads((REPO / f"tests/{kind}_step_programs_pr37.json")
                     .read_text())[setup]
    assert sorted(new) == sorted(
        name for name, (_, W) in engine.step_program_shapes().items()
        if len(engine.step_groups(W)) > 1)


LOOPED_SETUPS = {"default": {}, "int8": {"kv_dtype": "int8"},
                 "spec": {"spec_decoding": True}}


def test_looped_step_programs_are_textually_the_parents():
    """The same witness for the looped decoder (PR 36, which gave the pool
    state a lane, the arena the model's key/value heads and the step input
    a section that a model with such state adds): the seven lowered step
    programs of three engines over `build(2)` have the text they had at PR
    35 (`tests/looped_step_programs_pr35.json`, hashed there from a copy of
    that commit with this jax); since PR 37 the four of one group of rows
    have, and the three `w16` the text of
    `tests/looped_step_programs_pr37.json`."""
    # all seven moved since, when a step took the previous step's result
    # (`_recorded`)
    assert sorted(_wanted("looped")) == sorted(LOOPED_SETUPS)
    for setup, kw in sorted(LOOPED_SETUPS.items()):
        engine = LLMEngine(build(2), block_size=8, max_batch=2,
                           max_seq_len=64, prefill_chunk=16, **kw)
        _hashes_are_the_recorded(engine, "looped", setup)


def test_the_benchmark_has_the_configuration_and_its_cell():
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry, = [c for c in bench["configs"] if c["name"] == "ouro-2p6b-serve"]
    assert entry["reduced"] == []
    assert entry["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                               "blob/main/config.json")
    cell, = [w for w in bench["workloads"]
             if w["name"] == "ouro-2p6b-serve.think"]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("ouro-2p6b-serve", "think", 1)
    config = json.loads((REPO / entry["file"]).read_text())
    for named in (entry["file"], config["reference"],
                  "benchmark/traffic/think.json",
                  "benchmark/reduce/looped.py",
                  *(f"benchmark/layer_metrics/{m['name']}.py"
                    for m in bench["per_layer"]
                    if "ouro-2p6b-serve.think" in m.get("workloads", ()))):
        assert (REPO / named).is_file(), named
    # published widths and depth, nothing cut
    assert [config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "head_dim", "intermediate_size", "total_ut_steps", "vocab_size")] \
        == [2048, 48, 16, 128, 5632, 4, 49152]
    out_tok_s, = [m for m in bench["end_to_end"] if m["name"] == "out_tok_s"]
    assert "ouro-2p6b-serve.think" in out_tok_s["workloads"]
    new = {m["name"] for m in bench["per_layer"]
           if m.get("workloads") == ["ouro-2p6b-serve.think"]}
    assert new == {"step.loop_mfu", "loop.decode_stream_share",
                   "kernel.loop_ragged_decode_roofline"}


def test_the_cells_rehearsal_ends_correct():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(REPO / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "ouro-2p6b-serve.think", "--seed", "2147483659", "--seconds", "30",
         "--trace", "0", "--platform", "cpu"], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    assert proc.stderr.strip().splitlines()[-1] == "correct: True"
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["compared"]["logit_deficit"]["value"] <= 0.06
