"""What the host hands the device for a step (serving/engine.py
`step_input_layout`, `_row_arrays`, `_run_step`, the jitted `step`): ONE
packed int32 buffer, and a sampling key that lives on the device.

- the layout round-trips: what the program unpacks is, bit for bit, what
  the thirteen (fifteen) separate host arrays of PR 34 held for the same
  plan, the float sections included, and an idle lane reads zeros and
  ``top_ps`` 1.0 whatever an earlier step planned there; a row whose token
  the step in flight samples reads no id and names that step's result row
  in ``src`` (-1 elsewhere);
- the key's stream is the one a host-side ``key, sub = split(key)`` a
  step gives: the sampler is fed that chain's ``sub`` and ``engine._key``
  is that chain's key, after every step launched, of every width;
- a step that raises leaves the key defined and the engine able to go on;
- tp=2 on the CPU mesh emits tp=1's tokens from the same replicated
  buffer and key.
"""
import functools

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models import lora as lora_mod
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import EngineSupervisor, LLMEngine, faults
from paddle_tpu.serving import spec as spec_mod
from paddle_tpu.serving.engine import step_input_layout, unpack_step_input
from paddle_tpu.serving.faults import FaultInjected, FaultPlan

CHUNK = 8
SEED = 5


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    return GPT(cfg).eval()


def _engine(model, **kw):
    # a budget of two chunks: two chunk rows a step (the default is one)
    kw = dict(dict(block_size=8, max_batch=3, max_seq_len=64,
                   prefill_chunk=CHUNK, token_budget=2 * CHUNK, seed=SEED),
              **kw)
    return LLMEngine(model, **kw)


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _chain(n, seed=SEED):
    """`[(key, sub)]` of n host-side splits: PR 34's `_run_step`."""
    key, out = jax.random.PRNGKey(seed), []
    for _ in range(n):
        key, sub = jax.random.split(key)
        out.append((key, sub))
    return out


def _same_key(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# -- (a) the layout ----------------------------------------------------------


def _pr34_arrays(engine, placed, B, S):
    """The separate host arrays PR 34's `_row_arrays` made and its
    `_run_rows` filled (written out from that commit) for a rectangle of
    `B` rows of `S` slots, through the engine's own `_fill_row`. `placed`:
    `(row of the rectangle, planned row)` pairs; PR 34's step was the one
    rectangle `[max_batch, S]` with every planned row in its place in the
    plan, and a step is one such rectangle a group now
    (`LLMEngine.step_groups`). A row whose pending token the step in flight
    samples has no id on the host; those arrays had no such row, their step
    being read before the next was planned."""
    a = {
        "ids": np.zeros((B, S), np.int32),
        "qpos": np.zeros((B, S), np.int32),
        "slots": np.zeros((B, S), np.int32),
        "offs": np.zeros((B, S), np.int32),
        "tables": np.zeros((B, engine.max_blocks), np.int32),
        "temps": np.zeros(B, np.float32),
        "top_ks": np.zeros(B, np.int32),
        "top_ps": np.ones(B, np.float32),
        "q_start": np.zeros(B, np.int32),
        "kv_live": np.zeros(B, np.int32),
        "adapter_slots": np.zeros(B, np.int32),
        "last_idx": np.zeros(B, np.int32),
        "spec_lens": np.zeros(B, np.int32),
    }
    if engine.pool.quantized:
        a["touched"] = np.zeros((B, engine._touched_width(S)), np.int32)
        a["touch_idx"] = np.zeros((B, S), np.int32)
    for i, row in placed:
        req, start, count, k = row.req, row.start, row.count, len(row.draft)
        if start == req.num_tokens - 1:
            a["ids"][i, 0] = req.last_token
        elif not req.unread:
            a["ids"][i, :count] = req.all_ids[start:start + count]
        if k:
            a["ids"][i, count:count + k] = row.draft
        a["last_idx"][i] = count - 1
        a["spec_lens"][i] = k
        engine._fill_row(a, i, req, start, count + k, S)
    return a


def _group_arrays(engine, rows, W):
    """`[(PR 34's arrays, rows used), ...]`, a group of the width-`W` step
    each: a planned row that feeds one token (and its drafts) in its place
    in the plan in the decode group, a wider one in the chunk group's next
    row, which also names that place (`lanes`; an idle row names none:
    `max_batch`); the decode group's `src` names, for a row fed a token the
    step in flight samples, that step's result row (-1 elsewhere)."""
    groups = engine.step_groups(W)
    wide = [i for i, r in enumerate(rows)
            if r.count + len(r.draft) > groups[0][1]]
    out = []
    for g, (B, S) in enumerate(groups):
        placed = ([(j, rows[i]) for j, i in enumerate(wide)] if g else
                  [(i, r) for i, r in enumerate(rows) if i not in wide])
        a = _pr34_arrays(engine, placed, B, S)
        if g:
            a["lanes"] = np.full(B, engine.max_batch, np.int32)
            a["lanes"][:len(wide)] = wide
        else:
            a["src"] = np.full(B, -1, np.int32)
            for i, row in placed:
                if row.req.unread:
                    a["src"][i] = engine._in_flight.result_row[row.req]
        out.append((a, {i for i, _ in placed}))
    return out


def _bits(x):
    x = np.asarray(x)
    return x.view(np.int32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("extras", [False, True], ids=["plain", "lora-spec"])
@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["bf16-arena", "int8-arena"])
def test_the_program_unpacks_what_pr34s_separate_arrays_held(
        model, monkeypatch, kv_dtype, extras):
    kw = dict(kv_dtype=kv_dtype)
    if extras:
        kw.update(lora_slots=2, lora_rank=4, spec_decoding=True)
    engine = _engine(model, **kw)
    if extras:
        engine.load_adapter("a", lora_mod.random_adapter(
            model.cfg, 4, lora_mod.LORA_TARGETS, seed=7, scale=0.5),
            alpha=8)
    steps = []          # (W, rows planned, PR 34's arrays, the packed buffer)

    run_rows, run_step = engine._run_rows, engine._run_step

    def spy_rows(rows, W, step_id=0):
        # before the step runs: emitting moves the requests on
        steps.append([W, len(rows), _group_arrays(engine, rows, W)])
        return run_rows(rows, W, step_id)

    def spy_step(fn, packed, **kw):
        steps[-1].append(packed)
        return run_step(fn, packed, **kw)

    monkeypatch.setattr(engine, "_run_rows", spy_rows)
    monkeypatch.setattr(engine, "_run_step", spy_step)
    # three lanes that empty one after another: later steps plan fewer
    # rows than earlier ones; a repeated motif so the drafter proposes
    prompts = _prompts((20, 7), seed=1) + [[5, 6, 7] * 4]
    knobs = [dict(temperature=0.7, top_k=5, top_p=0.9,
                  adapter="a" if extras else None),
             dict(temperature=1.3, top_p=0.35), dict()]
    for prompt, n, kn in zip(prompts, (2, 5, 9), knobs):
        engine.add_request(prompt, max_new_tokens=n, **kn)
    while engine.has_unfinished():
        engine.step()

    widths = {W for W, *_ in steps}
    assert {1, CHUNK} <= widths and (not extras or len(widths) == 3)
    fewer = chunk_beside_decode = False
    for n, (W, n_rows, groups, packed) in enumerate(steps):
        layouts, words = engine._step_layout(W)
        assert packed.dtype == np.int32 and packed.shape == (words,)
        # the chunk's width alone puts a chunk group behind the decode group
        assert len(layouts) == len(groups) == (2 if W == CHUNK else 1)
        for g, (fields, (want, used)) in enumerate(zip(layouts, groups)):
            assert list(fields) == [
                "ids", "tables", "slots", "offs", "qpos", "q_start",
                "kv_live", *(["touched", "touch_idx"] if kv_dtype else []),
                *(["lanes"] if g else ["src"]),
                "adapter_slots", "last_idx", "spec_lens", "temps", "top_ks",
                "top_ps"]
            got = jax.jit(functools.partial(unpack_step_input,
                                            fields=fields))(packed)
            assert sorted(got) == sorted(want)
            for name in want:
                assert got[name].dtype == want[name].dtype, name
                assert got[name].shape == want[name].shape, name
                assert np.array_equal(_bits(got[name]), _bits(want[name])), (
                    n, W, g, name)
            # rows without a planned row: zeros everywhere, top_ps 1.0, no
            # lane named and no result row
            idle = [i for i in range(len(want["last_idx"])) if i not in used]
            for name in want:
                fill = {"top_ps": 1.0, "lanes": engine.max_batch,
                        "src": -1}.get(name, 0)
                assert (np.asarray(got[name])[idle] == fill).all(), name
        chunk_beside_decode |= len(groups) == 2 and all(
            used for _, used in groups)
        fewer |= n > 0 and n_rows < steps[n - 1][1]
    assert chunk_beside_decode
    assert fewer
    # a decode row of a step launched before its predecessor was read names
    # that step's result row; a drafting engine reads every step first
    fed = any((a["src"] >= 0).any() for _, _, groups, _ in steps
              for a, _ in groups[:1])
    assert fed != extras
    # the sampling knobs reached the program as the floats they were
    first = steps[0][2][-1][0]     # the prompts' first chunks
    assert first["temps"][0] == np.float32(0.7)
    assert first["top_ps"][1] == np.float32(0.35)
    if extras:
        assert first["adapter_slots"][0] == 1
        assert any(w["spec_lens"].any() for _, _, groups, _ in steps
                   for w, _ in groups)


def test_the_layout_is_a_function_of_the_programs_shape():
    fields, words = step_input_layout(16, 128, 128)
    assert words * 4 == 41_472          # c1p3b-serve: 40.5 KiB a chunk step
    assert step_input_layout(16, 1, 128)[1] * 4 == 8_960     # 8.75 a decode
    q_fields, q_words = step_input_layout(16, 128, 128, touched_width=9)
    assert q_words - words == 16 * (9 + 128)
    # sections tile the buffer in the stated order, none overlapping
    for table, total in ((fields, words), (q_fields, q_words)):
        end = 0
        for off, shape, dtype in table.values():
            assert off == end and dtype.itemsize == 4
            end = off + int(np.prod(shape))
        assert end == total
    # one table a shape: the program and the fill read the same object
    assert step_input_layout(16, 128, 128) is step_input_layout(16, 128, 128)


# -- (b) the key's stream ----------------------------------------------------


def test_a_seeded_engine_samples_with_the_hosts_chain_of_keys(
        model, monkeypatch):
    """Temperature, top-k and top-p set; chunk-width and width-1 steps.
    The sampler inside the program is fed exactly the `sub` a host-side
    split a step would have handed it (PR 34's `_run_step`), and run
    again outside the program on the same logits with that `sub` it
    gives the tokens the engine emitted."""
    seen = []
    emit = spec_mod.spec_emit_arrays

    def spy(logits, ids, spec_lens, temps, top_ks, top_ps, key):
        jax.debug.callback(
            lambda *a: seen.append([np.asarray(x) for x in a]),
            logits, ids, spec_lens, temps, top_ks, top_ps, key, ordered=True)
        return emit(logits, ids, spec_lens, temps, top_ks, top_ps, key)

    monkeypatch.setattr(spec_mod, "spec_emit_arrays", spy)
    engine = _engine(model)
    rids = [engine.add_request(p, max_new_tokens=n, temperature=0.8,
                               top_k=20, top_p=0.9)
            for p, n in zip(_prompts((13, 5, 9), seed=3), (4, 6, 3))]
    emitted, lanes = [], []           # a step's {request: token}, its plan
    while engine.has_unfinished():
        emitted.append({o.request_id: o.token for o in engine.step()})
        lanes.append(list(engine.last_planned))
        jax.effects_barrier()
        # a call may launch the next step before it reads this one
        assert _same_key(engine._key, _chain(engine.step_count)[-1][0])
    n = len(emitted)
    assert len(seen) == n == engine.step_count \
        == engine.metrics.counters["host_syncs"]
    assert {w for _, w in engine._step_fns} == {1, CHUNK}
    assert any(emitted)
    for (_, sub), args, toks, plan in zip(_chain(n), seen, emitted, lanes):
        assert _same_key(args[-1], sub)
        run, n_acc = emit(*args[:-1], sub)
        assert not np.asarray(n_acc).any()
        # every token the step emitted is its lane's sample under `sub`
        assert toks == {rid: int(run[i, 0]) for i, rid in enumerate(plan)
                        if rid in toks}
    assert sum(len(t) for t in emitted) == 4 + 6 + 3
    assert all(engine.get_request(r).finished for r in rids)
    # and not greedy: the knobs and the key decide something
    greedy = _engine(model)
    want = greedy.generate(_prompts((13, 5, 9), seed=3), max_new_tokens=6)
    got = [engine.get_request(r).output_ids for r in rids]
    assert any(g != w[:len(g)] for g, w in zip(got, want))


def test_two_engines_of_one_seed_sample_the_same_tokens(model):
    def serve(seed):
        engine = _engine(model, seed=seed)
        rids = [engine.add_request(p, max_new_tokens=6, temperature=0.8,
                                   top_k=20, top_p=0.9)
                for p in _prompts((13, 5, 9), seed=3)]
        while engine.has_unfinished():
            engine.step()
        return [engine.get_request(r).output_ids for r in rids]

    assert serve(SEED) == serve(SEED)
    assert serve(SEED) != serve(SEED + 1)


# -- (c) a step that raises --------------------------------------------------


def _serve_supervised(engine, prompts, n=6):
    sup = EngineSupervisor(engine)
    rids = [engine.add_request(p, max_new_tokens=n) for p in prompts]
    failures = []
    for _ in range(200):
        if not engine.has_unfinished():
            break
        failures += sup.step()[1]
    return [engine.get_request(r).output_ids for r in rids], failures


@pytest.mark.parametrize("how", ["step_raise", "program-call"])
def test_a_step_that_raises_leaves_a_defined_key(model, monkeypatch, how):
    prompts = _prompts((13, 5, 9), seed=3)
    want = _engine(model).generate(prompts, max_new_tokens=6)
    engine = _engine(model)
    calls = {"n": 0}
    if how == "step_raise":
        # raised before the launch, as PR 34's was: no split either way
        faults.install(FaultPlan([{"point": "step_raise", "at_step": 3,
                                   "times": 1}]))
    else:
        get = engine._get_step_fn

        def flaky(B, W):
            fn = get(B, W)

            def call(*args):
                calls["n"] += 1
                if calls["n"] == 3:
                    raise RuntimeError("launch failed")
                return fn(*args)
            return call

        monkeypatch.setattr(engine, "_get_step_fn", flaky)
    try:
        got, failures = _serve_supervised(engine, prompts)
    finally:
        faults.clear()
    assert engine.metrics.counters["engine_step_errors"] == 1
    assert not failures and got == want
    # the key advanced once a launch that returned, never otherwise, and
    # was neither donated nor left half-assigned: one planned step was
    # never launched
    assert not engine._key.is_deleted()
    launches = engine.step_count - 1
    assert _same_key(engine._key, _chain(launches)[-1][0])
    syncs = int(engine.metrics.counters["host_syncs"])
    if how == "program-call":
        assert calls["n"] == launches + 1
        # the step in flight when the launch behind it raised: never read
        assert syncs == launches - 1
    else:
        assert syncs == launches


def test_a_raising_step_raises_to_a_bare_caller_and_the_next_one_runs(model):
    engine = _engine(model)
    rid = engine.add_request(_prompts((5,))[0], max_new_tokens=4)
    engine.step()
    key = np.asarray(engine._key)
    faults.install(FaultPlan([{"point": "step_raise", "times": 1}]))
    try:
        with pytest.raises(FaultInjected):
            engine.step()
        assert _same_key(engine._key, key)
        engine.requeue(rid)
        while engine.has_unfinished():
            engine.step()
    finally:
        faults.clear()
    assert len(engine.get_request(rid).output_ids) == 4


# -- (d) the mesh ------------------------------------------------------------


def test_tp2_emits_tp1s_tokens_from_one_replicated_buffer(model):
    prompts = _prompts((20, 7, 11), seed=1)

    def serve(mesh):
        engine = _engine(model, mesh=mesh, trace=1.0)
        out = engine.generate(prompts, max_new_tokens=6)
        return engine, out

    one, want = serve(None)
    two, got = serve(2)
    assert got == want
    n = int(one.metrics.counters["host_syncs"])
    assert n == two.metrics.counters["host_syncs"]
    assert _same_key(one._key, two._key)
    assert _same_key(two._key, _chain(n)[-1][0])
    # replicated from the first step on: one trace a program, no more
    assert two.metrics.counters["jit_traces"] == len(two._step_fns)
    assert len(two._key.sharding.device_set) == 2
    assert two._key.sharding.is_fully_replicated
    spans = [e["args"] for e in two.tracer.chrome_trace()["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("step[")]
    assert len(spans) == n and {a["h2d_arrays"] for a in spans} == {1}
    # the program's argument list is stated once, for the mesh too
    for W in two.width_buckets:
        args = two.step_program_args(W)
        packed, prev, key = args[-3:]
        assert packed.shape == (two._step_layout(W)[1],)
        assert packed.dtype == np.int32 and key.shape == two._key.shape
        assert prev.shape == two._prev.shape == (two.max_batch, 3)
        assert args[2] is two.pool.k and args[3] is two.pool.v
    assert sorted(two.lowered_step_programs()) == sorted(
        two.step_program_shapes())
