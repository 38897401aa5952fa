"""Tier-1 CI gate: the hlolint IR contracts hold on the compiled programs.

Lowers the serving engine's unified ragged step program at every width
bucket (w1/w4/w8 on the harness config) at tp=1 and tp=2 on the
8-fake-device host mesh plus the spmd train step — all on the smallest
GPT that still exercises tp sharding — and checks:

- zero contract violations on main (collective budget, donation
  aliasing, host-sync hygiene, program-shape baseline);
- the SEEDED regressions trip: a deliberately qkv-major (pre-PR-10)
  fused-QKV layout blows the tp=2 all-gather budget, and ungated
  ``donate_argnums`` on the cpu host-platform mesh blows the donation
  contract — both with messages naming the contract and the offending
  HLO facts;
- the HLO-text parsing schema canary: a trivial jitted psum on the fake
  mesh must parse to the expected op names, so a jax lowering-format
  drift fails HERE with a pointed message instead of letting every
  contract pass vacuously;
- the CLI: --ir without jax exits 2, --select/--ignore span both layers.
"""
import dataclasses
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.analysis import contracts, ir
from paddle_tpu.serving.sharded import serving_collective_budget

_build_s = []


@pytest.fixture(scope="module")
def artifacts():
    paddle.seed(0)
    t0 = time.perf_counter()
    arts = ir.default_artifacts()
    _build_s.append(time.perf_counter() - t0)
    return arts


# ---------------------------------------------------------------------------
# main is clean


def test_main_is_contract_clean(artifacts):
    violations = contracts.evaluate(artifacts)
    assert violations == [], (
        "IR contract violations (if a budget moved legitimately, rerun "
        "`python -m paddle_tpu.analysis --ir --update-baseline` and "
        "commit ir_baseline.json with the change that moved it):\n"
        + "\n".join(v.format() for v in violations))


def test_program_set_covers_the_registry(artifacts):
    from paddle_tpu.analysis.ir import build_serving_engine, tiny_gpt_config
    from paddle_tpu.models.gpt import GPT

    eng = build_serving_engine(GPT(tiny_gpt_config()), 1)
    names = {a.name for a in artifacts}
    want = {f"serve/tp{tp}/{name}"
            for tp in (1, 2) for name in eng.step_program_shapes()}
    want |= {f"serve/tp{tp}/{name}"
             for tp in (1, 2) for name in eng.swap_program_shapes()}
    # the int8 end-to-end family: w1 decode + the 4-array swap pair
    want |= {f"serve_int8/tp{tp}/w1" for tp in (1, 2)}
    want |= {f"serve_int8/tp{tp}/{name}"
             for tp in (1, 2) for name in eng.swap_program_shapes()}
    # the LoRA family: w1 decode with 2 adapter slots gathered in-step
    want |= {f"serve_lora/tp{tp}/w1" for tp in (1, 2)}
    # the train/* family: legacy dp2 x mp2, the locked zs2-legacy
    # 'before', and the explicit weight-update matrix on dp4
    train_names = {"train/dp2_mp2", "train/dp2_mp2/zs2-legacy",
                   "train/dp4/zs0", "train/dp4/zs2", "train/dp4/zs3",
                   "train/dp4/zs2_gm2", "train/dp4/zs2_q8"}
    want |= train_names
    # one artifact per ragged width bucket plus the host-tier swap pair
    # (x2 for the int8 family's w1 + swaps, +2 for serve_lora's w1) —
    # the engine helpers are the ONE place the program-count contract
    # lives
    assert len(want) == (2 * eng.expected_program_count()
                         + 4 * len(eng.swap_program_shapes()) + 2 + 2
                         + len(train_names))
    assert names == want, names


def test_gate_stays_under_budget(artifacts):
    # the whole lower+compile pass must stay cheap enough for tier-1;
    # budget raised 45s -> 95s with the PR 19 train/* family (7 train
    # programs at ~6s each lock the explicit ZeRO collective shapes —
    # paid for by slow-marking heavier always-on tests the same PR)
    assert _build_s[0] < 95.0, (
        f"hlolint program set took {_build_s[0]:.1f}s to lower+compile "
        "(budget 95s) — shrink the tiny config or trim the registry")


def test_tp2_collectives_match_the_layout_budget(artifacts):
    by_name = {a.name: a for a in artifacts}
    tp2 = by_name["serve/tp2/w1"]
    assert tp2.collectives == serving_collective_budget(
        ir.tiny_gpt_config(), 2)
    # 2 output projections per layer + the vocab-parallel embedding psum
    assert tp2.collectives["all-reduce"] == 2 * 2 + 1
    # exactly ONE all-gather: the sampler-boundary logit materialization
    assert tp2.collectives["all-gather"] == 1
    for name in ("w4", "w8"):
        assert by_name[f"serve/tp2/{name}"].collectives == tp2.collectives
    for name in ("w1", "w4", "w8"):
        assert not any(by_name[f"serve/tp1/{name}"].collectives.values())


def test_int8_tp2_collectives_match_the_quantized_budget(artifacts):
    """EQuARX per-op gating, locked by IR001: with both RowParallel
    projections quantized, each f32 all-reduce becomes an int8-payload
    all-gather + f32-scalar all-gather pair — 2L quantized ops leave
    exactly ONE f32 all-reduce (the vocab-parallel embedding psum) and
    2*2*L+1 all-gathers (incl. the sampler boundary)."""
    by_name = {a.name: a for a in artifacts}
    q = by_name["serve_int8/tp2/w1"]
    assert q.collectives == serving_collective_budget(
        ir.tiny_gpt_config(), 2, quant_collectives=("attn_proj",
                                                    "ffn_fc2"))
    assert q.collectives["all-reduce"] == 1
    assert q.collectives["all-gather"] == 2 * 2 * 2 + 1
    # single-chip int8: no collectives at all, like the f32 family
    assert not any(by_name["serve_int8/tp1/w1"].collectives.values())


def test_lora_family_adds_zero_collectives(artifacts):
    """The serve_lora IR001 pin: the in-step adapter gather must add NO
    collectives at any tp degree — A tables replicate, B tables shard on
    the already-tp-sharded output axis, and the per-row gather + two
    rank-r matmuls are chip-local. The budget is therefore the SAME
    arithmetic `serving_collective_budget` as the base family; a LoRA
    refactor that starts re-gathering adapter shards (or all-reducing
    the delta separately from the base projection) busts IR001 here."""
    by_name = {a.name: a for a in artifacts}
    for tp in (1, 2):
        base = by_name[f"serve/tp{tp}/w1"]
        lora = by_name[f"serve_lora/tp{tp}/w1"]
        assert lora.collectives == base.collectives, (tp, lora.collectives)
        # the adapter gather is REAL work, not a no-op: IR004 locks the
        # flops/bytes delta via serve_lora's own baseline entries
        assert lora.facts["flops"] > base.facts["flops"], tp
        assert (lora.facts["bytes_accessed"]
                > base.facts["bytes_accessed"]), tp
    assert not any(by_name["serve_lora/tp1/w1"].collectives.values())
    assert by_name["serve_lora/tp2/w1"].collectives == (
        serving_collective_budget(ir.tiny_gpt_config(), 2))


def test_int8_step_reads_fewer_bytes(artifacts):
    """The perf claim behind the int8 arena, checked on XLA's own cost
    model: the quantized decode step accesses fewer bytes than the f32
    program at the same (B, W) — the attention working set quarters and
    the scale sidecar must not eat the win."""
    by_name = {a.name: a for a in artifacts}
    for tp in (1, 2):
        f32 = by_name[f"serve/tp{tp}/w1"].facts["bytes_accessed"]
        q = by_name[f"serve_int8/tp{tp}/w1"].facts["bytes_accessed"]
        assert q < f32, (tp, q, f32)
    # and the host-tier swap copies move ~4x fewer bytes per block
    for tp in (1, 2):
        f32 = by_name[f"serve/tp{tp}/swap_out"].facts["bytes_accessed"]
        q = by_name[f"serve_int8/tp{tp}/swap_out"].facts["bytes_accessed"]
        assert q < 0.5 * f32, (tp, q, f32)


def test_donation_aliases_match_the_gate(artifacts):
    """tp=1 donates unconditionally: the arena inputs must actually
    alias. tp=2 on the cpu host platform is gated OFF: nothing may
    alias (the PR 3 miscompile is outputs aliasing freed inputs)."""
    for a in artifacts:
        if not a.name.startswith("serve/tp1/"):
            continue
        don = a.expected["donation"]
        if a.kind == "swap_out":
            # the gather's arena inputs stay live: NOTHING may alias
            assert don["expected"] is False
            assert a.aliases == [], (a.name, a.aliases)
            continue
        assert don["expected"] is True
        aliased = {al.param_number for al in a.aliases}
        assert set(don["param_indices"]) <= aliased, (a.name, a.aliases)
        # and the aliased outputs are the updated arenas, not the tokens
        outs = {al.output_index[0] for al in a.aliases}
        assert outs == set(don["output_indices"]), (a.name, a.aliases)
    for a in artifacts:
        if a.name.startswith("serve/tp2/") or a.kind == "train":
            assert a.expected["donation"]["expected"] is False
            assert a.aliases == [], (a.name, a.aliases)


# ---------------------------------------------------------------------------
# seeded regressions: the two incidents the checker exists to catch


def _qkv_major_split(qkv, b, s, num_heads, head_dim):
    """The pre-PR-10 layout: all Q heads first. A contiguous tp shard of
    the fused 3h axis is then NOT a head group, so XLA must re-gather
    the sharded axis inside every layer."""
    from paddle_tpu.ops import manipulation as M

    qkv = M.reshape(qkv, [b, s, 3, num_heads, head_dim])
    q = M.squeeze(M.slice(qkv, [2], [0], [1]), 2)
    k = M.squeeze(M.slice(qkv, [2], [1], [2]), 2)
    v = M.squeeze(M.slice(qkv, [2], [2], [3]), 2)
    return q, k, v


def test_qkv_major_layout_trips_the_all_gather_budget(monkeypatch):
    from paddle_tpu.models import gpt as gpt_mod

    monkeypatch.setattr(gpt_mod, "_split_fused_qkv", _qkv_major_split)
    arts = ir.serving_artifacts(tp_degrees=(2,), kinds=["w1"])
    (art,) = arts
    assert art.collectives["all-gather"] > 1, art.collectives
    violations = contracts.evaluate(arts, select=["IR001"])
    assert violations, "qkv-major regroup must blow the collective budget"
    msg = violations[0].format()
    assert "IR001" in msg and "collective-budget" in msg
    assert "all-gather" in msg
    # the message names the offending HLO ops so the diff author sees
    # WHERE the re-gather got inserted
    assert "offending HLO ops" in msg and "all-gather" in msg, msg


def test_ungated_donation_trips_the_donation_contract(monkeypatch):
    from paddle_tpu.parallel import spmd

    monkeypatch.setattr(spmd, "mesh_donate_argnums",
                        lambda argnums: tuple(argnums))
    arts = ir.serving_artifacts(tp_degrees=(2,), kinds=["w1"])
    (art,) = arts
    assert art.aliases, "ungated donation should alias on the host mesh"
    violations = contracts.evaluate(arts, select=["IR002"])
    assert violations, "ungated sharded donation must trip IR002"
    msg = violations[0].format()
    assert "IR002" in msg and "donation-verified" in msg
    assert "input_output_alias" in msg and "param" in msg, msg


def test_silently_disabled_equarx_gate_trips_the_quantized_budget(
        monkeypatch):
    """The int8 family's IR001 is a REGRESSION tripwire, not just a
    description: if the per-op quantization hook stops firing (here:
    `PagedLayerView.row_parallel` patched back to a plain layer call — the
    shape of a refactor that loses the gate), the engine still REPORTS
    quantized collectives, the budget still expects the all-gather
    pairs, and the now-f32 program must fail the contract instead of
    silently serving unquantized."""
    from paddle_tpu.serving.block_pool import PagedLayerView

    monkeypatch.setattr(PagedLayerView, "row_parallel",
                        lambda self, layer, x, op_name: layer(x))
    arts = ir.serving_artifacts(tp_degrees=(2,), kinds=["w1"],
                                kv_dtype="int8", quant_allreduce=True,
                                prefix="serve_int8")
    (art,) = arts
    # the broken gate falls back to plain psum all-reduces
    assert art.collectives["all-reduce"] > 1, art.collectives
    violations = contracts.evaluate(arts, select=["IR001"])
    assert violations, "a disabled EQuARX gate must blow the budget"
    msg = violations[0].format()
    assert "IR001" in msg and "collective-budget" in msg, msg


def test_hoisted_adapter_gather_trips_host_sync_hygiene(monkeypatch):
    """The serve_lora seeded regression: an adapter gather hoisted out
    of the compiled step onto the host (here: `gather_adapter_rows`
    patched to a `jax.pure_callback` row lookup — the shape of a
    refactor that 'simplifies' the per-row gather into a host-side
    table read) reintroduces a per-step device→host round trip. The
    callback's custom-call lands at its use site, upstream of the
    LM-head matmul, so IR003's whole-program hygiene flags it (IR005's
    sampler-tail check is the backstop had it landed after the head);
    the message must name the callback target so the diff author sees
    WHAT synced."""
    import jax

    from paddle_tpu.models import lora as lora_mod

    def hoisted_gather(tables, slots):
        if not tables:
            return None
        out = {}
        for name, (A, B) in tables.items():
            out[name] = tuple(
                jax.pure_callback(
                    lambda t, s: np.asarray(t)[np.asarray(s)],
                    jax.ShapeDtypeStruct(
                        (slots.shape[0],) + tab.shape[1:], tab.dtype),
                    tab, slots, vmap_method="sequential")
                for tab in (A, B))
        return out

    monkeypatch.setattr(lora_mod, "gather_adapter_rows", hoisted_gather)
    arts = ir.serving_artifacts(tp_degrees=(1,), kinds=["w1"],
                                lora_slots=2, prefix="serve_lora")
    (art,) = arts
    # the target jax 0.9.0 gives a pure_callback on the CPU
    assert any(op.custom_call_target == "xla_ffi_python_cpu_callback"
               for op in art.ops
               if op.opcode.startswith("custom-call")), art.name
    violations = contracts.evaluate(arts, select=["IR003", "IR005"])
    assert violations, "a host-hoisted adapter gather must trip hygiene"
    msg = violations[0].format()
    assert "IR003" in msg and "host-sync-hygiene" in msg, msg
    assert "xla_ffi_python_cpu_callback" in msg, msg


# ---------------------------------------------------------------------------
# cheap contract-unit checks (hand-built artifacts, no lowering)


def _fake_artifact(**kw):
    base = dict(name="serve/tp2/decode", kind="decode", tp_degree=2,
                backend="cpu", hlo_text="", ops=[], aliases=[],
                facts={}, expected={})
    base.update(kw)
    return ir.ProgramArtifact(**base)


def test_host_sync_hygiene_contract_flags_unsanctioned_custom_call():
    op = ir.HloOp(opcode="custom-call", result_type="f32[2]", line=7,
                  op_name="jit(step)/jit(main)/pure_callback",
                  custom_call_target="xla_python_cpu_callback",
                  text="custom-call(...)")
    art = _fake_artifact(ops=[op])
    violations = contracts.evaluate([art], select=["IR003"], baseline={})
    assert len(violations) == 1
    assert "xla_python_cpu_callback" in violations[0].message
    # whitelisted targets (the Pallas kernel, SPMD plumbing) pass
    ok = ir.HloOp(opcode="custom-call", result_type="f32[2]", line=7,
                  op_name="x", custom_call_target="tpu_custom_call",
                  text="custom-call(...)")
    assert contracts.evaluate([_fake_artifact(ops=[ok])],
                              select=["IR003"], baseline={}) == []


def test_sampler_fused_contract_flags_host_call_after_lm_head():
    """IR005: a host custom-call BETWEEN attention/LM-head and token
    emission (a callback-based sampler, say) trips the contract; the
    same call before the last matmul — or in a program with no sampler
    region (train) — does not."""
    def mm(line):
        return ir.HloOp(opcode="dot-general", result_type="f32[2,2]",
                        line=line, op_name="jit(step)/dot_general",
                        custom_call_target=None, text="dot-general(...)")

    def cb(line):
        return ir.HloOp(opcode="custom-call", result_type="s32[2]",
                        line=line,
                        op_name="jit(step)/jit(main)/pure_callback",
                        custom_call_target="xla_python_cpu_callback",
                        text="custom-call(...)")

    sampler_tail_call = _fake_artifact(
        ops=[mm(1), mm(2), cb(3)], expected={"sampler_region": True})
    violations = contracts.evaluate([sampler_tail_call], select=["IR005"],
                                    baseline={})
    assert len(violations) == 1
    msg = violations[0].format()
    assert "IR005" in msg and "sampler-fused" in msg
    assert "between attention and token emission" in msg
    # the same call BEFORE the last matmul is attention-side plumbing,
    # not a sampler host sync (IR003's whitelist governs it)
    pre = _fake_artifact(ops=[mm(1), cb(2), mm(3)],
                         expected={"sampler_region": True})
    assert contracts.evaluate([pre], select=["IR005"], baseline={}) == []
    # GSPMD annotation calls in the tail are tolerated
    ann = ir.HloOp(opcode="custom-call", result_type="f32[2]", line=3,
                   op_name="x", custom_call_target="Sharding",
                   text="custom-call(...)")
    tol = _fake_artifact(ops=[mm(1), mm(2), ann],
                         expected={"sampler_region": True})
    assert contracts.evaluate([tol], select=["IR005"], baseline={}) == []
    # programs without a sampler region (train) skip the contract
    train = _fake_artifact(ops=[mm(1), cb(2)], expected={})
    assert contracts.evaluate([train], select=["IR005"], baseline={}) == []


def test_donation_contract_flags_wrong_output_alias():
    """Aliasing SOMEWHERE is not enough: a donated arena routed to the
    wrong output (in-place reuse of the sampled-tokens buffer, say) must
    trip IR002 even though the param number appears in the alias map."""
    don = {"expected": True, "param_indices": (10, 11),
           "output_indices": (2, 3), "what": "KV arena (k, v)"}
    right = [ir.Alias(output_index=(2,), param_number=10, kind="must-alias"),
             ir.Alias(output_index=(3,), param_number=11, kind="must-alias")]
    art = _fake_artifact(aliases=right, expected={"donation": don})
    assert contracts.evaluate([art], select=["IR002"], baseline={}) == []
    wrong = [ir.Alias(output_index=(0,), param_number=10, kind="must-alias"),
             ir.Alias(output_index=(3,), param_number=11, kind="must-alias")]
    art = _fake_artifact(aliases=wrong, expected={"donation": don})
    violations = contracts.evaluate([art], select=["IR002"], baseline={})
    assert len(violations) == 1
    msg = violations[0].message
    assert "parameter 10" in msg and "output 0" in msg and "2" in msg


def test_baseline_contract_flags_drift_and_missing_programs(artifacts):
    art = artifacts[0]
    drifted = dataclasses.replace(
        art, facts={k: v * 3 for k, v in art.facts.items()})
    violations = contracts.evaluate([drifted], select=["IR004"])
    assert violations and "drifted" in violations[0].message
    unknown = dataclasses.replace(art, name="serve/tp2/nonesuch")
    violations = contracts.evaluate([unknown], select=["IR004"])
    assert violations and "no recorded baseline" in violations[0].message
    # a missing/unreadable baseline FILE must not silently disable the
    # contract (a wheel without the package-data entry would otherwise be
    # a permanent false green) — it reports every program as unrecorded
    violations = contracts.evaluate([art], select=["IR004"], baseline={})
    assert violations and "no recorded baseline" in violations[0].message


# ---------------------------------------------------------------------------
# schema canary: HLO-text parsing vs jax lowering-format drift


def test_hlo_parser_schema_canary():
    """Lower a trivial jitted psum on the fake mesh and assert the
    parser extracts the expected op names — if jax/XLA ever change the
    compiled-HLO text format, THIS fails with a pointed message instead
    of every contract passing vacuously on empty parses."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "tp"), mesh=mesh,
                               in_specs=P("tp"), out_specs=P()))
    comp = fn.lower(jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    text = comp.as_text()
    ops = ir.parse_hlo_ops(text)
    drift = ("jax lowering-format drift: analysis/ir.py's HLO-text "
             "parser no longer extracts %s from a trivial jitted psum — "
             "fix the parser or every IR contract passes vacuously")
    assert ops, drift % "any instructions"
    counts = ir.collective_counts(ops)
    assert counts["all-reduce"] >= 1, drift % "the psum's all-reduce"
    ar = next(o for o in ops if ir._base_opcode(o.opcode) == "all-reduce")
    assert ar.result_type.startswith("f32"), drift % "result types"
    assert any(o.op_name for o in ops), drift % "op_name metadata"

    donated = jax.jit(lambda a: a * 2.0, donate_argnums=(0,))
    dcomp = donated.lower(
        jax.ShapeDtypeStruct((128,), jnp.float32)).compile()
    aliases = ir.parse_input_output_aliases(dcomp.as_text())
    assert [a.param_number for a in aliases] == [0], (
        drift % "the input_output_alias map")
    facts = ir.extract_facts(dcomp)
    assert facts.get("flops", 0) > 0, drift % "cost_analysis flops"
    assert facts.get("peak_bytes", 0) > 0, drift % "memory_analysis sizes"


# ---------------------------------------------------------------------------
# CLI: both layers behind one command


def test_cli_ir_without_jax_exits_2(capsys, monkeypatch):
    from paddle_tpu.analysis import cli

    def broken_import():
        raise ImportError("No module named 'jax'")

    monkeypatch.setattr(cli, "_import_jax", broken_import)
    assert cli.main(["--ir"]) == 2
    err = capsys.readouterr().err
    assert "jax" in err and "--ir" in err
    # the AST-only path stays stdlib-pure and fully functional
    monkeypatch.undo()
    assert cli.main(["--update-baseline"]) == 2  # requires --ir
    capsys.readouterr()
    # a contract-only --select without --ir must be a usage error, not a
    # run of NEITHER layer that exits 0 (a false green in a CI job that
    # dropped the flag)
    assert cli.main(["--select", "IR001"]) == 2
    assert "--ir" in capsys.readouterr().err
    # same class: a typo'd id prefix must not silently run neither layer
    assert cli.main(["--select", "JK001"]) == 2
    assert "JK001" in capsys.readouterr().err
    assert cli.main(["--ignore", "XX999"]) == 2
    assert "XX999" in capsys.readouterr().err
    # and a correctly-prefixed but NONEXISTENT id (IR01 typo of IR001)
    # must not select zero contracts and exit 0 — validate against the
    # catalog, not the prefix
    assert cli.main(["--select", "IR01"]) == 2
    assert "IR01" in capsys.readouterr().err
    assert cli.main(["--ignore", "JL999"]) == 2
    assert "JL999" in capsys.readouterr().err
    # a typo'd explicit path must exit 2 even when an IR-only --select
    # skips the AST sweep that would have read it — not silently pass
    # having checked nothing at that path (returns before any lowering,
    # so this costs no compile time)
    assert cli.main(["--ir", "--select", "IR001",
                     "/no/such/paddle_tpu_path"]) == 2
    assert "no such path" in capsys.readouterr().err


def test_cli_harness_errors_exit_2_but_program_failures_propagate(
        capsys, monkeypatch):
    """Only usage-shaped harness failures (IRHarnessError, OSError) map
    to exit 2; a genuine lowering/compile failure of a registered
    program — jax's XlaRuntimeError is also a RuntimeError subclass —
    must propagate with its traceback instead of masquerading as a
    misconfigured invocation a CI wrapper might skip."""
    from paddle_tpu.analysis import cli

    def harness_broken(args, ir_select, ir_ignore, record_only=False):
        raise ir.IRHarnessError("backend has 1 device")

    monkeypatch.setattr(cli, "_run_ir", harness_broken)
    assert cli.main(["--ir", "--select", "IR001"]) == 2
    assert "1 device" in capsys.readouterr().err

    class FakeXlaRuntimeError(RuntimeError):
        pass

    def program_broken(args, ir_select, ir_ignore, record_only=False):
        raise FakeXlaRuntimeError("INTERNAL: program failed to compile")

    monkeypatch.setattr(cli, "_run_ir", program_broken)
    with pytest.raises(FakeXlaRuntimeError):
        cli.main(["--ir", "--select", "IR001"])


def test_cli_select_and_ignore_span_both_layers(capsys, monkeypatch,
                                                artifacts):
    from paddle_tpu.analysis import cli

    # reuse the module fixture's artifacts so the CLI test costs no
    # second lowering pass
    monkeypatch.setattr(ir, "default_artifacts", lambda: artifacts)
    assert cli.main(["--ir", "--select", "IR001,IR002,IR003", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ir"]["summary"]["programs"] == len(artifacts)
    assert doc["ir"]["summary"]["violations"] == 0
    # an IR-only select skips the AST sweep (0 files linted)
    assert doc["summary"]["files"] == 0
    # per-program facts + collectives ride on the JSON line
    names = {p["name"] for p in doc["ir"]["programs"]}
    assert "serve/tp2/w1" in names
    p = next(p for p in doc["ir"]["programs"]
             if p["name"] == "serve/tp2/w1")
    assert p["collectives"]["all-reduce"] == 5
    assert {"flops", "bytes_accessed", "peak_bytes"} <= set(p["facts"])
    # ignoring every contract leaves the IR layer green trivially
    assert cli.main(["--ir", "--ignore",
                     "IR001,IR002,IR003,IR004,IR005"]) == 0
    capsys.readouterr()
    # a JL-only select skips the IR layer even with --ir: no "ir" key
    assert cli.main(["--ir", "--select", "JL008", "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert "ir" not in doc
    assert cli.main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "IR001" in out and "JL008" in out


def test_cli_update_baseline_respects_jl_only_select(capsys, monkeypatch,
                                                     artifacts, tmp_path):
    """--update-baseline forced the IR layer on so the artifacts exist to
    record from, but a JL-only --select still means "skip this layer's
    CHECKS": the baseline is written and no contract evaluates (an IR004
    drift between the old and new baseline must not flip the exit)."""
    from paddle_tpu.analysis import cli

    monkeypatch.setattr(ir, "default_artifacts", lambda: artifacts)
    path = tmp_path / "ir_baseline.json"
    monkeypatch.setattr(contracts, "BASELINE_PATH", str(path))
    assert cli.main(["--ir", "--update-baseline", "--select", "JL008",
                     "--json"]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["ir"]["summary"]["violations"] == 0
    recorded = json.loads(path.read_text())
    assert set(recorded["programs"]) == {a.name for a in artifacts}
