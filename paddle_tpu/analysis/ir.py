"""hlolint: the compiled-program half of the analyzer.

jaxlint (core.py) checks the SOURCE; this module checks the artifact XLA
actually runs. The two most expensive recent regressions lived below the
AST where no source rule could see them: a fused-QKV layout change that
silently added per-layer all-gathers to every tp=2 decode step (caught by
hand in PR 10 review), and donation that silently didn't alias (the PR 3
host-platform miscompile). Both are properties of the LOWERED program —
its collective ops, its ``input_output_alias`` map — so hlolint lowers
the handful of programs this repo actually serves and trains with,
parses the post-SPMD HLO text plus ``compiled.cost_analysis()`` /
``memory_analysis()``, and hands the resulting `ProgramArtifact`s to the
declarative contracts in `contracts.py`.

The program set (`default_artifacts`): the serving engine's unified
ragged step program at every width bucket (``w1`` / ``w4`` / ``w8`` on
the harness config — decode, spec, and chunk widths of ONE kind-free
program) at tp=1 and tp=2 on the 8-fake-device host mesh, the host-tier
swap gather/scatter pair at each tp degree (serving/kv_tier.py — the
swap-in donation and the swap-out no-alias are IR002 facts), plus the
spmd train step on a dp2 x mp2 mesh — all on the smallest GPT config that
still exercises tp sharding, so the whole pass lowers + compiles in
seconds and can gate tier-1 (tests/test_ir_contracts.py).

Everything here imports jax lazily: ``paddle_tpu.analysis`` itself stays
stdlib-pure (the AST layer must run before the heavyweight runtime even
installs), and the CLI exits 2 with a pointed message when ``--ir`` is
requested without jax (cli.py).

HLO-text parsing is deliberately narrow — instruction opcode, result
type, ``op_name``/``custom_call_target`` metadata, and the module's
``input_output_alias`` map — and a schema canary (a trivial jitted psum
in tests/test_ir_contracts.py) fails CI with a pointed message if a jax
lowering-format drift ever makes the parser extract nothing, so the
contracts can never pass vacuously.
"""
from __future__ import annotations

import dataclasses
import re

# Collective opcodes counted by `collective_counts` (async `-start`
# forms normalize onto the base opcode; `-done` halves are skipped so an
# async pair still counts once).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "all-to-all",
    "reduce-scatter",
    "collective-permute",
    "collective-broadcast",
)

# Opcodes that round-trip through the host (or an opaque runtime call)
# inside a compiled program — the IR-level backstop behind jaxlint JL003.
HOST_BOUNDARY_OPS = (
    "custom-call",
    "infeed",
    "outfeed",
    "send",
    "recv",
)

# custom-call targets sanctioned inside serving/train programs: device
# kernels and SPMD plumbing, not host syncs. (The cpu host-platform
# programs compile to none of these today; the entries keep a real-TPU
# run of the same contracts from tripping on the Pallas ragged kernel.)
DEFAULT_CUSTOM_CALL_WHITELIST = frozenset({
    "tpu_custom_call",            # Pallas ragged paged-attention kernel
    "Sharding",                   # GSPMD annotation calls
    "SPMDFullToShardShape",       # shard_map boundaries
    "SPMDShardToFullShape",
})


# ---------------------------------------------------------------------------
# HLO text model


@dataclasses.dataclass
class HloOp:
    """One parsed HLO instruction line."""

    opcode: str
    result_type: str
    line: int                     # 1-based line in the HLO text
    op_name: str | None           # jax-stamped metadata (source op path)
    custom_call_target: str | None
    text: str                     # the stripped instruction line

    def describe(self):
        where = f" at {self.op_name}" if self.op_name else ""
        tgt = (f' target="{self.custom_call_target}"'
               if self.custom_call_target else "")
        return f"{self.opcode} {self.result_type}{tgt}{where}"


@dataclasses.dataclass
class Alias:
    """One entry of the module's ``input_output_alias`` map."""

    output_index: tuple           # tuple-shape index of the aliased output
    param_number: int             # flat entry-parameter number
    kind: str                     # "may-alias" | "must-alias"


# instruction line: `[ROOT] %name = <type> opcode(...)`; the result type
# may itself be a parenthesized tuple type containing spaces, so match it
# as either one paren group or one space-free token
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.-]+\s*=\s*"
    r"(?P<type>\([^)]*\)|\S+?)\s+"
    r"(?P<opcode>[a-z][\w-]*)\("
)
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_CC_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_ALIAS_ENTRY_RE = re.compile(
    r"\{\s*([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(may-alias|must-alias)\)"
)


def parse_hlo_ops(text):
    """Every instruction in an HLO module text, entry and non-entry
    computations alike (a collective inside a while body or a cond
    branch is still a per-invocation collective). Parameter lines carry
    no call parens and are skipped — we model ops, not values."""
    ops = []
    for i, line in enumerate(text.splitlines(), start=1):
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        name = _OP_NAME_RE.search(line)
        tgt = _CC_TARGET_RE.search(line)
        ops.append(HloOp(
            opcode=m.group("opcode"),
            result_type=m.group("type"),
            line=i,
            op_name=name.group(1) if name else None,
            custom_call_target=tgt.group(1) if tgt else None,
            text=line.strip(),
        ))
    return ops


def parse_input_output_aliases(text):
    """The module header's ``input_output_alias={...}`` entries (the
    ground truth of what donation actually bought), as `Alias` rows.
    Absent or empty map parses to []."""
    m = re.search(r"input_output_alias=\{(.*)$", text, re.M)
    if m is None:
        return []
    # the map is one header line; entries are nested-brace groups
    return [
        Alias(
            output_index=tuple(int(s) for s in idx.split(",") if s.strip()),
            param_number=int(param),
            kind=kind,
        )
        for idx, param, kind in _ALIAS_ENTRY_RE.findall(m.group(1))
    ]


def _base_opcode(opcode):
    return opcode[:-6] if opcode.endswith("-start") else opcode


def collective_counts(ops):
    """{collective opcode: count} over every parsed op, zero-filled so a
    contract can assert on absent opcodes too."""
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for op in ops:
        if op.opcode.endswith("-done"):
            continue
        base = _base_opcode(op.opcode)
        if base in counts:
            counts[base] += 1
    return counts


def host_boundary_ops(ops):
    """Ops that leave the device program: custom-calls, infeed/outfeed,
    send/recv (async ``-done`` halves skipped — the ``-start`` carries
    the target)."""
    return [
        op for op in ops
        if not op.opcode.endswith("-done")
        and _base_opcode(op.opcode) in HOST_BOUNDARY_OPS
    ]


# matmul-class opcodes: the LAST one in a serving step is the LM head
# projection — everything after it is the on-device sampler / spec-accept
# / emission-packing tail (IR005's "between attention and token
# emission" region)
_MATMUL_OPS = ("dot", "dot-general", "convolution")


def sampler_region_ops(ops):
    """Ops after the program's LAST matmul-class op (text order). In a
    serving step every attention and projection matmul — the LM head
    included — precedes sampling, so this tail is exactly the compiled
    sampler + speculative accept + packed-output assembly. The unified
    ragged program moved that whole region on-device; a host callback
    reintroduced there (e.g. ``jax.pure_callback`` sampling) lowers to a
    custom-call at its use site, which IR005 flags."""
    last = -1
    for idx, op in enumerate(ops):
        if _base_opcode(op.opcode) in _MATMUL_OPS:
            last = idx
    return ops[last + 1:]


# ---------------------------------------------------------------------------
# program artifacts


@dataclasses.dataclass
class ProgramArtifact:
    """One lowered+compiled program plus every fact the contracts check."""

    name: str                     # "serve/tp2/w1", "train/dp2_mp2"
    kind: str                     # "w<width>" (serving) | "train"
    tp_degree: int
    backend: str
    hlo_text: str
    ops: list
    aliases: list
    facts: dict                   # flops / bytes_accessed / peak_bytes ...
    expected: dict                # contract inputs (budgets, donation map)

    @property
    def collectives(self):
        return collective_counts(self.ops)

    def to_json(self):
        return {
            "name": self.name,
            "kind": self.kind,
            "tp_degree": self.tp_degree,
            "backend": self.backend,
            "facts": self.facts,
            "collectives": self.collectives,
            "aliases": [
                {"output_index": list(a.output_index),
                 "param_number": a.param_number, "kind": a.kind}
                for a in self.aliases
            ],
        }


def extract_facts(compiled):
    """Machine-readable program-shape facts from a `jax.stages.Compiled`:
    flops and bytes-accessed from ``cost_analysis()`` (a list on some jax
    versions, a bare dict on others), buffer sizes and a peak-memory
    estimate from ``memory_analysis()``."""
    facts = {}
    try:
        ca = compiled.cost_analysis()
    except Exception:  # pragma: no cover - backend without cost analysis
        ca = None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if isinstance(ca, dict):
        facts["flops"] = float(ca.get("flops", 0.0))
        facts["bytes_accessed"] = float(ca.get("bytes accessed", 0.0))
    try:
        ma = compiled.memory_analysis()
    except Exception:  # pragma: no cover
        ma = None
    if ma is not None:
        arg = int(getattr(ma, "argument_size_in_bytes", 0))
        out = int(getattr(ma, "output_size_in_bytes", 0))
        tmp = int(getattr(ma, "temp_size_in_bytes", 0))
        facts.update(
            argument_bytes=arg, output_bytes=out, temp_bytes=tmp,
            # Donated buffers count on BOTH sides here (upper-bound
            # accounting). memory_analysis().alias_size_in_bytes is NOT
            # subtracted: a persistent-cache-deserialized executable
            # reports 0 for it while a fresh compile of the same program
            # reports the donated bytes, so any formula involving it
            # flaps with cache hit/miss and breaks the IR004 baseline
            # band. Donation correctness is IR002's job; this number
            # only needs to be a deterministic drift detector.
            peak_bytes=arg + out + tmp,
        )
    return facts


def artifact_from_compiled(name, kind, tp_degree, backend, compiled,
                           expected):
    text = compiled.as_text()
    return ProgramArtifact(
        name=name, kind=kind, tp_degree=tp_degree, backend=backend,
        hlo_text=text, ops=parse_hlo_ops(text),
        aliases=parse_input_output_aliases(text),
        facts=extract_facts(compiled), expected=dict(expected),
    )


# ---------------------------------------------------------------------------
# the lowering harness


class IRHarnessError(RuntimeError):
    """Usage-shaped failure of the --ir harness itself (the initialized
    backend cannot host the tp=2 mesh) — the CLI maps it to exit 2.
    Deliberately NOT raised for lowering/compile failures of a registered
    program: jax's XlaRuntimeError is also a RuntimeError subclass, and a
    program that stopped compiling is a regression that must propagate
    with its traceback, not masquerade as a misconfigured invocation."""


def ensure_host_devices(n=8):
    """Make sure the jax backend can host the tp=2 mesh. Any backend with
    >= 2 devices is accepted as-is (a real TPU pod runs the same
    contracts on its own chips); otherwise raise IRHarnessError — which
    the CLI turns into exit 2 — pointing at the 8-fake-device host
    platform. Only the CLI's own re-exec'd process (cli.py
    `_reexec_on_fake_mesh_if_needed`, marked by _PADDLE_TPU_IR_REEXEC)
    may pin the platform here: a PROGRAMMATIC caller on an accelerator
    host must never have its process-wide backend silently repointed to
    fake CPU devices by a lint pass."""
    import os

    import jax

    flags = os.environ.get("XLA_FLAGS", "")
    if ("--xla_force_host_platform_device_count" not in flags
            and os.environ.get("_PADDLE_TPU_IR_REEXEC")):
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()
        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:  # pragma: no cover - backend already pinned
            pass
    if len(jax.devices()) < 2:
        raise IRHarnessError(
            f"hlolint needs >= 2 devices for the tp=2 contracts but the "
            f"initialized backend ({jax.default_backend()}) has "
            f"{len(jax.devices())} — run before jax initializes, or on "
            "the 8-fake-device host platform (tests/_cpu_mesh.py)"
        )


def tiny_gpt_config():
    """The smallest GPT that still exercises tp sharding: 2 heads / 64
    vocab / 128 FFN columns all divide tp=2, so every Megatron layout
    (column, row, vocab-parallel) and the head-sharded arena appear in
    the lowered programs while each compile stays ~1s on the host
    platform (the tier-1 gate budget)."""
    from ..models.gpt import GPTConfig

    return GPTConfig(vocab_size=64, hidden_size=32, num_layers=2,
                     num_heads=2, max_seq_len=64, attn_impl="xla",
                     dropout=0.0)


def build_serving_engine(model, tp_degree, kv_dtype=None,
                         quant_allreduce=None, lora_slots=0, lora_rank=4):
    """The harness engine: spec decoding ON so every default width
    bucket exists (w1 decode, w4 spec, w8 chunk); mesh=1 is the
    single-chip engine (serving/sharded.py).
    ``kv_dtype``/``quant_allreduce`` select the int8 program family
    (quantized arena + EQuARX collectives);
    ``lora_slots`` the serve_lora family (stacked adapter tables gathered
    per row inside the same unified step)."""
    from ..serving.engine import LLMEngine

    return LLMEngine(model, block_size=8, max_batch=2, prefill_chunk=8,
                     mesh=tp_degree, spec_decoding=True, num_spec_tokens=3,
                     host_kv_blocks=8, kv_dtype=kv_dtype,
                     quant_allreduce=quant_allreduce,
                     lora_slots=lora_slots, lora_rank=lora_rank)


def serving_artifacts(model=None, tp_degrees=(1, 2), kinds=None,
                      kv_dtype=None, quant_allreduce=None, prefix="serve",
                      include_swap=None, lora_slots=0, lora_rank=4):
    """Lower + compile the engine's width-bucket programs at each tp
    degree; returns [ProgramArtifact]. `kinds` restricts to a name
    subset (the seeded-regression tests lower just "w1");
    `include_swap` overrides the default "swap programs only on the
    full set" rule. `kv_dtype`/`quant_allreduce` build the int8 family
    under its own `prefix` — the budget derives from the ENGINE's
    resolved `quant_collectives` (per-op gating), so IR001 locks the
    quantized collective shape exactly. `lora_slots` builds the
    serve_lora family: the budget is the SAME arithmetic
    `serving_collective_budget` as the base family — the per-row
    adapter gather adds tensors, never collectives (A replicated, B
    sharded on the already-tp-sharded output axis), and IR001 pins
    that at every tp degree."""
    import jax

    from ..models.gpt import GPT
    from ..serving.sharded import serving_collective_budget

    if model is None:
        model = GPT(tiny_gpt_config())
    if include_swap is None:
        include_swap = kinds is None
    arts = []
    for tp in tp_degrees:
        eng = build_serving_engine(model, tp, kv_dtype=kv_dtype,
                                   quant_allreduce=quant_allreduce,
                                   lora_slots=lora_slots,
                                   lora_rank=lora_rank)
        spec = eng.step_program_spec()
        budget = serving_collective_budget(
            model.cfg, tp, quant_collectives=eng.quant_collectives)
        arena_what = ("KV arena (k, v, k_scale, v_scale)"
                      if eng.pool.quantized else "KV arena (k, v)")
        for name, lowered in eng.lowered_step_programs(kinds=kinds).items():
            expected = {
                "collective_budget": budget,
                "donation": {
                    "expected": spec["donation_expected"],
                    "param_indices": spec["arena_param_indices"],
                    "output_indices": spec["arena_output_indices"][name],
                    "what": arena_what,
                },
                "custom_call_whitelist": DEFAULT_CUSTOM_CALL_WHITELIST,
                # IR005: the program tail (post-attention sampling, spec
                # accept, emission packing) must stay free of host
                # boundaries — serving steps only; the train artifact
                # has no sampler region
                "sampler_region": True,
            }
            arts.append(artifact_from_compiled(
                f"{prefix}/tp{tp}/{name}", name, tp,
                jax.default_backend(), lowered.compile(), expected))
        if not include_swap:
            continue   # restricted step subset: skip the swap programs
        # the host-tier swap copies (serving/kv_tier.py): the swap-in
        # scatter must donate the arenas under the same gate as the step
        # program, and the swap-out gather must alias NOTHING (the arena
        # stays live under it). Chip-local copies — no collective budget.
        sspec = eng.swap_program_spec()
        for name, lowered in eng.lowered_swap_programs().items():
            expected = {
                "collective_budget": None,
                "donation": {
                    "expected": (sspec["donation_expected"]
                                 and name not in sspec["no_alias"]),
                    "param_indices": sspec["arena_param_indices"],
                    "output_indices":
                        sspec["arena_output_indices"].get(name),
                    "what": arena_what,
                },
                "custom_call_whitelist": DEFAULT_CUSTOM_CALL_WHITELIST,
            }
            arts.append(artifact_from_compiled(
                f"{prefix}/tp{tp}/{name}", name, tp,
                jax.default_backend(), lowered.compile(), expected))
    return arts


def train_artifact(mesh_degrees=None, zero_stage=0, gradient_merge_k=1,
                   quant_grads=False, explicit_update=None, optimizer="SGD",
                   name=None):
    """Lower + compile ONE spmd sharded train step configuration on the
    tiny GPT (dp2 x mp2 zero-0 by default: both the dp grad psums and the
    Megatron tp collectives appear). Explicit-path configurations
    (zero_stage >= 2 on a pure-dp mesh) get the EXACT layout-derived
    IR001 budget from `spmd.train_collective_budget`; GSPMD-lowered
    configurations have no arithmetic budget (collective counts are
    XLA-emergent) and are locked by their IR004 baselines instead. Every
    train artifact also carries the measured `per_chip_opt_state_bytes`
    fact from the PLACED init_state arrays — the IR004-locked proof that
    the explicit path's optimizer state actually drops ~dp-fold. The
    training mesh installs globally for the trace (mp_layers' constraints
    consult it) and ALWAYS restores — a leaked mesh would reject the
    serving engine's own placement (the PR 10 deep fix)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as paddle
    from ..distributed.mesh import get_mesh, init_mesh, set_mesh
    from ..models.gpt import GPT, gpt_loss_fn
    from ..parallel.spmd import (
        make_sharded_train_step,
        per_chip_opt_state_bytes,
        train_collective_budget,
    )

    degrees = dict(mesh_degrees or {"dp": 2, "mp": 2})
    if name is None:
        name = "train/" + "_".join(f"{k}{v}" for k, v in degrees.items())
    prev = get_mesh()
    mesh = init_mesh(degrees)
    try:
        model = GPT(tiny_gpt_config())
        opt_cls = getattr(paddle.optimizer, optimizer)
        opt = opt_cls(learning_rate=0.1, parameters=model.parameters())
        step = make_sharded_train_step(
            model, gpt_loss_fn, opt, mesh, batch_specs=(P("dp"), P("dp")),
            zero_stage=zero_stage, gradient_merge_k=gradient_merge_k,
            explicit_update=explicit_update, quant_grads=quant_grads)
        batch = jax.ShapeDtypeStruct((4, 16), jnp.int32)
        lowered, donation = step.lower_step(batch, batch)
        if step.explicit_update:
            budget = train_collective_budget(
                len(model.named_parameters_dict()),
                int(degrees.get("dp", 1)), quant_grads=quant_grads)
        else:
            # no arithmetic budget: GSPMD-lowered train collectives are
            # XLA-emergent — IR004 locks these programs' shape
            budget = None
        expected = {
            "collective_budget": budget,
            "donation": {
                "expected": donation["donation_expected"],
                "param_indices": donation["donated_param_indices"],
                "output_indices": None,
                "what": "params + optimizer state",
            },
            "custom_call_whitelist": DEFAULT_CUSTOM_CALL_WHITELIST,
        }
        art = artifact_from_compiled(
            name, "train", int(degrees.get("mp", 1)),
            jax.default_backend(), lowered.compile(), expected)
        _, _, opt_state = step.init_state()
        art.facts["per_chip_opt_state_bytes"] = per_chip_opt_state_bytes(
            opt_state)
        return art
    finally:
        set_mesh(prev)


def train_artifacts():
    """The train/* artifact family: the legacy dp2 x mp2 GSPMD step, the
    locked 'before' (constraint-hint zero-2 on the same mesh compiles to
    the SAME collective counts as zero-0 — the measured motivation for
    the explicit path), and the explicit weight-update matrix on the
    pure-dp mesh: zero stages 0 (GSPMD reference) / 2 / 3, gradient-merge
    on, and int8 quantized gradients — each explicit program carrying the
    exact `train_collective_budget` (zero full-size grad all-reduce at
    stage >= 2) and the per-chip optimizer-state-bytes fact. AdamW
    everywhere the optimizer-state shard matters (SGD has no slots)."""
    dp4 = {"dp": 4}
    return [
        train_artifact(),
        train_artifact(zero_stage=2, optimizer="AdamW",
                       name="train/dp2_mp2/zs2-legacy"),
        train_artifact(dp4, optimizer="AdamW", name="train/dp4/zs0"),
        train_artifact(dp4, zero_stage=2, optimizer="AdamW",
                       name="train/dp4/zs2"),
        train_artifact(dp4, zero_stage=3, optimizer="AdamW",
                       name="train/dp4/zs3"),
        train_artifact(dp4, zero_stage=2, gradient_merge_k=2,
                       optimizer="AdamW", name="train/dp4/zs2_gm2"),
        train_artifact(dp4, zero_stage=2, quant_grads=True,
                       optimizer="AdamW", name="train/dp4/zs2_q8"),
    ]


def default_artifacts():
    """The registered program set the CLI and the tier-1 gate evaluate:
    the unified step at every width bucket x {tp=1, tp=2} + the int8
    end-to-end family (quantized arena + EQuARX collectives; the w1
    decode step and the 4-array swap copies — the widths share one
    quantization story, so w1 pins the shape without tripling compile
    time) + the serve_lora family (2-slot adapter tables gathered per
    row inside the w1 decode step; the collective budget is IDENTICAL
    to the base family at both tp degrees — IR001's zero-new-collectives
    pin — and IR004 locks the adapter-gather flops/bytes delta) + the
    train/* family (legacy dp2 x mp2, the locked zs2-legacy 'before',
    and the explicit weight-update matrix on dp4)."""
    arts = serving_artifacts()
    arts += serving_artifacts(kinds=("w1",), kv_dtype="int8",
                              quant_allreduce=True, prefix="serve_int8",
                              include_swap=True)
    arts += serving_artifacts(kinds=("w1",), lora_slots=2,
                              prefix="serve_lora")
    arts += train_artifacts()
    return arts
