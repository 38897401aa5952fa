"""The attention kernels through the real TPU compiler, Mosaic included,
without a chip: libtpu can compile for a v5e topology it does not have
(`jax.experimental.topologies`). Interpret mode checks what a kernel
computes and `lowering_platforms=("tpu",)` what Pallas will lower; only this
shows what Mosaic refuses (tilings, layouts, casts, VMEM). It compiles and
never runs, so numerics on the chip stay `chip_smoke.py`'s to check.

It also shows what the device plane will call each kernel: the TPU compiler
names a Pallas custom call after the last scope in front of it, so the
compiled text holds `%ragged_paged_attention(.N)`, `%paged_kv_append(.N)`,
`%flash_fwd`, `%flash_bwd_dkv`, `%flash_bwd_dq`, which is what a reader of a
device capture matches (`benchmark/layer_metrics/kernel.*`).

Runs in a subprocess: the topology client is libtpu's, and nothing it does
should be able to take the test process down with it. Skips when libtpu
cannot describe the topology here.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r'''
import collections
import json
import os
import re
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:  # no libtpu, or it cannot start here
    print("NO_TOPOLOGY", type(e).__name__, e)
    raise SystemExit(0)
one = SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)


NAMES = {}     # case -> {custom call's instruction name, no .N: how many}
SCATTERS = {}  # case -> scatter instructions in the compiled text


def kernel_names(compiled):
    names = collections.Counter()
    for line in compiled.as_text().splitlines():
        if 'custom_call_target="tpu_custom_call"' in line:
            name = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", line).group(1)
            names[re.sub(r"\.\d+$", "", name)] += 1
    return dict(names)


def compile_for_v5e(fn, *args, case=None):
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)
    lowered = jitted.trace(*args).lower(lowering_platforms=("tpu",))
    n = lowered.as_text().count("tpu_custom_call")
    compiled = lowered.compile()   # the TPU compiler; raises on a refusal
    if case is not None:
        NAMES[case] = kernel_names(compiled)
        SCATTERS[case] = len(re.findall(r" scatter\(", compiled.as_text()))
        COMPILED[case] = compiled
    return n


COMPILED = {}  # case -> the compiled program


def leaf_facts(compiled, shape):
    """What a compiled program does with the array of `shape` (float32):
    copies of it, and the bytes aliased input to output."""
    relaid = re.compile(r"= f32\[%s\]\S* copy\(" % ",".join(map(str, shape)))
    return {"copies": len(relaid.findall(compiled.as_text())),
            "alias_bytes": compiled.memory_analysis().alias_size_in_bytes}


from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

# the serving shapes: gpt_1p3b heads, 16-token blocks, 128-slot tables
B, H, D, bs, nk, NB = 8, 16, 128, 16, 128, 64
for quant in (False, True):
    for width in (1, 5, 128):
        arena = sds((2, H, NB, bs, D), "int8" if quant else "bfloat16")
        args = [sds((B, width, H, D), "bfloat16"), arena, arena,
                sds((B, nk), "int32")] + [sds((B,), "int32")] * 3
        if quant:
            args += [sds((2, H, NB), "float32")] * 2

        def ragged(q, k, v, tables, q_start, kv_live, q_lens, *scales):
            kw = dict(zip(("k_scale", "v_scale"), scales))
            return ragged_paged_attention(
                q, k, v, 1, tables, q_start, kv_live, q_lens=q_lens,
                interpret=False, **kw)

        case = f"ragged-{'int8' if quant else 'bf16'}-w{width}"
        assert compile_for_v5e(ragged, *args, case=case) == 1, case

# the append's block writer, K and V in one call as the float step programs
# make it, at the same shapes; f32 is the tests' arena, int8 takes the
# quantized tokens as f32
from paddle_tpu.ops.pallas.paged_kv_append import paged_kv_append

for dt in ("bfloat16", "float32", "int8"):
    for width in (1, 5, 8, 128):
        arena = sds((2, H, NB, bs, D), dt)
        new = sds((B, width, H, D), "bfloat16" if dt == "bfloat16"
                  else "float32")
        targets = sds((B, width), "int32")

        def append(k, v, k_new, v_new, slots, offs):
            return paged_kv_append((k, v), 1, (k_new, v_new), slots, offs)

        case = f"append-{dt}-w{width}"
        assert compile_for_v5e(append, arena, arena, new, new, targets,
                               targets, case=case) == 1, case

# grouped-query attention at the hybrid configuration's sizes (PR 36): 32
# query heads over an arena of 2 K/V heads of 128, 32 rows; the walk fetches
# a K/V head once for its 16 query heads, the writer writes two heads
GB, GH, GKV = 32, 32, 2
for width in (1, 128):
    arena = sds((1, GKV, NB, bs, D), "bfloat16")
    args = [sds((GB, width, GH, D), "bfloat16"), arena, arena,
            sds((GB, nk), "int32")] + [sds((GB,), "int32")] * 3
    assert compile_for_v5e(ragged, *args, case=f"ragged-gqa-w{width}") == 1
    new = sds((GB, width, GKV, D), "bfloat16")
    targets = sds((GB, width), "int32")
    assert compile_for_v5e(append, arena, arena, new, new, targets, targets,
                           case=f"append-gqa-w{width}") == 1

# heads of 64: Mosaic pads the arena's minor dimension to the 128 lanes in
# HBM and then refuses the page copies' slices of it, which is why
# `paged_attention_arrays` keeps such a model on the XLA path
arena = sds((2, H, NB, bs, 64), "bfloat16")
args = [sds((B, 1, H, 64), "bfloat16"), arena, arena,
        sds((B, nk), "int32")] + [sds((B,), "int32")] * 3
try:
    compile_for_v5e(ragged, *args)
    print("HEAD64 compiles")
except Exception as e:
    print("HEAD64 refused: " + str(e).splitlines()[0][:300])

# the flash kernels at the training head sizes (the dispatch asks the
# default backend, which is the CPU here, so the gate is opened by hand)
fa._use_pallas = lambda: True
for shape in ((2, 1024, 12, 64), (1, 2048, 16, 128)):
    x = sds(shape, "bfloat16")

    def loss(q, k, v):
        o = fa.flash_attention_array(q, k, v, causal=True)
        return o.astype(jnp.float32).sum()

    grad = jax.grad(loss, argnums=(0, 1, 2))
    n = compile_for_v5e(grad, x, x, x, case=f"flash-grad-d{shape[3]}")
    assert n == 3, (shape, n)          # forward, dK/dV, dQ
# rematerialised, as a train step with `remat` calls it: other transforms
# stand around the kernels, and their names must not move
compile_for_v5e(jax.grad(jax.checkpoint(loss), argnums=(0, 1, 2)), x, x, x,
                case="flash-grad-remat")

# the engine's own step programs at a tiny depth: one ragged call a layer
# under the engine's `jit(step)`, and no Pallas call under another name
import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.serving import LLMEngine

pa.use_pallas = lambda: True
pa.interpret_mode = lambda: False
paddle.seed(0)
model = GPT(GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                      num_heads=2, max_seq_len=256, dropout=0.0))
engine = LLMEngine(model, block_size=16, max_batch=4, max_seq_len=256,
                   prefill_chunk=32)
like = lambda a: sds(a.shape, a.dtype)


def step_args(engine, W):
    """The step program's arguments (`LLMEngine.step_program_args`) as
    shapes on the described chip."""
    return jax.tree.map(like, engine.step_program_args(W))


B = engine.max_batch
for W in engine.width_buckets:
    compile_for_v5e(engine._get_step_fn(B, W), *step_args(engine, W),
                    case=f"engine-step-w{W}")

# heads of 64: the one rule (`paged_kernels_off`) keeps both the walk and the
# append on their XLA paths, so the program holds no kernel and the scatter
engine = LLMEngine(
    GPT(GPTConfig(vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
                  max_seq_len=256, dropout=0.0)),
    block_size=16, max_batch=4, max_seq_len=256, prefill_chunk=32)
compile_for_v5e(engine._get_step_fn(B, 1), *step_args(engine, 1),
                case="engine-step-head64-w1")

# a looped decoder (models/looped.py): two layers run twice inside one
# `lax.fori_loop`, the slab index traced. Both kernels take it as a
# prefetched scalar, so the program holds a layer's pair once, not once a pass
from paddle_tpu.models.looped import LoopedConfig, LoopedLM


def looped(hidden, heads):
    return LoopedLM(LoopedConfig(
        vocab_size=256, hidden_size=hidden, num_layers=2, num_heads=heads,
        head_dim=128, intermediate_size=2 * hidden, max_seq_len=256,
        loop_steps=2))


engine = LLMEngine(looped(256, 2), block_size=16, max_batch=4,
                   max_seq_len=256, prefill_chunk=32)
assert engine.pool.k.shape[0] == 4
for W in engine.width_buckets:
    compile_for_v5e(engine._get_step_fn(B, W), *step_args(engine, W),
                    case=f"looped-step-w{W}")

# the routed experts' grouped matmul alone, at the hybrid cell's four shapes
# (nemotron3s-ep4-serve: a decode step's 704 pair rows and a chunk-carrying
# step's 3,520 through `[1024, 2688]` and back, 128 held experts), with the
# tiles the kernel's file chooses: a whole expert matrix a block
from paddle_tpu.ops.pallas import grouped_matmul as gm

for rows in (704, 3520):
    for K, N in ((1024, 2688), (2688, 1024)):
        case = f"grouped-matmul-m{rows}-{K}x{N}"
        assert compile_for_v5e(
            gm.grouped_matmul_kernel, sds((rows, K), "bfloat16"),
            sds((128, K, N), "bfloat16"), sds((128,), "int32"),
            case=case) == 1, case

# the full layers' selection alone (ops/pallas/topk_select.py), at the dots3
# cell's two shapes: a chunk row's tile of 64 queries and the decode group's
# 32, each over a row's 17,408 slots, 2,048 chosen
from paddle_tpu.ops.pallas import topk_select as tsel

for rows in (64, 32):
    case = f"topk-select-q{rows}"
    assert compile_for_v5e(
        lambda s, q: tsel.topk_select_kernel(s, q, 2048),
        sds((rows, 17408), "float32"), sds((rows,), "int32"),
        case=case) == 1, case

# the decode group's Mamba state update alone (ops/pallas/ssm_state_update.py)
# at the hybrid cell's leaf, [5 layers, 32 lanes, 128 heads, 64, 128] float32
# with 8 groups: layer 2 read and written in place, the leaf aliased
from paddle_tpu.ops.pallas import ssm_state_update as su

STATE = {}     # case -> what the compiled program does with the state leaf
LEAF = (5, 32, 128, 64, 128)
assert compile_for_v5e(
    jax.jit(lambda S, x, B, C, dt, A, fresh, live: su.ssm_state_update(
        S, 2, x, B, C, dt, A, fresh, live), donate_argnums=0),
    sds(LEAF, "float32"), sds((32, 128, 64), "float32"),
    sds((32, 8, 128), "float32"), sds((32, 8, 128), "float32"),
    sds((32, 128), "float32"), sds((128,), "float32"), sds((32,), "bool"),
    sds((32,), "bool"), case="ssm-state-update-cell") == 1
STATE["ssm-state-update-cell"] = dict(
    leaf_facts(COMPILED["ssm-state-update-cell"], LEAF),
    leaf_bytes=4 * 5 * 32 * 128 * 64 * 128)

# the hybrid block (models/nemotron_h.py): two Mamba, one attention and one
# expert layer; the arena has the model's 2 K/V heads under 4 query heads,
# the pool's lane state rides beside it, the decode group's Mamba layers
# update it through `ssm_state_update`, and the expert layer's two grouped
# products (latent 128, expert 256: widths the kernel takes) are
# `grouped_matmul` kernels (the dispatches ask the default backend, the CPU
# here, so their gates are opened by hand as the attention's is above)
from paddle_tpu.models.nemotron_h import NemotronH, NemotronHConfig

gm.use_pallas = lambda: True
gm.interpret_mode = lambda: False
su.use_pallas = lambda: True
su.interpret_mode = lambda: False

engine = LLMEngine(
    NemotronH(NemotronHConfig(
        vocab_size=256, hidden_size=256, pattern="MM*E", num_heads=4,
        num_kv_heads=2, head_dim=128, mamba_num_heads=8, mamba_head_dim=64,
        ssm_state_size=128, n_groups=2, chunk_size=32, moe_latent_size=128,
        moe_intermediate_size=256, shared_intermediate_size=256,
        router_experts=32, experts_held=8, experts_per_tok=4,
        max_seq_len=256)),
    block_size=16, max_batch=4, max_seq_len=256, prefill_chunk=32)
assert engine.pool.k.shape[:2] == (1, 2)
leaf = engine.pool.lane_state["ssm"]
for W in engine.width_buckets:
    compile_for_v5e(engine._get_step_fn(B, W), *step_args(engine, W),
                    case=f"hybrid-step-w{W}")
    STATE[f"hybrid-step-w{W}"] = dict(
        leaf_facts(COMPILED[f"hybrid-step-w{W}"], leaf.shape),
        leaf_bytes=leaf.nbytes)
print("STATE_FACTS " + json.dumps(STATE))
COMPILED.clear()

# the arena through a step program: appended to and read by the kernel in
# one layout, so the compiler relays it nowhere. Heads of 128 and arenas of
# 128 MiB: a smaller one the compiler keeps in S(1) when it does relay it,
# and does not count among the temporaries
ARENA = {}     # case -> what the compiled program says of the arena
L, H, D, bs = 2, 4, 128, 16
model = GPT(GPTConfig(vocab_size=256, hidden_size=H * D, num_layers=L,
                      num_heads=H, max_seq_len=256, dropout=0.0))
# (the looped decoder carries the arenas through its loop over passes: there
# too they stay where they are)
for served, kv_dtype, arena_dtype in ((model, None, "float32"),
                                      (model, "int8", "int8"),
                                      (looped(H * D, H), None, "float32")):
    block_bytes = served.kv_slabs * H * bs * D \
        * jnp.dtype(arena_dtype).itemsize
    engine = LLMEngine(served, block_size=bs, max_batch=4, max_seq_len=256,
                       prefill_chunk=32, kv_dtype=kv_dtype,
                       num_blocks=(128 << 20) // block_bytes)
    k = engine.pool.k
    assert str(k.dtype) == arena_dtype and k.nbytes == 128 << 20, (
        k.dtype, k.shape)
    relaid = re.compile(r"= \w+\[%s\]\S* copy\(" % ",".join(map(str, k.shape)))
    kind = "looped-" if served is not model else ""
    for W in engine.width_buckets:
        compiled = engine._get_step_fn(engine.max_batch, W).trace(
            *step_args(engine, W)).lower(
                lowering_platforms=("tpu",)).compile()
        mem = compiled.memory_analysis()
        ARENA[f"{kind}{arena_dtype}-w{W}"] = {
            "arena_bytes": k.nbytes,
            "arena_copies": len(relaid.findall(compiled.as_text())),
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes}
print("ARENA_FACTS " + json.dumps(ARENA))
print("KERNEL_NAMES " + json.dumps(NAMES))
print("SCATTERS " + json.dumps(SCATTERS))
print("MOSAIC_OK")
'''

FLASH = {"flash_fwd": 1, "flash_bwd_dkv": 1, "flash_bwd_dq": 1}
# case -> the Pallas custom calls its compiled text must hold, by name
EXPECTED = {
    **{f"ragged-{dt}-w{w}": {"ragged_paged_attention": 1}
       for dt in ("bf16", "int8") for w in (1, 5, 128)},
    **{f"append-{dt}-w{w}": {"paged_kv_append": 1}
       for dt in ("bfloat16", "float32", "int8") for w in (1, 5, 8, 128)},
    "flash-grad-d64": FLASH,
    "flash-grad-d128": FLASH,
    "flash-grad-remat": FLASH,
    # gpt of two layers; LLMEngine's default width buckets: a layer's K and
    # V go in by one writer call and are read by one ragged call, for each
    # group of rows the program holds (w1: the decode group; w32: the chunk
    # group behind it, `LLMEngine.step_groups`)
    "engine-step-w1": {"paged_kv_append": 2, "ragged_paged_attention": 2},
    "engine-step-w32": {"paged_kv_append": 4, "ragged_paged_attention": 4},
    # heads of 64: no kernel at all (`paged_kernels_off`)
    "engine-step-head64-w1": {},
    # a looped decoder of two layers and two passes: the loop's body is
    # compiled once, so two pairs a group and not four
    "looped-step-w1": {"paged_kv_append": 2, "ragged_paged_attention": 2},
    "looped-step-w32": {"paged_kv_append": 4, "ragged_paged_attention": 4},
    # grouped-query attention: 32 query heads over 2 K/V heads of 128
    **{f"ragged-gqa-w{w}": {"ragged_paged_attention": 1} for w in (1, 128)},
    **{f"append-gqa-w{w}": {"paged_kv_append": 1} for w in (1, 128)},
    # the routed experts' grouped matmul at the hybrid cell's shapes
    **{f"grouped-matmul-m{m}-{k}x{n}": {"grouped_matmul": 1}
       for m in (704, 3520) for k, n in ((1024, 2688), (2688, 1024))},
    # the full layers' selection at the dots3 cell's shapes
    **{f"topk-select-q{q}": {"topk_select": 1} for q in (64, 32)},
    # the decode group's Mamba state update at the hybrid cell's leaf
    "ssm-state-update-cell": {"ssm_state_update": 1},
}


def test_hybrid_step_programs_hold_one_kernel_pair_a_group(aot):
    """The hybrid block's one attention layer: one writer call and one
    ragged call for each group of rows a program holds, and its one expert
    layer's two grouped products as `grouped_matmul` kernels over the
    program's whole token axis, under the names the readers match; the
    other custom calls of the program are the compiler's own (the router's
    top-k), and none of them a `ragged-dot`."""
    names = _facts(aot, "KERNEL_NAMES")
    for w, groups in ((1, 1), (32, 2)):
        got = names[f"hybrid-step-w{w}"]
        assert got.get("paged_kv_append") == groups, got
        assert got.get("ragged_paged_attention") == groups, got
        assert got.get("grouped_matmul") == 2, got
        # the decode group's two Mamba layers; the chunk group's rows
        # gather and scatter their lanes' state on the XLA path
        assert got.get("ssm_state_update") == 2, got
        assert not any("ragged-dot" in name for name in got), got


@pytest.mark.parametrize("case", ["ssm-state-update-cell", "hybrid-step-w1",
                                  "hybrid-step-w32"])
def test_the_state_leaf_is_updated_where_it_lies(aot, case):
    """The kernel writes the layer it updates into the leaf it was handed
    (aliased), and no program copies the leaf: not the kernel alone at the
    hybrid cell's shapes, not the step programs, in which the decode group's
    two Mamba layers update it one after the other and the chunk group's
    rows gather and scatter their own lanes. The step programs still alias
    the donated leaf to their outputs."""
    f = _facts(aot, "STATE_FACTS")[case]
    assert f["copies"] == 0, f
    assert f["alias_bytes"] >= f["leaf_bytes"], f


@pytest.fixture(scope="module")
def aot():
    """The script's output, run once for the module."""
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here: "
                    + proc.stdout.strip().splitlines()[-1][:200])
    return proc


def _facts(aot, tag):
    line = next((ln for ln in aot.stdout.splitlines()
                 if ln.startswith(tag + " ")), None)
    assert line is not None, aot.stdout[-2000:] + aot.stderr[-4000:]
    return json.loads(line[len(tag) + 1:])


def test_attention_kernels_compile_for_v5e(aot):
    assert aot.returncode == 0 and "MOSAIC_OK" in aot.stdout, (
        aot.stdout[-2000:] + aot.stderr[-4000:])


def test_mosaic_still_refuses_the_walk_at_head_size_64(aot):
    """The reason for the dispatch's head-size condition
    (`paged_attention_arrays`): when this compiles, the condition can go."""
    line = next(ln for ln in aot.stdout.splitlines()
                if ln.startswith("HEAD64 "))
    assert line.startswith("HEAD64 refused: ") and "aligned to tiling" \
        in line, line


@pytest.mark.parametrize("case", sorted(EXPECTED))
def test_compiled_text_names_each_kernel(aot, case):
    """One name a kernel, whatever the width, the arena's dtype or the
    transforms around the call, and no Pallas call under another name."""
    assert _facts(aot, "KERNEL_NAMES")[case] == EXPECTED[case]


@pytest.mark.parametrize("case,scatters", [
    ("engine-step-w1", 0), ("engine-step-w32", 0),
    ("engine-step-head64-w1", 4)])
def test_one_append_ships_a_program(aot, case, scatters):
    """With heads of 128 the compiled step program holds the writer and no
    scatter at all; with heads of 64 it keeps the row scatter, K's and V's
    a layer, and holds no kernel (`test_compiled_text_names_each_kernel`)."""
    assert _facts(aot, "SCATTERS")[case] == scatters


@pytest.mark.parametrize("case", ["float32-w1", "float32-w32",
                                  "int8-w1", "int8-w32",
                                  "looped-float32-w1", "looped-float32-w32"])
def test_step_program_keeps_the_arena_in_one_layout(aot, case):
    """The append (`serving/block_pool.py _append`: the Pallas block writer
    here, its arenas aliased; the row scatter before PR 33) addresses the
    arena as the ragged kernel does, so the compiled step program holds no
    copy of an arena, needs less than one arena of temporaries, and still
    aliases the donated arenas to its outputs. With the head in the
    scatter's window (PR 24 and before) it held 6 to 8 copies and up to
    three arenas of temporaries at this size, 9 GiB beside the benchmark's
    6 GiB."""
    f = _facts(aot, "ARENA_FACTS")[case]
    assert f["arena_copies"] == 0, f
    assert f["temp_bytes"] < f["arena_bytes"], f
    assert f["alias_bytes"] >= 2 * f["arena_bytes"], f
