"""Which append a step program holds (`serving/block_pool.py _append`).

Off the chip, without the interpret knob, every lowered step program is
textually the one the row scatter alone gave (PR 32's `_append`, written
out below), so `ir_baseline.json` stands and nothing counts as a fall-back;
with the knob every program goes through the Pallas writer, once a layer
(K and V together) or twice (an int8 arena: each payload after its own
requantization) for each group of rows the program holds, and through the
scatter nowhere. The six set-ups are PR
32's witness. On a tpu backend the compiled programs are
`tests/test_mosaic_aot.py`'s to read.
"""
import pytest

import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.ops.pallas import _backend
from paddle_tpu.ops.pallas import paged_kv_append as writer_module
from paddle_tpu.serving import LLMEngine, block_pool

LAYERS = 2
SETUPS = {
    "default": {},
    "int8": dict(kv_dtype="int8"),
    "tp2-quant-allreduce": dict(mesh=2, quant_allreduce=True),
    "lora": dict(lora_slots=2),
    "all-four": dict(kv_dtype="int8", mesh=2, quant_allreduce=True,
                     lora_slots=2),
    "spec": dict(spec_decoding=True),
}


def _scatter_only(arenas, layer, news, slots, offs, mesh):
    """PR 32's `_append`, an arena a call."""
    def write(arena, new, slots, offs):
        heads = jnp.arange(arena.shape[1], dtype=jnp.int32)
        return arena.at[layer, heads, slots[..., None], offs[..., None]].set(
            new.astype(arena.dtype))

    if mesh is not None and mesh.shape.get("tp", 1) > 1:
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.parallel._compat import shard_map

        write = shard_map(
            write, mesh=mesh,
            in_specs=(P(None, "tp"), P(None, None, "tp", None), P(), P()),
            out_specs=P(None, "tp"))
    return tuple(write(arena, new, slots, offs)
                 for arena, new in zip(arenas, news))


def _engine(setup):
    paddle.seed(0)
    model = GPT(GPTConfig(vocab_size=64, hidden_size=32, num_layers=LAYERS,
                          num_heads=2, max_seq_len=64, dropout=0.0))
    return LLMEngine(model, block_size=4, max_batch=2, max_seq_len=64,
                     prefill_chunk=8, **SETUPS[setup])


def _programs(setup):
    return _engine(setup).lowered_step_programs()


def _groups(engine):
    """How many groups of rows each of the engine's programs holds (`w1`:
    the decode group alone; the chunk's width: the chunk group behind it):
    the append and the attention run once a group."""
    return {name: len(engine.step_groups(W))
            for name, (_, W) in engine.step_program_shapes().items()}


@pytest.mark.parametrize("setup", sorted(SETUPS))
def test_append_dispatch(monkeypatch, setup):
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.delenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", raising=False)
    _backend.XLA_FALLBACKS.clear()
    calls = []

    def spy(arenas, *a, **kw):
        # the dispatch is what is looked at here, so the kernel itself
        # (tests/test_paged_kv_append.py) is left out of the lowering
        calls.append(len(arenas))
        return tuple(arenas)

    monkeypatch.setattr(writer_module, "paged_kv_append", spy)

    shipped = {k: low.as_text() for k, low in _programs(setup).items()}
    assert not calls and not _backend.XLA_FALLBACKS
    with monkeypatch.context() as m:
        m.setattr(block_pool, "_append", _scatter_only)
        parents = {k: low.as_text() for k, low in _programs(setup).items()}
    assert sorted(shipped) == sorted(parents) and len(shipped) >= 2
    for name in shipped:
        assert shipped[name] == parents[name], name

    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")
    with monkeypatch.context() as m:
        # the scatter nowhere: its fall-back would be counted here
        m.setattr(_backend, "note_xla_fallback",
                  lambda *a: calls.append("xla"))
        engine = _engine(setup)
        programs = engine.lowered_step_programs()
    int8 = "kv_dtype" in SETUPS[setup]
    groups = _groups(engine)
    assert sorted(groups) == sorted(programs) and groups["w1"] == 1 \
        and groups["w8"] == 2
    assert calls == ([1, 1] if int8 else [2]) * LAYERS * sum(groups.values())
    assert not _backend.XLA_FALLBACKS
