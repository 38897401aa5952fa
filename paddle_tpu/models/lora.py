"""Many-adapter LoRA serving over one shared base GPT.

One base model, N per-request low-rank adapters, ONE compiled program per
ragged width bucket — the multi-tenant counterpart to the scheduling
policy (serving/policy.py). The design constraints, in engine terms:

- **Adapter weights are an extra ``[num_slots, ...]`` tree next to the
  base params.** Each column-parallel target op (the fused QKV and the
  FFN up-projection — where LoRA deltas live in practice) gets a pair of
  stacked tables: ``A [S, L, in, r]`` replicated and ``B [S, L, r, out]``
  sharded on 'tp' along the SAME out axis as the base weight it rides
  (serving/sharded.py), so the per-row delta lands in the base output's
  exact layout and adds locally — the adapter path introduces ZERO new
  collectives at any tp degree (analysis contract IR001 covers the
  adapter-gather program variant).

- **Slot 0 is the base model.** Both tables are all-zeros there, so a
  lane whose request carries no adapter computes ``x@A@B == 0`` and the
  engine with adapters enabled is numerically the base engine for plain
  requests. Idle/padded lanes also read slot 0.

- **Per-row gather INSIDE the step program.** The engine marshals one
  ``adapter_slots [B] int32`` host input per step (exactly like
  ``q_lens``) and the trace gathers each lane's adapter rows from the
  stacked tables (`gather_adapter_rows`). Shapes depend only on
  ``(max_batch, width)`` — which adapters a step mixes never keys a
  program, so ``expected_program_count()`` is unchanged and the
  recompile sentinel stays quiet. Hoisting the gather OUT of the program
  (host-indexing the tables per step) would put a [B, L, in, r]
  device-put on every step's critical path — the IR005 seeded trip test
  proves hlolint catches that rewrite.

- **KV is adapter-dependent.** A sequence's K/V was computed THROUGH its
  adapter, so the same prompt under different adapters must never share
  prefix-cache blocks: the engine salts `chain_block_hashes` with the
  request's adapter name (serving/block_pool.py).

The engine-side registry (`LLMEngine.load_adapter` / `unload_adapter`,
bounded ``lora_slots``, LRU eviction of idle adapters) owns slot
assignment; this module owns the math and the table layout. Token
identity is tested against `merge_adapter_into` — folding ``W + A@B``
into a dedicated per-adapter engine's base weights must reproduce the
multi-adapter engine's greedy tokens exactly.
"""
from __future__ import annotations

import numpy as np

# Column-parallel serving ops that accept adapters, by the op names
# models/gpt.py hands to its cache's `column_parallel`
# (serving/block_pool.py PagedLayerView). Row-parallel
# ops are deliberately NOT targets: their tp-sharded INPUT would force
# the A-projection to reduce over a sharded axis (a psum per layer per
# adapter — exactly the collective creep IR001 exists to forbid).
LORA_TARGETS = ("attn_qkv", "ffn_fc1")


def target_dims(cfg, target):
    """(d_in, d_out) of a target op's base weight ([in, out] orientation,
    mp_layers.ColumnParallelLinear)."""
    if target == "attn_qkv":
        return cfg.hidden_size, 3 * cfg.hidden_size
    if target == "ffn_fc1":
        return cfg.hidden_size, cfg.intermediate_size
    raise ValueError(f"unknown LoRA target {target!r} "
                     f"(supported: {LORA_TARGETS})")


def init_adapter_tables(cfg, num_slots, rank, targets=LORA_TARGETS,
                        smesh=None):
    """Zeroed stacked adapter tables for an engine with ``num_slots``
    slots (slot 0 = the all-zeros base): {target: (A [S, L, in, r],
    B [S, L, r, out])}. On a serving mesh, A is replicated and B is
    sharded on its out axis over 'tp' — the base column weight's layout,
    stacked."""
    import jax
    import jax.numpy as jnp

    tables = {}
    for t in targets:
        d_in, d_out = target_dims(cfg, t)
        a = jnp.zeros((num_slots, cfg.num_layers, d_in, rank), jnp.float32)
        b = jnp.zeros((num_slots, cfg.num_layers, rank, d_out), jnp.float32)
        if smesh is not None:
            if d_out % smesh.tp_degree:
                raise ValueError(
                    f"LoRA target {t!r}: out dim {d_out} not divisible by "
                    f"tp degree {smesh.tp_degree}")
            a = jax.device_put(a, smesh.replicated())
            b = jax.device_put(b, smesh.named(None, None, None, "tp"))
        tables[t] = (a, b)
    return tables


def table_shardings(targets, smesh):
    """The tables' NamedShardings in `init_adapter_tables` layout — what
    the engine pins the lora pytree to in the step jit's in_shardings."""
    rep = smesh.replicated()
    col = smesh.named(None, None, None, "tp")
    return {t: (rep, col) for t in targets}


def pack_adapter(cfg, weights, rank, targets, alpha=None):
    """Validate + normalize one adapter's host weights for a table slot.

    `weights` maps each target (a subset of `targets` is fine — missing
    targets stay zero) to ``(A [L, in, r'], B [L, r', out])`` with
    ``r' <= rank``; narrower adapters are zero-padded up to the table
    rank (zero rows/cols contribute nothing). The conventional
    ``alpha / r'`` LoRA scale is folded into B here — the serving path
    never multiplies by a per-request scalar."""
    packed = {}
    for t, (a, b) in weights.items():
        if t not in targets:
            raise ValueError(
                f"adapter target {t!r} not enabled on this engine "
                f"(lora_targets={tuple(targets)})")
        d_in, d_out = target_dims(cfg, t)
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        r = a.shape[-1]
        if a.shape != (cfg.num_layers, d_in, r):
            raise ValueError(
                f"adapter {t!r} A shape {a.shape} != "
                f"({cfg.num_layers}, {d_in}, r)")
        if b.shape != (cfg.num_layers, r, d_out):
            raise ValueError(
                f"adapter {t!r} B shape {b.shape} != "
                f"({cfg.num_layers}, r, {d_out})")
        if r > rank:
            raise ValueError(
                f"adapter {t!r} rank {r} exceeds the engine's table "
                f"rank {rank}")
        if alpha is not None:
            b = b * (float(alpha) / r)
        if r < rank:
            a = np.concatenate(
                [a, np.zeros((cfg.num_layers, d_in, rank - r), np.float32)],
                axis=-1)
            b = np.concatenate(
                [b, np.zeros((cfg.num_layers, rank - r, d_out), np.float32)],
                axis=1)
        packed[t] = (a, b)
    if not packed:
        raise ValueError("adapter has no target weights")
    return packed


def write_slot(tables, slot, packed, zero_missing=True):
    """Return tables with `slot` holding `packed` (targets absent from
    `packed` are zeroed when `zero_missing`). Out-of-jit functional
    update — sharded operands keep their placement; the copy is per-load,
    never per-step."""
    out = {}
    for t, (a, b) in tables.items():
        if t in packed:
            pa, pb = packed[t]
            a = a.at[slot].set(pa)
            b = b.at[slot].set(pb)
        elif zero_missing:
            a = a.at[slot].set(0.0)
            b = b.at[slot].set(0.0)
        out[t] = (a, b)
    return out


def zero_slot(tables, slot):
    """Tables with `slot` zeroed (unload hygiene: a freed slot holds no
    stale weights even though no live request can index it)."""
    return write_slot(tables, slot, {}, zero_missing=True)


def gather_adapter_rows(tables, slots):
    """Per-lane adapter rows, gathered INSIDE the step trace:
    {target: (a_rows [B, L, in, r], b_rows [B, L, r, out])}. ``slots``
    is the step's host-marshalled ``adapter_slots [B] int32`` (0 = base
    = zeros). Returns None for empty tables so the lora-off engine
    traces the identical program it always has."""
    if not tables:
        return None
    import jax.numpy as jnp

    return {t: (jnp.take(a, slots, axis=0), jnp.take(b, slots, axis=0))
            for t, (a, b) in tables.items()}


def apply_adapter_rows(x, a_rows, b_rows, layer):
    """One layer's per-lane LoRA delta for a column-parallel op:
    ``delta[i] = x[i] @ A[slot_i, layer] @ B[slot_i, layer]`` batched
    over lanes. x [B, S, in] replicated; the result inherits B's out-axis
    'tp' sharding — the base op's exact output layout, added locally."""
    import jax.numpy as jnp

    a = a_rows[:, layer]     # [B, in, r]
    b = b_rows[:, layer]     # [B, r, out]
    h = jnp.einsum("bsi,bir->bsr", x, a,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("bsr,bro->bso", h.astype(x.dtype), b)


def random_adapter(cfg, rank, targets=LORA_TARGETS, seed=0, scale=0.05):
    """A reproducible nonzero test adapter (both factors random — unlike
    training init, tests want a delta that actually moves logits):
    {target: (A [L, in, r], B [L, r, out])} float32 host arrays."""
    rs = np.random.RandomState(seed)
    out = {}
    for t in targets:
        d_in, d_out = target_dims(cfg, t)
        out[t] = (
            rs.normal(0.0, scale, (cfg.num_layers, d_in, rank))
            .astype(np.float32),
            rs.normal(0.0, scale, (cfg.num_layers, rank, d_out))
            .astype(np.float32),
        )
    return out


def _target_layer(model, target, layer):
    blk = model.blocks[layer]
    if target == "attn_qkv":
        return blk.attn.qkv
    if target == "ffn_fc1":
        return blk.fc1
    raise ValueError(f"unknown LoRA target {target!r}")


def merge_adapter_into(model, weights, alpha=None):
    """Fold an adapter into a model's base weights IN PLACE:
    ``W_l += A_l @ B_l`` per target per layer (alpha folded like
    `pack_adapter`). This is the token-identity reference — an engine
    over the merged model must emit exactly what the multi-adapter
    engine emits for requests on this adapter. Merge BEFORE building an
    engine (engines snapshot params at construction)."""
    import jax.numpy as jnp

    cfg = model.cfg
    for t, (a, b) in weights.items():
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if alpha is not None:
            b = b * (float(alpha) / a.shape[-1])
        for layer in range(cfg.num_layers):
            w = _target_layer(model, t, layer).weight
            delta = jnp.asarray(a[layer] @ b[layer], w._array.dtype)
            w._array = w._array + delta
    return model
