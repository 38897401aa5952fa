"""Tensor-parallel serving: the NamedSharding mesh layer under LLMEngine.

Single-chip serving caps the model at one chip's HBM and one chip's FLOPs.
This module makes the whole serving subsystem mesh-native (ROADMAP item 1,
the Gemma-on-TPU comparison's standard TP recipe): GPT weights and the
paged KV arena shard over a ``tp`` mesh axis while every scheduling
decision — block tables, prefix cache, refcounts, admission, preemption —
stays host-side and byte-identical to the single-chip engine. Build a
mesh with `build_serving_mesh` (or just pass ``mesh=2`` to `LLMEngine`)
and the engine's unified ragged step program becomes mesh-aware at every
width bucket with the same ``(B, width)`` keying.

The tp layout (the Megatron partitioning the training side already
encodes in ``Parameter.sharding_axes``, here renamed onto the serving
axis — `serving_param_specs` is `spmd.module_param_specs` with ``mp`` →
``tp``):

====================  =========================  ========================
tensor                 shape                      PartitionSpec
====================  =========================  ========================
wte (vocab embed)      [vocab, hidden]            P('tp', None)
attn qkv weight        [hidden, 3*hidden]         P(None, 'tp')  (heads)
attn proj weight       [hidden, hidden]           P('tp', None)  (+psum)
ffn fc1 weight         [hidden, 4*hidden]         P(None, 'tp')  (columns)
ffn fc2 weight         [4*hidden, hidden]         P('tp', None)  (+psum)
layernorms, wpe        (small)                    P()  (replicated)
KV arena k/v           [layers, heads, blocks,    P(None, 'tp')
                        block_size, head_dim]      (head-major shard)
step metadata/tokens   block tables, slots, ids…  P()  (replicated)
====================  =========================  ========================

Head-sharding the arena is what the PR 2 head-major layout was for: each
chip owns a contiguous ``[layers, heads/tp, blocks, block_size,
head_dim]`` slab, scatters only its own heads' K/V, and attends its own
heads. The fused QKV projection is per-head-grouped (models/gpt.py), so a
contiguous tp shard of its columns IS a head group and the q/k/v split
costs no realignment; the dominant cross-chip traffic in a step is the tp
all-reduce on the attention/FFN output projections (kept explicit so
EQuARX-style quantized collectives can slot in later), plus the sampled
positions' logit gather at the program boundary. The Pallas ragged kernel is single-device
by construction; on a mesh the dispatch (ops/pallas/paged_attention.py
`ragged_paged_attention_sharded`) runs it per-shard via `shard_map` over
the head axis (each shard sees its local head slice of the arena), with
the XLA padded-gather path as the GSPMD-partitioned fallback everywhere
else.

Donation of the sharded arenas routes through
`parallel.spmd.mesh_donate_argnums` (the JL004 gate): the XLA-CPU
host-platform mesh miscompiles donated sharded buffers (outputs alias
freed inputs), so donation stays off on the cpu backend and on for real
accelerators.

Single-chip parity guarantee: a tp-sharded serve is token-for-token
identical to the single-chip engine on the same model — greedy AND
temperature>0 sampling (same PRNG key, same tokens): sampling runs
inside the compiled step on logit rows pinned replicated at the program
boundary, so every sampler reduction sees the same replicated values on
every chip. The mesh changes WHERE flops run, never which tokens come
out (tests/test_serving_sharded.py locks both on the 8-fake-device CPU
mesh, prefix-cache hits and speculative decoding included).

Weight placement has two paths. The eager path places SHARDED COPIES of
the model's weights (`jax.device_put` per `serving_param_specs`) and
serves from those; the caller's eager model keeps its own single-device
arrays — the engine does not mutate state it does not own (test fixtures
share one model across sharded and reference engines) — so the caller
transiently holds one full replica. For a model too large for that, use
the checkpoint-streaming recipe (distributed/checkpoint.py
`stream_load_state`, README "Elastic fleet"): build the model under
``nn.layer.skeleton_init()`` (shapes only, no arrays), then
``LLMEngine(model, mesh=N, checkpoint_path=ckpt_dir)`` streams each
leaf's shards straight from the `save_sharded_model` directory to mesh
placement — peak host memory is one shard slice and each chip only ever
holds its own shards, so the full tree is never materialized anywhere
(``LLMEngine(param_hbm_bytes=...)`` turns that bound into a construction
-time assertion; tests/test_stream_checkpoint.py proves the eager path
busts the same budget the streamed path meets).
"""
from __future__ import annotations

import numpy as np


class ServingMesh:
    """The serving topology handle threaded through engine, pool, and the
    paged-attention dispatch: a `jax.sharding.Mesh` whose ``tp`` axis
    shards attention heads / FFN columns / the KV arena's head axis.
    Construct via `build_serving_mesh` (or pass an int/Mesh to
    `LLMEngine(mesh=...)`, which lands here through `as_serving_mesh`)."""

    TP_AXIS = "tp"

    def __init__(self, mesh):
        self.mesh = mesh
        if self.TP_AXIS not in mesh.shape:
            raise ValueError(
                f"serving mesh needs a '{self.TP_AXIS}' axis; got axes "
                f"{tuple(mesh.shape)}"
            )

    @property
    def tp_degree(self):
        return int(self.mesh.shape[self.TP_AXIS])

    @property
    def device_count(self):
        return int(self.mesh.devices.size)

    @property
    def backend(self):
        return self.mesh.devices.flat[0].platform

    def named(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec

        return NamedSharding(self.mesh, PartitionSpec(*spec))

    def replicated(self):
        return self.named()

    def arena_sharding(self):
        """The head-major KV arena ``[layers, heads, blocks, block_size,
        head_dim]`` shards its HEAD axis over tp — each chip owns
        ``heads/tp`` full head slabs, so the ragged kernel's per-(head,
        block) tiles never straddle chips."""
        return self.named(None, self.TP_AXIS)

    def tp_head_ranges(self, num_heads):
        """The arena head axis cut into per-shard ``(h0, h1)`` ranges, in
        tp shard order — the host-tier slab layout (serving/kv_tier.py):
        one host slab per range, filled from each chip's own addressable
        shard so the save path never gathers across chips."""
        tp = self.tp_degree
        if num_heads % tp:
            raise ValueError(
                f"tp_degree {tp} does not divide num_heads {num_heads}")
        per = num_heads // tp
        return [(i * per, (i + 1) * per) for i in range(tp)]

    def validate_model(self, cfg, kv_heads=None):
        """Reject a model the tp degree cannot shard evenly: attention
        heads, FFN columns, and the (vocab-parallel) embedding rows must
        all divide, or GSPMD would silently pad — and the head-sharded
        arena would not tile. One loud error at engine construction.
        `kv_heads` is the arena's head count (the engine's; the model's
        query heads when None): a tp degree above it leaves chips with no
        head of the cache to hold."""
        tp = self.tp_degree
        for name, dim in (("num_heads", cfg.num_heads),
                          ("intermediate_size", cfg.intermediate_size),
                          ("vocab_size", cfg.vocab_size)):
            if dim % tp:
                raise ValueError(
                    f"tp_degree {tp} does not divide {name} {dim} — pick "
                    "a tp degree that divides the head/FFN/vocab dims"
                )
        kv_heads = cfg.num_heads if kv_heads is None else int(kv_heads)
        if tp > kv_heads or kv_heads % tp:
            raise ValueError(
                f"tp_degree {tp} cannot shard a KV arena of {kv_heads} "
                "key/value heads: the arena is sharded by head, so the tp "
                "degree must divide the model's kv_heads and not exceed it")

    def info(self):
        """Topology facts for /healthz and the mesh gauges."""
        return {"tp_degree": self.tp_degree,
                "device_count": self.device_count,
                "backend": self.backend}


def build_serving_mesh(tp_degree, devices=None):
    """A 1-D ``('tp',)`` mesh over the first `tp_degree` devices. On the
    8-fake-device CPU host platform (tests/_cpu_mesh.py) this is how the
    tp=2/tp=4 parity harnesses get their mesh without TPUs."""
    import jax
    from jax.sharding import Mesh

    tp = int(tp_degree)
    if tp < 2:
        raise ValueError("build_serving_mesh needs tp_degree >= 2 "
                         "(single-chip engines pass mesh=None)")
    devices = list(devices if devices is not None else jax.devices())
    if tp > len(devices):
        raise ValueError(
            f"tp_degree {tp} needs {tp} devices, have {len(devices)}"
        )
    return ServingMesh(Mesh(np.asarray(devices[:tp]), (ServingMesh.TP_AXIS,)))


def as_serving_mesh(mesh):
    """Coerce `LLMEngine(mesh=...)`'s accepted forms — ServingMesh,
    jax Mesh (must carry a tp axis), or int tp degree — to a ServingMesh.
    Any form that resolves to tp degree <= 1 coerces to None: ``mesh=1``
    (or a 1-device Mesh) is the single-chip engine, and degree 1 must
    take the true single-chip path — the sharded engine would otherwise
    disable donation for nothing."""
    if mesh is None:
        return mesh
    if isinstance(mesh, (int, np.integer)):
        return None if int(mesh) <= 1 else build_serving_mesh(int(mesh))
    smesh = mesh if isinstance(mesh, ServingMesh) else ServingMesh(mesh)
    return None if smesh.tp_degree <= 1 else smesh


def serving_param_specs(model, smesh):
    """Per-parameter PartitionSpecs for the serving mesh: the model's own
    ``Parameter.sharding_axes`` Megatron layout (mp_layers.py annotates
    ColumnParallel out-dims, RowParallel in-dims, and the vocab embedding)
    renamed onto the serving ``tp`` axis — the `spmd.module_param_specs`
    pattern, minus the training-only ZeRO branches. Unannotated tensors
    (layernorms, wpe, RowParallel biases) replicate."""
    from jax.sharding import PartitionSpec as P

    tp = smesh.TP_AXIS
    specs = {}
    for name, p in model.named_parameters_dict().items():
        axes = getattr(p, "sharding_axes", None)
        spec = [tp if a == "mp" else None for a in axes] if axes else []
        specs[name] = P(*spec) if any(spec) else P()
    return specs


def serving_collective_budget(cfg, tp_degree, quant_collectives=()):
    """EXACT expected collective counts in ONE compiled serving step at
    this tp degree — the layout table above, stated as arithmetic, and
    the IR collective-budget contract's input (analysis/contracts.py
    IR001, gated in tier-1 by tests/test_ir_contracts.py):

    - ``all-reduce``: one per F32 RowParallel output projection (attn
      proj + ffn fc2 = 2 per layer, minus any in `quant_collectives`)
      plus one for the vocab-parallel embedding's masked-lookup psum ->
      ``(2 - n_quant) * num_layers + 1``;
    - ``all-gather``: ONE sampler-boundary gather that materializes the
      sampled positions' full vocab rows replicated (engine.py pins it
      with a sharding constraint so no other sampler reduction pays its
      own collective) — plus, per EQuARX-quantized projection in
      `quant_collectives` (``"attn_proj"`` / ``"ffn_fc2"``), TWO
      all-gathers per layer: the int8 partial-sum payload and its f32
      per-shard scale (models/gpt.py routes the op through
      `quantized_row_parallel` instead of the psum'd f32 matmul) ->
      ``2 * n_quant * num_layers + 1``. An f32 all-reduce sneaking back
      into a quantized op, or a quantized gather appearing unrequested,
      moves BOTH counts and trips IR001;
    - everything else (``all-to-all``, ``reduce-scatter``, ...): zero.
      The head-major arena + per-head-grouped fused QKV exist precisely
      so the attention path needs NO re-gather of the sharded axis; a
      qkv-major regroup (the pre-PR-10 layout) adds per-layer gathers
      and must trip the budget.

    Single-chip programs (tp<=1) budget zero collectives of any kind."""
    if int(tp_degree) <= 1:
        return {"all-reduce": 0, "all-gather": 0, "all-to-all": 0,
                "reduce-scatter": 0, "collective-permute": 0,
                "collective-broadcast": 0}
    n_quant = len(set(quant_collectives) & {"attn_proj", "ffn_fc2"})
    L = int(cfg.num_layers)
    return {"all-reduce": (2 - n_quant) * L + 1,
            "all-gather": 2 * n_quant * L + 1,
            "all-to-all": 0, "reduce-scatter": 0, "collective-permute": 0,
            "collective-broadcast": 0}


def kv_capacity_blocks(kv_bytes, num_layers, num_heads, block_size,
                       head_dim, dtype_itemsize, tp_degree=1,
                       scale_itemsize=0):
    """KV blocks a PER-CHIP byte budget buys. `num_layers` is the arena's
    leading axis, the model's `kv_slabs`: a GPT's layers, a looped model's
    (pass, layer) pairs, so a block of it costs that many times a layer's
    block. The arena is head-sharded
    over tp, so one chip stores ``num_heads / tp_degree`` heads per block
    — the same budget holds ``tp_degree``x the blocks of the naive
    logical-head-count formula. Admission (`LLMEngine.validate`, and the
    frontend's ``max_kv_commit_blocks`` gate that reuses it) must reject
    against what one shard can actually hold, which is THIS number, so
    every capacity derivation funnels here. `dtype_itemsize` is the
    ACTIVE kv dtype's (1 for the int8 arena — the ~2x block count the
    quantized pool admits flows from here into admission, the router
    bench, and the gauges); a quantized arena also pays `scale_itemsize`
    (4, f32) for the two per-(layer, head) scale sidecar columns each
    block carries. Returns the raw block count (possibly 0/1) — the
    engine rejects an unusably small budget loudly at construction
    rather than booting a replica that 4xxes every request."""
    local_heads = -(-int(num_heads) // max(1, int(tp_degree)))
    per_block = (2 * int(num_layers) * local_heads * int(block_size)
                 * int(head_dim) * int(dtype_itemsize)
                 + 2 * int(num_layers) * local_heads * int(scale_itemsize))
    return int(kv_bytes) // per_block


def quantized_row_parallel(x, w, bias, mesh, tp_axis=ServingMesh.TP_AXIS):
    """EQuARX-style quantized RowParallel projection: the tp output
    collective moves int8, not f32.

    The f32 path lets GSPMD insert one all-reduce over the per-shard
    partial sums of ``x @ w`` (w sharded on its IN dim). That collective
    is the dominant cross-chip traffic of every decode step, and its
    payload tolerates quantization well because each shard's partial sum
    is a dense activation with a narrow dynamic range per step. Following
    EQuARX (arXiv:2506.17615) each shard:

    1. computes its local f32 partial sum ``[.., hidden]``,
    2. quantizes it with ONE per-shard scalar scale (absmax/127),
    3. all-gathers the int8 payload + f32 scale over ``tp``
       (2 gathers — the shapes IR001 locks via
       `serving_collective_budget(quant_collectives=...)`),
    4. dequantizes and sums the tp partials in f32.

    The reduction itself stays f32 — only the wire format is int8, so
    error does not compound across shards (each partial is quantized
    once). The replicated bias adds AFTER the summed dequant, outside
    the quantization, exactly like the f32 path. ~4x less collective
    traffic for one extra rounding per shard partial.

    x: [.., in] activations (feature axis tp-sharded or replicated — the
    in_spec slices either); w: [in, out] tp-sharded on in; bias: [out]
    replicated or None; `mesh` the raw ``jax.sharding.Mesh`` (what
    ``PagedState.mesh`` carries inside the traced step). Returns
    replicated [.., out] f32. Gated per-op by
    ``LLMEngine(quant_allreduce=...)`` -> ``PagedState.quant_collectives``
    (models/gpt.py hooks)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from ..parallel._compat import shard_map
    from ..parallel.collectives import quantized_allgather_sum

    tp = tp_axis

    def local(xs, ws):
        part = jax.lax.dot_general(
            xs.astype(jnp.float32), ws.astype(jnp.float32),
            (((xs.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # 2 all-gathers (int8 payload + f32 scale) — the shapes IR001
        # locks via `serving_collective_budget(quant_collectives=...)`.
        return quantized_allgather_sum(part, tp)

    in_spec_x = P(*([None] * (x.ndim - 1) + [tp]))
    fn = shard_map(local, mesh=mesh,
                   in_specs=(in_spec_x, P(tp, None)), out_specs=P())
    out = fn(x, w)
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


# The per-shard Pallas dispatch (shard_map over the head axis) lives next
# to the kernel it wraps: ops/pallas/paged_attention.py
# `ragged_paged_attention_sharded`, selected by `paged_attention_arrays`
# whenever the threaded-through PagedState carries a mesh.
