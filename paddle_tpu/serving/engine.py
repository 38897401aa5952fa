"""LLMEngine: continuous-batching generation over the paged KV cache.

`add_request` enqueues, `step` runs ONE mixed device step (decode rows plus
chunked-prefill rows, planned by the scheduler), `stream` yields a request's
tokens as they land. The whole serve compiles ONE kind-free ragged step
program — the scheduler's mixed plan is the only program shape — keyed by
``(max_batch, width)`` where ``width`` is drawn from a small set of
**ragged width buckets** (`expected_program_count` is their count):

- every planned row is ragged: a decode row feeds its 1 pending token, a
  prefill row its next ``<= prefill_chunk``-token chunk, a speculative row
  its pending token plus up to ``num_spec_tokens`` prompt-lookup drafted
  candidates (serving/spec.py), padding walks the null block. The step's
  compiled width is the smallest bucket covering its widest row — by
  default ``{1, 1 + num_spec_tokens (spec engines), prefill_chunk}``, so
  the dominant all-decode steps run at width 1 and never pay chunk-width
  compute;
- **a step's tokens lie on one flat axis made of groups** (`step_groups`):
  the decode group ``[max_batch, 1 + K]`` (K the draft capacity) holds the
  rows that feed one token and their drafts, and a bucket wider than that
  is the width of the chunk group ``[C, W]`` behind it, ``C = token_budget
  // prefill_chunk`` rows. What is a function of one token runs once over
  the whole axis; attention, the append and whatever else mixes along a
  sequence run a group at a time through the same kernels
  (block_pool.py `PagedState`). So a step that carries a prompt chunk
  computes the chunk's slots and ONE slot a decoding lane, not ``max_batch``
  chunks;
- **sampling runs inside the program**: temperature / top-k / top-p via
  the one-descending-sort formulation in serving/spec.py (greedy argmax
  and the per-row isfinite containment check included), on logit rows
  pinned replicated at the program boundary under tp;
- **the speculative accept/rollback decision is compiled too**
  (`spec.spec_emit_arrays`): the program returns ONE packed int32 array —
  emitted-run tokens, accept lengths, row-finite flags — so every step
  makes exactly one device→host transfer (the ``host_syncs`` counter /
  the step trace's ``sync`` phase). Enable speculation with
  ``spec_decoding=True``; with greedy
  sampling the output is token-for-token identical to non-speculative
  decode, and with temperature sampling verification runs rejection
  sampling against the same temperature/top-k/top-p-processed
  distribution, so the output distribution is unchanged.

**One step in flight ahead of the host**: a call of `step` plans and
launches the next step as if the step in flight had emitted, and only then
reads the step in flight (`_Launched`, `_finish`), so the device runs one
step while the host reads, emits and plans around it. A decode row whose
token the step in flight samples names that step's result row in the
packed input (section ``src``) and the program takes the token there: the
token never visits the host before it is fed. Drafts, ``only``, a fault
plan, a host KV tier and a plan that would need a block the pool lacks
keep a call synchronous (`step`).

Prefill buckets are gone: a prompt of ANY length streams into the arena
`prefill_chunk` tokens at a time while the running batch keeps decoding in
the same steps, so time-to-first-token of in-flight requests no longer
spikes when a long prompt arrives. ``width_buckets`` adds intermediate
ragged widths (e.g. ``[8, 32]``) so short prefill tails stop paying full
chunk width — each extra bucket is one more compiled program. The
`jit_traces` counter in `metrics` increments inside the traced body (trace
time only) and is the test's recompile alarm; step KINDS (mixed / decode /
verify) survive as metrics/trace labels only — they no longer key
programs, so coinciding widths dedup into one executable.

Decode outputs are token-for-token identical to `GPT.generate`'s greedy
path: the same attention math runs through the block-table gather instead
of a contiguous buffer (models/gpt.py `CausalSelfAttention` +
ops/pallas/paged_attention.py's XLA fallback; the Pallas ragged kernel on
TPU matches to kernel-accumulation tolerance).

**Automatic prefix caching** is on by default (disable with
``prefix_cache=False``): the engine chains each request's full-block
prompt hashes ONCE at `add`, the scheduler pins any cached prefix at
admission so prefill starts at the first uncached token, and freed blocks
park in the pool's cached-free LRU tier. A cache-hit serve is
token-for-token identical to a cold serve (tests/test_prefix_cache.py):
reused blocks hold exactly the K/V a replay would recompute, and writes
into shared blocks copy-on-write first.

**Tensor-parallel serving** (``mesh=...``, serving/sharded.py): weights
and the head-major KV arena shard over a ``tp`` NamedSharding mesh — the
same width-bucket programs compile mesh-aware (weights/arena pinned to
their tp layouts, host-marshalled step inputs replicated, arena donation
through the ``mesh_donate_argnums`` gate), while block tables, scheduler,
prefix cache, and refcounts stay host-side and identical to the
single-chip engine. Greedy sharded output is token-for-token identical to
single-chip serving.

**Fault tolerance**: the step programs report per-row logit finiteness,
and a NaN/Inf row is aborted with ``error:nonfinite_logits`` (its blocks
never published to the prefix cache) instead of sampling garbage —
reported in ``step_faults``. ``step(only=...)`` restricts one step to a
set of request ids: the supervision layer (serving/supervisor.py) uses it
to bisect a raising step down to the one poisoned request, re-queueing
everyone else via ``requeue`` (preempt-by-recompute). Deterministic fault
injection (serving/faults.py, `faults.install`) is compiled into the
step/alloc hot paths as one-pointer-test hook sites, off by default.

**Observability** (serving/trace.py, off by default): ``trace=True`` (or
a sampling fraction) turns on the ring-buffered lifecycle/step tracer —
per-request span trees and a per-`step()` phase timeline exported as
Perfetto-loadable trace-event JSON (``GET /debug/trace`` on the HTTP
server, `engine.tracer.dump()` anywhere else), with step ids stamped into
`jax.profiler` annotations so device captures join back to host spans.
Disabled, ``self.tracer`` is None and every hook is one pointer test.
Independently, ``request_log=True`` logs ONE structured JSON line per
finished/aborted request (queue wait, TTFT, TPOT, tenant/priority/deadline,
the phase decomposition, cached/spec tokens, preemptions) on the
``paddle_tpu.serving.request`` logger — the greppable fallback when full
tracing is off.

**SLO ledger** (serving/slo.py, ``slo=True``): a per-request phase clock
decomposes every request's wall time into
``queued`` / ``prefill_compute`` / ``decode_compute`` / ``preempted`` /
``stalled`` / ``emit`` (summing to e2e exactly, by construction), and
per-(tenant, priority) rollups — p50/p95 TTFT, TPOT, tokens/s,
preemption share, deadline attainment against ``deadline_s`` — export
as ``GET /debug/slo`` JSON and true labeled Prometheus histograms on
``/metrics``. **Flight recorder** (serving/postmortem.py,
``postmortem_dir=``): every supervisor fault event (poison isolation,
watchdog trip, non-finite row, engine-thread death) writes one bounded
on-disk postmortem bundle (trace ring, metrics/pool/health snapshots,
fault plan, the victim's ledger decomposition, recent request-log lines),
pruned to a cap and listable at ``GET /debug/postmortem``. Both off by
default behind one pointer test per hook site.
"""
from __future__ import annotations

import contextlib
import functools
import json
import logging
import math
import threading
import time
import warnings
from collections import namedtuple

import numpy as np

from ..core.functional import functional_call, state_dict_arrays
from ..ops.pallas.paged_attention import ragged_grid_steps
from ..ops.pallas.paged_kv_append import append_blocks, blocks_per_row
from . import faults
from .block_pool import (BlockPool, PagedState, blocks_for,
                         chain_block_hashes)
from .faults import FaultInjected
from .metrics import ServingMetrics
from .scheduler import RUNNING, WAITING, MustDrain, Request, Scheduler

_request_log = logging.getLogger("paddle_tpu.serving.request")

StepOutput = namedtuple("StepOutput", ["request_id", "token", "finished"])

# a result row's words where the program drafts nothing (K = 0): the token,
# the accept length, the row-finite flag. The previous step's result, which
# a step takes to gather the tokens the host has not read, is laid out so
_ROW_WORDS = 3


class _Launched:
    """A step launched on the device whose result the host has not read
    (`LLMEngine._in_flight`, at most one between calls): what reading and
    emitting it needs, and what the tracer records of it then. ``lanes[i]``
    is planned row i's row of the result; ``result_row`` maps a request
    that emits in this step to that row, where the next step's ``src`` finds
    its token; ``gens[i]`` is row i's request's preemption count at the
    launch, so a row whose request ended or was requeued since is dropped at
    the read; ``read``: the result has been read."""

    def __init__(self, rows, lanes, W, K, out, step_id, phases, args):
        self.rows, self.lanes, self.W, self.K = rows, lanes, W, K
        self.out, self.step_id = out, step_id
        self.phases, self.args = phases, args
        self.kind = None
        self.gens = [row.req.preemptions for row in rows]
        self.result_row = {row.req: lane for lane, row in zip(lanes, rows)
                           if row.emit}
        self.read = False


@functools.lru_cache(maxsize=None)
def step_input_layout(B, W, max_blocks, touched_width=None,
                      lane_state=False, lanes=False, src=False):
    """THE layout of a step's host inputs, a group of rows at a time:
    ``({name: (offset, shape, dtype)}, words)`` for one group of ``B`` rows
    of ``W`` slots (`LLMEngine.step_groups`). What the host hands the
    device for a step is ONE int32 array, the groups' ``words`` one after
    the other (`LLMEngine._step_layout`), and every named input is a
    section of it, in the order below — a pure function of what the
    program's shape already depends on (``touched_width`` is the int8
    arena's `_touched_width`, None for a float arena; ``lane_state`` says
    that the pool holds state a lane, and adds the section ``fresh``: 1
    where a row starts its sequence from zeros; ``lanes`` adds the section
    of that name for a group behind the decode group: the decode group's
    row each of its rows stands in for, where the request is decided and
    where state a lane holds lives; ``src`` adds the decode group's section
    of that name: the row of the previous step's result whose token the
    row feeds, -1 where the host gives it). `_row_arrays` makes its views
    from this table and the jitted step (`unpack_step_input`) its slices,
    so the two cannot drift apart. The float32 sections are views of the
    same words (``ndarray.view`` on the host, ``lax.bitcast_convert_type``
    in the program): bit for bit."""
    i32, f32 = np.dtype(np.int32), np.dtype(np.float32)
    sections = [
        ("ids", (B, W), i32), ("tables", (B, max_blocks), i32),
        ("slots", (B, W), i32), ("offs", (B, W), i32), ("qpos", (B, W), i32),
        ("q_start", (B,), i32), ("kv_live", (B,), i32),
        *([("touched", (B, touched_width), i32), ("touch_idx", (B, W), i32)]
          if touched_width else []),
        *([("fresh", (B,), i32)] if lane_state else []),
        *([("lanes", (B,), i32)] if lanes else []),
        *([("src", (B,), i32)] if src else []),
        ("adapter_slots", (B,), i32), ("last_idx", (B,), i32),
        ("spec_lens", (B,), i32), ("temps", (B,), f32), ("top_ks", (B,), i32),
        ("top_ps", (B,), f32),
    ]
    fields, words = {}, 0
    for name, shape, dtype in sections:
        fields[name] = (words, shape, dtype)
        words += math.prod(shape)
    return fields, words


def unpack_step_input(packed, fields):
    """`{name: array}` out of the packed step input, inside the program:
    static slices and reshapes of `step_input_layout`'s sections."""
    import jax

    out = {}
    for name, (off, shape, dtype) in fields.items():
        x = jax.lax.slice(packed, (off,), (off + math.prod(shape),))
        if dtype != packed.dtype:
            x = jax.lax.bitcast_convert_type(x, dtype)
        out[name] = x.reshape(shape)
    return out


def _refuse_option(model, option, asked, why):
    """Three of the engine's options are written against GPT's modules:
    "tp" (`validate_model` and the Megatron placement), "quantize"
    (AdaRound's calibration walks `blk.attn.qkv`) and "lora" (targets named
    after GPT's projections). A model that names the ones it takes
    (`serving_options`) is refused the others here, with a sentence and not
    an AttributeError from inside them; a model that names none is GPT."""
    takes = getattr(model, "serving_options", None)
    if takes is not None and option not in takes:
        raise ValueError(
            f"{asked} is not available for {type(model).__name__}: {why}")


def _adaround_model_int8(model, calib_prompts, iters=300):
    """Int8 weight quantization for a GPT serving model: AdaRound
    (quantization/adaround.py `learn_rounding`) on every tp-parallel
    Linear in the blocks — qkv/proj/fc1/fc2 — with per-output-channel
    absmax scales, written back QDQ (``w = q * s``) so every downstream
    consumer (eager calibration, functional_call step programs, the
    tied lm head being wte and thus untouched) sees the quantized
    values with no layer swaps. Norms, embeddings, and biases stay
    f32. Calibration inputs are captured per layer with forward
    pre-hooks over `calib_prompts` (token-id sequences; a small
    deterministic set when None — fine for smoke quality, real
    deployments should pass held-out prompts). ``iters=0`` degrades to
    round-to-nearest QDQ (learn_rounding's loop just doesn't run)."""
    import jax.numpy as jnp

    from ..core.tensor import Tensor
    from ..distributed.mesh import suppress_mesh
    from ..quantization.adaround import learn_rounding

    if calib_prompts is None:
        vocab = int(model.cfg.vocab_size)
        calib_prompts = [
            [(7 * i + 3 * j + 1) % vocab for j in range(16)]
            for i in range(4)
        ]
    subs = []
    for blk in model.blocks:
        subs += [blk.attn.qkv, blk.attn.proj, blk.fc1, blk.fc2]
    captured = {id(s): [] for s in subs}

    def _capture(store):
        # pre-hook contract (nn/layer.py): returning None keeps the
        # inputs; list.append obliges
        return lambda layer, inputs: store.append(
            np.asarray(inputs[0]._array, np.float32))

    hooks = [s.register_forward_pre_hook(_capture(captured[id(s)]))
             for s in subs]
    try:
        with suppress_mesh():
            for prompt in calib_prompts:
                ids = np.asarray(prompt, np.int32).reshape(1, -1)
                model(Tensor(jnp.asarray(ids)))
    finally:
        for h in hooks:
            h.remove()
    for s in subs:
        xs = captured[id(s)]
        w = np.asarray(s.weight._array, np.float32)
        scales = np.maximum(np.abs(w).max(axis=0), 1e-8)[None, :] / 127.0
        bias = (None if s.bias is None
                else jnp.asarray(s.bias._array, jnp.float32))

        def apply_fn(wq, x, _b=bias):
            y = x.astype(jnp.float32) @ wq
            return y if _b is None else y + _b

        targets = [np.asarray(apply_fn(jnp.asarray(w), jnp.asarray(x)))
                   for x in xs]
        q = learn_rounding(w, scales, apply_fn, xs, targets, 127.0,
                           iters=int(iters))
        s.weight._array = jnp.asarray(q * scales,
                                      s.weight._array.dtype)


class LLMEngine:
    def __init__(self, model, block_size=16, num_blocks=None, max_batch=4,
                 prefill_chunk=None, token_budget=None, max_seq_len=None,
                 seed=0, prefix_cache=None, spec_decoding=False,
                 num_spec_tokens=4, spec_max_ngram=3, spec_min_ngram=1,
                 trace=None, trace_buffer=65536, request_log=False,
                 mesh=None, kv_hbm_bytes=None, slo=False,
                 postmortem_dir=None, postmortem_keep=16, width_buckets=(),
                 host_kv_blocks=0, host_swap_chunk=4,
                 kv_dtype=None, quantize=None, calib_prompts=None,
                 quantize_iters=300, quant_allreduce=None,
                 checkpoint_path=None, param_hbm_bytes=None,
                 policy=None, lora_slots=0, lora_rank=8,
                 lora_targets=None, warmup=False):
        import jax

        from .sharded import as_serving_mesh, kv_capacity_blocks

        model.eval()
        self.model = model
        cfg = model.cfg
        # tensor-parallel serving (serving/sharded.py): `mesh` is a
        # ServingMesh / jax Mesh with a 'tp' axis / int tp degree. None
        # (degree 1) keeps the single-chip engine byte-identical.
        self._smesh = as_serving_mesh(mesh)
        # heads of a cache slab: the model's key/value heads (fewer than
        # its query heads under grouped-query attention). THE one place the
        # arena's head count is taken from
        self.kv_heads = int(getattr(model, "kv_heads", cfg.num_heads))
        if self._smesh is not None:
            _refuse_option(
                model, "tp", "mesh= (tensor-parallel serving)",
                "the tp placement and its collective budget are written "
                "for GPT's fused-QKV block; serve it on one chip")
            self._smesh.validate_model(cfg, self.kv_heads)
        # cache slabs a token's K/V take: the model says (a GPT: one a
        # layer; a looped model: one a (pass, layer))
        self.kv_slabs = int(model.kv_slabs)
        # state a lane holds that no position addresses (a state-space
        # layer's recurrent state): the model says what, the pool owns it.
        # What hands a request blocks WITHOUT the tokens that made them
        # cannot rebuild such state, so those options are refused
        spec = getattr(model, "lane_state_spec", None)
        self._lane_spec = spec() if spec is not None else {}
        # the cache kinds the model declares (block_pool.py `CacheKind`), an
        # arena a kind in the pool; None: a head's keys and values, the
        # pair the paged kernels walk. A kind a window bounds is a ring a
        # lane, state as above: the same options are refused for it
        kinds = getattr(model, "cache_kinds", None)
        self._cache_kinds = None if kinds is None else tuple(kinds())
        self._lane_held = tuple(self._lane_spec) + tuple(
            k.name for k in self._cache_kinds or () if k.window is not None)
        # int8 KV arena (`kv_dtype="int8"`): payload bytes quarter (vs
        # f32) and the SAME kv_hbm_bytes budget admits ~4x the blocks —
        # behind the parity/perplexity quality gates in
        # tests/test_int8_kv.py. None keeps the weight-dtype arena.
        if kv_dtype is not None and str(kv_dtype) not in ("int8",):
            raise ValueError(
                f"kv_dtype {kv_dtype!r} not supported — pass 'int8' for "
                "the quantized arena or None for the weight dtype")
        self.kv_dtype = None if kv_dtype is None else str(kv_dtype)
        self.kv_quantized = self.kv_dtype == "int8"
        if self.kv_quantized and self._cache_kinds is not None:
            raise ValueError(
                f"kv_dtype='int8' is not available for "
                f"{type(model).__name__}: the int8 arena keeps a scale a "
                "head and block of keys or values, and this model's cache "
                f"rows ({', '.join(k.name for k in self._cache_kinds)}) are "
                "not a head's")
        # int8 weights (AdaRound, quantization/adaround.py): QDQ in place
        # on the caller's model at construction, calibrated on
        # `calib_prompts` token sequences. Norms/embeddings stay f32.
        if quantize is not None and quantize is not False:
            if quantize != "int8":
                raise ValueError(
                    f"quantize={quantize!r} not supported — only 'int8'")
            _refuse_option(
                model, "quantize", "quantize='int8'",
                "AdaRound's calibration walks GPT's qkv/proj/fc1/fc2 "
                "modules (kv_dtype='int8' does not depend on the model)")
            if checkpoint_path is not None:
                raise ValueError(
                    "checkpoint_path and quantize are mutually exclusive: "
                    "AdaRound calibrates against eager weights the "
                    "streamed engine never materializes — quantize a "
                    "single-chip engine, save_sharded_model its weights, "
                    "then serve THAT checkpoint (kv_dtype='int8' composes "
                    "with streaming as-is)")
            if self._smesh is not None:
                raise ValueError(
                    "quantize='int8' requires mesh=None: AdaRound "
                    "calibrates against the eager single-device model "
                    "before placement — quantize first, then build the "
                    "sharded engine from the quantized model")
            _adaround_model_int8(model, calib_prompts,
                                 iters=int(quantize_iters))
        self.quantize = quantize or None
        # EQuARX quantized tp all-reduce (serving/sharded.py
        # `quantized_row_parallel`), gated PER OP so IR001 can lock the
        # resulting collective shape: True = both RowParallel projections,
        # or an iterable drawn from {"attn_proj", "ffn_fc2"}; None: none.
        # Meaningless (and ignored) single-chip — there is no collective
        # to quantize at tp=1.
        if quant_allreduce is True:
            quant_allreduce = ("attn_proj", "ffn_fc2")
        self.quant_collectives = frozenset(quant_allreduce or ())
        if not self.quant_collectives <= {"attn_proj", "ffn_fc2"}:
            raise ValueError(
                f"quant_allreduce names unknown ops "
                f"{sorted(self.quant_collectives - {'attn_proj', 'ffn_fc2'})}"
                " — the quantizable RowParallel collectives are "
                "'attn_proj' and 'ffn_fc2'")
        if self._smesh is None:
            self.quant_collectives = frozenset()
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self.max_seq_len > cfg.max_seq_len:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} exceeds the model's "
                f"max_seq_len {cfg.max_seq_len}"
            )
        self.block_size = int(block_size)
        self.max_blocks = -(-self.max_seq_len // self.block_size)
        self.max_batch = int(max_batch)
        if kv_hbm_bytes is not None:
            if num_blocks is not None:
                raise ValueError(
                    "pass num_blocks OR kv_hbm_bytes, not both — the byte "
                    "budget would be silently ignored"
                )
            # size the pool from a PER-CHIP byte budget. The arena is
            # head-sharded under tp, so one shard stores heads/tp per
            # block and the budget buys tp x the logical-head-count
            # formula's blocks — capacity (and therefore `validate`'s
            # admission bound) is derived from what ONE SHARD holds.
            # An int8 arena prices blocks at itemsize 1 plus the f32
            # scale-sidecar overhead — this is where the same budget
            # starts admitting ~4x (f32) / ~2x (bf16) the sequences.
            if self._cache_kinds is not None:
                # declared kinds: a block costs its rows in every kind that
                # lives in blocks (a windowed kind costs a lane, not a block)
                num_blocks = int(kv_hbm_bytes) // sum(
                    k.slabs * (k.heads or 1) * self.block_size * k.width
                    * self._weight_dtype().itemsize
                    for k in self._cache_kinds if k.window is None)
            else:
                num_blocks = kv_capacity_blocks(
                    kv_hbm_bytes, self.kv_slabs, self.kv_heads,
                    self.block_size, self._head_dim(),
                    1 if self.kv_quantized
                    else self._weight_dtype().itemsize,
                    tp_degree=(1 if self._smesh is None
                               else self._smesh.tp_degree),
                    scale_itemsize=4 if self.kv_quantized else 0,
                )
            # validate()'s worst case for a max-length request: every
            # token but the final sampled one is cached — the gate must
            # mirror that bound exactly or it rejects budgets admission
            # would serve (blocks_for is the ONE ceiling formula; the
            # pool doesn't exist yet, so use the module-level form)
            worst = blocks_for(self.max_seq_len - 1, self.block_size)
            if num_blocks < 1 + worst:
                # too small to hold even ONE max-length sequence (+null):
                # fail at construction naming the budget, not per-request
                raise ValueError(
                    f"kv_hbm_bytes {kv_hbm_bytes} buys only {num_blocks} "
                    f"KV blocks per shard but one max_seq_len="
                    f"{self.max_seq_len} sequence needs {worst} (+ the "
                    "null block) — raise the budget, lower max_seq_len, "
                    "or raise tp_degree"
                )
        if num_blocks is None:
            # enough for a full decode batch of max-length sequences (+null)
            num_blocks = self.max_batch * self.max_blocks + 1
        if prefill_chunk is None:
            prefill_chunk = min(128, self.max_seq_len)
        self.prefill_chunk = max(1, min(int(prefill_chunk), self.max_seq_len))
        if token_budget is None:
            # default: ONE chunk of prefill a step beside the decode rows
            # (the chunk group is `token_budget // prefill_chunk` rows,
            # `step_groups`). A chunk row that no request fills is computed
            # all the same: its slots ride the step's weight stream (a v5e
            # reads a bf16 weight in the time of about 240 tokens' products
            # on it, 197 TFLOP/s over 819 GB/s), but what mixes along a
            # sequence, and a model's passes and experts, cost their own
            # time: measured on the chip, a second row that stays empty
            # costs a chunk-carrying step 1-2 ms in a 1.3B GPT, 4 ms in the
            # hybrid and 17 ms in the looped decoder (PERF.md section 6,
            # PR 37), and in a closed loop it nearly always stays empty:
            # requests end one at a time. The price: where MANY rows
            # prefill at once (a burst, long documents behind one
            # another) every chunk takes a step of its own and pays the
            # step's fixed cost; such a deployment raises the budget, a
            # row a chunk, up to `max_batch * prefill_chunk` (every lane
            # may carry a full chunk a step)
            token_budget = self.prefill_chunk
        self.prefill_chunk = min(self.prefill_chunk, int(token_budget))
        # prefix caching: None is on, except where a lane holds state
        self.prefix_cache = (not self._lane_held if prefix_cache is None
                             else bool(prefix_cache))
        self._refuse_for_lane_state(
            self.prefix_cache, "prefix_cache=True",
            "a prefix hit hands a request blocks and no state")
        # speculative decoding: default OFF. num_spec_tokens fixes the
        # verify program's width (per-request knobs can only lower the
        # draft cap)
        self.spec_decoding = bool(spec_decoding)
        self._refuse_for_lane_state(
            self.spec_decoding, "spec_decoding=True",
            "a rejected draft has already moved the state")
        self.num_spec_tokens = int(num_spec_tokens)
        drafter = None
        if self.spec_decoding:
            from .spec import NgramDrafter

            if self.num_spec_tokens + 1 > self.max_seq_len:
                raise ValueError(
                    f"num_spec_tokens {self.num_spec_tokens} does not fit "
                    f"max_seq_len {self.max_seq_len}"
                )
            drafter = NgramDrafter(
                num_spec_tokens=self.num_spec_tokens,
                max_ngram=spec_max_ngram, min_ngram=spec_min_ngram,
            )
        # ragged width buckets: the ONLY program shapes this engine ever
        # compiles — (max_batch, W) for W in this sorted set. Defaults:
        # width 1 (the dominant all-decode steps), 1 + num_spec_tokens
        # (spec engines: a drafted pure-decode step), prefill_chunk (the
        # widest possible chunk). `width_buckets` (e.g. (8, 32)) adds
        # intermediate widths so short prefill tails stop paying chunk
        # width — each bucket is one more compiled program, which is why
        # the default set stays minimal. Coinciding widths (e.g.
        # 1 + num_spec == prefill_chunk) dedup: the table is keyed by
        # width, not by step kind.
        buckets = {1, self.prefill_chunk}
        if self.spec_decoding:
            buckets.add(min(1 + self.num_spec_tokens, self.max_seq_len))
        top = max(buckets)
        for w in width_buckets:
            w = int(w)
            if w < 1:
                raise ValueError(f"width_buckets entries must be >= 1; "
                                 f"got {w}")
            if 1 <= w <= top:
                buckets.add(w)   # wider than any plannable row: useless
        self.width_buckets = sorted(buckets)
        self.metrics = ServingMetrics()
        # replica lifecycle (serving/lifecycle.py): this constructor
        # drives cold -> loading (weight placement below) -> warm (end of
        # __init__, after the optional warmup wave); the async frontend
        # and router drive serving/draining/stopped. Surfaced on
        # /healthz, /metrics (lifecycle_state gauge), and /debug/router.
        from .lifecycle import ReplicaLifecycle

        self.lifecycle = ReplicaLifecycle(metrics=self.metrics)
        # tracing: off unless `trace` asks for it (True, or a fraction of
        # requests to sample); the step timeline is always recorded while
        # the tracer exists. When off, tracer is None and every hook site
        # below is a single pointer test — the untraced serve is
        # byte-identical to the pre-trace engine.
        from .trace import EngineTracer

        sample = min(max(float(trace or 0.0), 0.0), 1.0)
        self.tracer = (EngineTracer(capacity=max(16, int(trace_buffer)),
                                    sample=sample)
                       if sample > 0.0 else None)
        self.request_log = bool(request_log)
        # flight recorder (serving/postmortem.py): a configured directory
        # turns supervisor events (poison isolation, watchdog trip,
        # non-finite row, thread death) into pruned on-disk postmortem
        # bundles; None otherwise and every hook is one pointer test
        from .postmortem import FlightRecorder
        from .slo import SLOLedger

        self.recorder = None
        if postmortem_dir:
            self.recorder = FlightRecorder(
                postmortem_dir, keep=int(postmortem_keep)).attach(self)
        # SLO attribution ledger (serving/slo.py): per-request phase
        # clock + per-(tenant, priority) rollups/histograms and
        # /debug/slo. On when asked — and whenever the request log or
        # the flight recorder is on, since both embed the decomposition;
        # otherwise None and every hook is one pointer test.
        self.slo = (SLOLedger(metrics=self.metrics)
                    if slo or self.request_log
                    or self.recorder is not None else None)
        # weight placement — two paths:
        #  - eager (checkpoint_path=None): the model's resident arrays are
        #    the source; sharded engines device_put them once. The full
        #    tree necessarily exists on the model's device first, which is
        #    exactly what a model bigger than one chip cannot do.
        #  - streamed (checkpoint_path=...): weights stream shard-by-shard
        #    from disk straight onto their serving placement
        #    (distributed/checkpoint.py stream_load_state) — no full host
        #    buffer, no chip beyond its own shards. The model may be a
        #    `skeleton_init()` shell (ShapeDtypeStruct "arrays" carrying
        #    only shape/dtype/sharding_axes); the engine serves from
        #    self._params via functional_call, so the shell never needs
        #    real numbers.
        from ..nn.layer import is_skeleton

        self.checkpoint_path = checkpoint_path
        self.load_report = None
        self.lifecycle.to("loading", "placing weights")
        if is_skeleton(model) and checkpoint_path is None:
            raise ValueError(
                "model was built under skeleton_init() (no real weight "
                "arrays) — pass checkpoint_path= so the engine can stream "
                "weights from disk, or build the model eagerly")
        self._param_shardings = self._buffer_shardings = None
        if checkpoint_path is not None:
            self._stream_params_from_checkpoint(checkpoint_path)
        elif self._smesh is not None:
            # place weights once at construction: attention heads / FFN
            # columns / vocab rows over 'tp' (serving_param_specs is the
            # model's own Megatron sharding_axes renamed mp -> tp),
            # everything unannotated replicated. The step programs then
            # pin these layouts via in_shardings — placement never
            # re-happens per step.
            from .sharded import serving_param_specs

            self._params, self._buffers = state_dict_arrays(model)
            specs = serving_param_specs(model, self._smesh)
            self._param_shardings = {
                k: self._smesh.named(*specs[k]) for k in self._params
            }
            self._buffer_shardings = {
                k: self._smesh.replicated() for k in self._buffers
            }
            self._params = {
                k: jax.device_put(v, self._param_shardings[k])
                for k, v in self._params.items()
            }
            self._buffers = {
                k: jax.device_put(v, self._buffer_shardings[k])
                for k, v in self._buffers.items()
            }
        else:
            self._params, self._buffers = state_dict_arrays(model)
        # per-chip parameter budget: fail AT CONSTRUCTION, naming the
        # overage, when any single device holds more parameter bytes than
        # allowed. `param_bytes_by_device` counts the model's own resident
        # arrays too, so the eager path is (correctly) charged for its
        # full-tree source copy — the streamed+skeleton path is not.
        self.param_hbm_bytes = (None if param_hbm_bytes is None
                                else int(param_hbm_bytes))
        if self.param_hbm_bytes is not None:
            peak = max(self.param_bytes_by_device().values(), default=0)
            if peak > self.param_hbm_bytes:
                raise ValueError(
                    f"param_hbm_bytes {self.param_hbm_bytes}: a device "
                    f"holds {peak} parameter bytes — the model does not "
                    "fit one chip. Serve it from a sharded checkpoint "
                    "(LLMEngine(skeleton, checkpoint_path=..., mesh=N)) "
                    "so no chip ever materializes the full tree")
        self.pool = BlockPool(
            num_blocks, self.kv_slabs, self.block_size, self.kv_heads,
            self._head_dim(), dtype=self._weight_dtype(),
            metrics=self.metrics, tracer=self.tracer,
            sharding=(None if self._smesh is None
                      else self._smesh.arena_sharding()),
            kv_dtype=self.kv_dtype,
            lane_state=self._lane_spec, lanes=self.max_batch,
            kinds=self._cache_kinds, chunk=self.prefill_chunk,
        )
        # host-memory KV tier (serving/kv_tier.py): `host_kv_blocks` host
        # block slots make evicted cached prefixes swap-back-able instead
        # of dying (and carry them across replicas on drain/eject).
        # 0 = off, one pointer, every hook a single test — the tierless
        # engine is byte-identical to the pre-tier engine.
        self.tier = None
        self._refuse_for_lane_state(
            host_kv_blocks, "host_kv_blocks (the host KV tier)",
            "a swap carries blocks and no state")
        if host_kv_blocks:
            from .kv_tier import KVTier

            self.tier = KVTier(self.pool, host_kv_blocks,
                               mesh=self._smesh, metrics=self.metrics,
                               swap_chunk=host_swap_chunk)
            self.pool.attach_tier(self.tier)
        # mesh topology gauges: a replica's shape is visible on /metrics
        # and /healthz without log-diving (single-chip engines report
        # tp_degree 1 so dashboards need no sharded-or-not special case)
        mi = self.mesh_info()
        self.metrics.set_gauge("mesh_tp_degree", mi["tp_degree"])
        self.metrics.set_gauge("mesh_device_count", mi["device_count"])
        self.metrics.set_info("mesh", {"backend": mi["backend"]})
        # KV dtype observability: the active arena dtype and what one
        # logical block costs ride /metrics (and mesh_info/pool_stats),
        # so the int8 capacity doubling is visible on every surface that
        # reports blocks
        self.metrics.set_gauge("kv_bytes_per_block",
                               self.pool.bytes_per_block())
        self.metrics.set_gauge("kv_slabs", self.kv_slabs)
        self.metrics.set_gauge("kv_heads", self.kv_heads)
        self.metrics.set_gauge("state_bytes_per_lane",
                               self.pool.state_bytes_per_lane())
        self.metrics.set_gauge(
            "state_layers",
            max((a.shape[0] for a in self.pool.lane_state.values()),
                default=0))
        for name, value in getattr(model, "serving_gauges", dict)().items():
            self.metrics.set_gauge(name, value)
        if self._cache_kinds is not None:
            for name, n in self.pool.bytes_per_token().items():
                self.metrics.set_gauge(f"cache_bytes_per_token_{name}", n)
            self.metrics.set_gauge("window_cache_bytes_per_lane",
                                   self.pool.window_bytes_per_lane())
        # counts the model's step leaves on the paged state
        # (`step_stats`), which ride the packed result to the step span
        self._stat_names = tuple(getattr(model, "step_stat_names", ()))
        self.metrics.set_gauge(
            "kv_bytes_per_token",
            self.pool.bytes_per_block() / self.block_size)
        self.metrics.set_info("kv", {"dtype": self.pool.kv_dtype})
        # scheduling policy (serving/policy.py): priority classes,
        # windowed tenant fairness, deadline early-reject. None (the
        # default) keeps the FCFS scheduler byte-identical.
        from .policy import as_policy

        self.policy = as_policy(policy)
        self.scheduler = Scheduler(
            self.pool, max_batch=self.max_batch,
            token_budget=int(token_budget),
            prefill_chunk=self.prefill_chunk, metrics=self.metrics,
            prefix_cache=self.prefix_cache, drafter=drafter,
            tracer=self.tracer, slo=self.slo,
            width_buckets=self.width_buckets, policy=self.policy,
        )
        # per-request LoRA adapters over the shared base model
        # (models/lora.py): `lora_slots` device slots (slot 0 = the
        # all-zeros "no adapter"), each holding a rank-<= lora_rank
        # adapter over the column-parallel targets, gathered per-row
        # INSIDE the unified ragged step. 0 slots = off: the step
        # signature carries an empty table tree and the engine is
        # byte-identical to the pre-LoRA engine.
        self.lora_slots = int(lora_slots)
        self.lora_rank = int(lora_rank)
        self._lora_tables = {}
        self._lora_shardings = {}      # step-jit in_shardings (empty = off)
        self._adapters = {}        # name -> slot (1-based; 0 = base)
        self._adapter_inflight = {}    # name -> live request count
        self._adapter_lru = []         # names, least-recent first
        self.lora_targets = ()
        if self.lora_slots:
            from ..models import lora as lora_mod

            _refuse_option(
                model, "lora", "lora_slots",
                "the adapter tables are laid out a GPT layer and target "
                "GPT's projections (models/lora.py LORA_TARGETS)")
            if self.lora_rank < 1:
                raise ValueError("lora_rank must be >= 1 with lora_slots")
            self.lora_targets = tuple(lora_targets
                                      or lora_mod.LORA_TARGETS)
            self._lora_tables = lora_mod.init_adapter_tables(
                cfg, 1 + self.lora_slots, self.lora_rank,
                self.lora_targets, smesh=self._smesh)
            if self._smesh is not None:
                self._lora_shardings = lora_mod.table_shardings(
                    self.lora_targets, self._smesh)
        self._requests = {}
        self._step_fns = {}
        self._layouts = {}  # width bucket -> `_step_layout`
        # the step launched and not yet read (`_Launched`), or None: at most
        # one is in flight between two calls of `step`
        self._in_flight = None
        self._retrace_warned = False
        # stamped by AsyncLLMEngine.start(): while that thread is alive,
        # stepping from any OTHER thread would race the arena donation
        # mid-flight (the PR 16 documented hazard) — `_guard_thread`
        # raises a pointed RuntimeError instead of corrupting
        self._engine_thread = None
        # where a step's packed host input and the sampling key go: the
        # default device, or every chip of the mesh
        self._rep = (None if self._smesh is None
                     else self._smesh.replicated())
        # the sampling key lives on the device: the step program splits it
        # and hands the advanced key back (`_run_step`). Under a mesh it
        # starts out replicated, as the program returns it, so the first
        # step and every later one offer the same kind of array
        self._key = jax.device_put(jax.random.PRNGKey(seed), self._rep)
        # the previous step's result, where a step gathers the tokens its
        # predecessor sampled and the host has not read (section `src`):
        # zeros before the first step, after a step that drafts (whose
        # result is wider) and after a step whose result is never read
        n_stats = len(self._stat_names)
        self._no_prev = jax.device_put(np.zeros(
            (self.max_batch * _ROW_WORDS + n_stats,) if n_stats
            else (self.max_batch, _ROW_WORDS), np.int32), self._rep)
        self._prev = self._no_prev
        # supervision surface (serving/supervisor.py reads these):
        self.step_count = 0      # planned steps run (bisection probes too)
        self.last_planned = []   # request ids of the most recent plan
        self.step_faults = []    # (rid, detail) rows contained this step
        # warm: weights are placed; warmup=True additionally compiles the
        # FULL width-bucket program table now (synthetic wave below) so
        # the first served request never pays an XLA compile inside its
        # TTFT — lifecycle.warmed records which guarantee holds.
        if warmup:
            self.warmup()
        self.lifecycle.to("warm", "weights placed"
                          + (" + programs compiled" if warmup else ""))

    # -- construction helpers ----------------------------------------------

    def _weight_dtype(self):
        """The type the model's weights are served in, which a float arena
        takes: the first parameter's (a skeleton's abstract one too)."""
        return self.model.parameters()[0]._array.dtype

    def _refuse_for_lane_state(self, asked, option, why):
        if asked and self._lane_held:
            raise ValueError(
                f"{option} is not available for "
                f"{type(self.model).__name__}, which keeps state a lane "
                f"({', '.join(self._lane_held)}) that no block holds: {why}")

    def _head_dim(self):
        cfg = self.model.cfg
        return getattr(cfg, "head_dim", None) \
            or cfg.hidden_size // cfg.num_heads

    def _stream_params_from_checkpoint(self, path):
        """Stream weights from a sharded checkpoint straight onto their
        serving placement (distributed/checkpoint.py `stream_load_state`):
        per-leaf, per-shard device_put against `serving_param_specs`. The
        full tree never exists on one host buffer or one chip; the
        measured bounds land in `self.load_report` (a StreamLoadReport)
        and on /metrics."""
        import jax

        from ..distributed.checkpoint import stream_load_state

        pmap = self.model.named_parameters_dict()
        bmap = self.model.named_buffers_dict()
        if self._smesh is not None:
            from .sharded import serving_param_specs

            specs = serving_param_specs(self.model, self._smesh)
            self._param_shardings = {
                k: self._smesh.named(*specs[k]) for k in pmap
            }
            self._buffer_shardings = {
                k: self._smesh.replicated() for k in bmap
            }
        else:
            one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
            self._param_shardings = {k: one for k in pmap}
            self._buffer_shardings = {k: one for k in bmap}
        shardings = {f"params/{k}": s
                     for k, s in self._param_shardings.items()}
        shardings.update({f"buffers/{k}": s
                          for k, s in self._buffer_shardings.items()})
        state, report = stream_load_state(path, shardings,
                                          keys=set(shardings))
        got_p = state.get("params", {})
        got_b = state.get("buffers", {})
        missing = ([f"params/{k}" for k in pmap if k not in got_p]
                   + [f"buffers/{k}" for k in bmap if k not in got_b])
        if missing:
            raise ValueError(
                f"checkpoint {path!r} is missing model arrays "
                f"{missing[:4]}{' ...' if len(missing) > 4 else ''} — was "
                "it saved from this architecture (save_sharded_model)?")

        def _check(kind, k, want, got):
            if (tuple(got.shape) != tuple(want.shape)
                    or got.dtype != want.dtype):
                raise ValueError(
                    f"checkpoint {path!r}: {kind} {k!r} is "
                    f"{got.dtype}{tuple(got.shape)} but the model "
                    f"declares {want.dtype}{tuple(want.shape)} — "
                    "checkpoint and model config disagree")

        for k, t in pmap.items():
            _check("param", k, t._array, got_p[k])
        for k, t in bmap.items():
            _check("buffer", k, t._array, got_b[k])
        self._params = {k: got_p[k] for k in pmap}
        self._buffers = {k: got_b[k] for k in bmap}
        self.load_report = report
        self.metrics.set_gauge("ckpt_stream_peak_host_bytes",
                               float(report.peak_host_bytes))
        self.metrics.set_gauge("ckpt_stream_max_chip_bytes",
                               float(report.max_chip_bytes))
        self.metrics.set_gauge("ckpt_stream_seconds", report.seconds)

    def param_bytes_by_device(self):
        """Resident parameter/buffer bytes per device: the engine's placed
        arrays PLUS any real arrays the model itself still holds (the
        eager path's full-tree source copy — exactly why that path cannot
        satisfy a per-chip budget a too-big model needs), deduped by
        identity. The `param_hbm_bytes` budget checks the max of this."""
        import jax

        seen, out = set(), {}

        def note(a):
            if not isinstance(a, jax.Array) or id(a) in seen:
                return
            seen.add(id(a))
            for sh in a.addressable_shards:
                out[sh.device] = out.get(sh.device, 0) + int(sh.data.nbytes)

        for a in self._params.values():
            note(a)
        for a in self._buffers.values():
            note(a)
        for m in (self.model.named_parameters_dict(),
                  self.model.named_buffers_dict()):
            for t in m.values():
                note(getattr(t, "_array", None))
        return out

    def warmup(self):
        """Compile the engine's ENTIRE width-bucket program table by
        serving one synthetic request per bucket, one at a time (a batch
        of mixed widths would compile only its widest bucket):

        - a bucket ``W <= prefill_chunk`` is reached by a prompt of
          exactly ``W`` tokens — its first prefill chunk has width W, the
          planner picks the smallest covering bucket, W itself;
        - a spec bucket wider than ``prefill_chunk`` is only reachable as
          a drafted decode step, so its request carries a cyclic prompt
          the n-gram drafter always matches, forcing one full-width
          draft+verify step.

        Prefix caching is suspended for the duration (synthetic prompts
        must not seed the cache or dodge compilation via a hit). Programs
        land in the ordinary jit dispatch cache — the same cache served
        steps hit — so after warmup the first real step is 0 retraces
        (the `jit_traces` sentinel's warm guarantee, recorded on
        `lifecycle.warmed`). Returns the number of compiled programs."""
        if self.has_unfinished():
            raise RuntimeError(
                "warmup() requires an idle engine — it serves synthetic "
                "requests through the real step path")
        t0 = time.monotonic()
        expected = self.expected_program_count()
        pc_engine, pc_sched = self.prefix_cache, self.scheduler.prefix_cache
        self.prefix_cache = self.scheduler.prefix_cache = False
        try:
            for W in self.width_buckets:
                if (self.max_batch, W) in self._step_fns:
                    continue  # coinciding widths dedup
                if W <= self.prefill_chunk:
                    plen = min(W, self.max_seq_len - 1)
                    prompt = [0] * plen
                    mnt = 1
                else:
                    # drafted-only bucket (1 + num_spec_tokens beyond the
                    # chunk): cyclic prompt -> the n-gram drafter proposes
                    # a full draft on the first decode step
                    mnt = self.num_spec_tokens + 2
                    plen = max(1, min(self.prefill_chunk,
                                      self.max_seq_len - mnt))
                    prompt = [(i % 3) + 1 for i in range(plen)]
                rid = self.add_request(prompt, max_new_tokens=mnt,
                                       temperature=0.0,
                                       tenant="_warmup")
                for _ in range(8 * mnt + 8):
                    if not self.has_unfinished():
                        break
                    self.step()
                    if (self.max_batch, W) in self._step_fns:
                        # bucket compiled — the rest of this request is
                        # redundant work
                        if rid in self._requests:
                            self.abort(rid)
                        break
                else:
                    raise RuntimeError(
                        f"warmup: synthetic request for bucket {W} never "
                        "finished")
        finally:
            self.prefix_cache = pc_engine
            self.scheduler.prefix_cache = pc_sched
        compiled = len(self._step_fns)
        if compiled < expected:
            missing = [W for W in self.width_buckets
                       if (self.max_batch, W) not in self._step_fns]
            raise RuntimeError(
                f"warmup compiled {compiled}/{expected} width-bucket "
                f"programs — buckets {missing} were never exercised")
        self.lifecycle.warmed = True
        self.lifecycle.programs_compiled = compiled
        self.metrics.set_gauge("warmup_programs", float(compiled))
        self.metrics.set_gauge("warmup_seconds",
                               round(time.monotonic() - t0, 3))
        return compiled

    # -- request lifecycle -------------------------------------------------

    def add_request(self, prompt_ids, max_new_tokens=16, temperature=0.0,
                    eos_token_id=None, request_id=None, top_k=None,
                    top_p=None, spec_decoding=None, num_spec_tokens=None,
                    trace=None, tenant=None, priority=None,
                    deadline_s=None, adapter=None):
        """Enqueue one generation request; returns its id. Admission happens
        inside a later `step()` (continuous batching: requests join the
        running batch between decode steps, never blocking them). Prompts of
        any length are accepted — prefill is chunked under the scheduler's
        token budget, so no prompt can monopolize a step. `top_k`/`top_p`
        restrict the sampling support (temperature > 0 only; greedy
        ignores them); `spec_decoding=False` / `num_spec_tokens` opt this
        request out of (or cap) speculative drafting on a spec-enabled
        engine; `trace=True`/`False` forces this request into (out of)
        the lifecycle tracer regardless of its sampling fraction;
        `tenant`/`priority` label the request's SLO accounting class and
        `deadline_s` its attainment target (serving/slo.py — accounting
        only here; the async frontend's ``timeout_s`` also enforces);
        `adapter` names a loaded LoRA adapter (`load_adapter`) this
        request decodes through (None = the shared base model)."""
        prompt_ids = [int(t) for t in np.asarray(prompt_ids).reshape(-1)]
        req = Request(prompt_ids, max_new_tokens=max_new_tokens,
                      temperature=temperature, eos_token_id=eos_token_id,
                      request_id=request_id, top_k=top_k, top_p=top_p,
                      spec_decoding=spec_decoding,
                      num_spec_tokens=num_spec_tokens, trace=trace,
                      tenant=tenant, priority=priority,
                      deadline_s=deadline_s, adapter=adapter)
        return self.add(req)

    def mesh_info(self):
        """Topology of this replica — {tp_degree, device_count, backend,
        kv_dtype} — for /healthz, the ``mesh_*`` gauges, and benches.
        Single-chip engines report degree/count 1 on the default
        backend. `kv_dtype` is the ACTIVE arena dtype (int8 when
        quantized), so capacity numbers on the same surface are
        interpretable."""
        if self._smesh is not None:
            info = self._smesh.info()
        else:
            import jax

            info = {"tp_degree": 1, "device_count": 1,
                    "backend": jax.default_backend()}
        pool = getattr(self, "pool", None)
        info["kv_dtype"] = (pool.kv_dtype if pool is not None
                            else (self.kv_dtype or "float32"))
        return info

    def kv_capacity_blocks(self):
        """Usable KV blocks — what ONE SHARD of the arena actually holds
        (minus the null block). Under tp the arena is head-sharded, so a
        per-chip byte budget (``kv_hbm_bytes``) buys ``tp_degree`` times
        the blocks of the naive logical-head-count formula; the pool's
        ``num_blocks`` is already derived per-shard at construction, and
        every admission bound (`validate`, hence the frontend's
        ``max_kv_commit_blocks`` gate) must reject against THIS number,
        never a logical-head recomputation."""
        return self.pool.num_blocks - 1

    # -- LoRA adapter registry (models/lora.py owns the math) --------------

    def _touch_adapter(self, name):
        """Move `name` to the recently-used end of the LRU order."""
        try:
            self._adapter_lru.remove(name)
        except ValueError:
            pass
        self._adapter_lru.append(name)

    def _find_adapter_slot(self, name):
        """Slot for a (re)load of `name`: its current slot, else a free
        one, else the least-recently-used idle adapter's (evicting it).
        Raises when every slot holds an adapter with requests in
        flight."""
        if name in self._adapters:
            return self._adapters[name]
        used = set(self._adapters.values())
        for slot in range(1, 1 + self.lora_slots):
            if slot not in used:
                return slot
        for victim in self._adapter_lru:
            if not self._adapter_inflight.get(victim, 0):
                slot = self._adapters.pop(victim)
                self._adapter_lru.remove(victim)
                self._adapter_inflight.pop(victim, None)
                self.metrics.inc("lora_adapter_evictions")
                self.metrics.inc_labeled("lora_adapter_evictions",
                                         {"adapter": victim})
                return slot
        raise RuntimeError(
            f"all {self.lora_slots} adapter slots hold adapters with "
            "requests in flight — raise lora_slots or drain first "
            f"(inflight: { {k: v for k, v in self._adapter_inflight.items() if v} })"
        )

    def load_adapter(self, name, weights, alpha=None):
        """Load (or replace) a named LoRA adapter into a device slot so
        requests can decode through it (``add_request(adapter=name)``).
        `weights` maps target op names to ``(A [L, in, r], B [L, r, out])``
        host arrays with ``r <= lora_rank`` (`models.lora.pack_adapter`
        validates; `alpha` folds the conventional ``alpha/r`` scale into
        B at load time). Slots are bounded: when all ``lora_slots`` are
        taken, the least-recently-used adapter with NO requests in flight
        is evicted; if every adapter is busy this raises. The table
        update is functional and the new tree is swapped in with one
        rebind — in-flight steps keep reading the tree they captured.
        Returns the device slot index."""
        if not self.lora_slots:
            raise RuntimeError(
                "engine built without LoRA slots (lora_slots=0)")
        from ..models import lora as lora_mod

        name = str(name)[:64]
        packed = lora_mod.pack_adapter(self.model.cfg, weights,
                                       self.lora_rank, self.lora_targets,
                                       alpha=alpha)
        slot = self._find_adapter_slot(name)
        self._lora_tables = lora_mod.write_slot(self._lora_tables, slot,
                                                packed)
        self._adapters[name] = slot
        self._adapter_inflight.setdefault(name, 0)
        self._touch_adapter(name)
        self.metrics.set_gauge("lora_adapters_loaded", len(self._adapters))
        return slot

    def unload_adapter(self, name):
        """Free a named adapter's slot. Refuses while any request on it
        is still in flight (their gathered rows index this slot — zeroing
        it mid-decode would silently serve base-model tokens). The freed
        slot is zeroed so no stale weights linger."""
        if name not in self._adapters:
            raise ValueError(f"unknown adapter {name!r} "
                             f"(loaded: {sorted(self._adapters)})")
        n = self._adapter_inflight.get(name, 0)
        if n:
            raise RuntimeError(
                f"adapter {name!r} has {n} request(s) in flight — drain "
                "or abort them before unloading")
        from ..models import lora as lora_mod

        slot = self._adapters.pop(name)
        self._adapter_inflight.pop(name, None)
        try:
            self._adapter_lru.remove(name)
        except ValueError:
            pass
        self._lora_tables = lora_mod.zero_slot(self._lora_tables, slot)
        self.metrics.set_gauge("lora_adapters_loaded", len(self._adapters))

    def validate(self, req):
        """Admission-time request validation, shared by `add` and the async
        frontend's `submit` (which must reject bad requests BEFORE they
        reach the engine thread). Raises ValueError on a request that could
        never complete: too long for the model, or needing more KV blocks
        at its worst case than one arena shard holds (`kv_capacity_blocks`
        — per-shard under tp, NOT a logical-head-count formula) — without
        this check such a request is accepted, becomes the oldest running
        sequence, and the scheduler's no-livelock error then kills the
        whole serve instead of the one offender. Returns the request's
        worst-case KV block need (the frontend's ``max_kv_commit_blocks``
        gate reuses it — ONE definition of worst case)."""
        if req.adapter is not None:
            if not self.lora_slots:
                raise ValueError(
                    f"request {req.request_id}: adapter {req.adapter!r} "
                    "on an engine built without LoRA slots (lora_slots=0)"
                )
            if req.adapter not in self._adapters:
                raise ValueError(
                    f"request {req.request_id}: unknown adapter "
                    f"{req.adapter!r} — load_adapter() it first "
                    f"(loaded: {sorted(self._adapters)})"
                )
        if req.num_tokens + req.max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"request {req.request_id}: prompt {req.num_tokens} + "
                f"{req.max_new_tokens} new tokens exceeds max_seq_len "
                f"{self.max_seq_len}"
            )
        # worst-case cached tokens: everything but the final sampled token
        need = self.pool.blocks_for(req.num_tokens + req.max_new_tokens - 1)
        if need > self.kv_capacity_blocks():
            raise ValueError(
                f"request {req.request_id}: needs up to {need} KV blocks "
                f"but the pool only has {self.kv_capacity_blocks()} usable "
                "— raise num_blocks or shorten the request"
            )
        return need

    def add(self, req):
        """Enqueue a pre-built Request (the async frontend constructs and
        validates Requests off the engine thread, then hands them over
        here). Returns the request id."""
        self.validate(req)
        if req.request_id in self._requests:
            raise ValueError(f"duplicate request id {req.request_id}")
        if req.adapter is not None:
            # validate() above guarantees the adapter is loaded; pin its
            # slot for the request's whole lifetime (across preemptions —
            # replayed KV must go through the same adapter) and hold the
            # slot against LRU eviction while any request is in flight
            req.adapter_slot = self._adapters[req.adapter]
            self._adapter_inflight[req.adapter] = (
                self._adapter_inflight.get(req.adapter, 0) + 1)
            self._touch_adapter(req.adapter)
            self.metrics.inc("lora_requests")
            self.metrics.inc_labeled("lora_requests",
                                     {"adapter": req.adapter})
        if self.prefix_cache and not req.block_hashes:
            # chained once per request; the scheduler reuses them for every
            # admission (including post-preemption re-admissions). The
            # adapter name salts the chain: KV is computed THROUGH the
            # adapter, so the same prompt under different adapters must
            # never share cached blocks
            req.block_hashes = chain_block_hashes(
                req.prompt_ids, self.block_size, salt=req.adapter
            )
        self._requests[req.request_id] = req
        if self.slo is not None:
            self.slo.begin(req)   # the `queued` phase opens at arrival
        self.scheduler.add(req)
        self.metrics.inc("requests_added")
        tr = self.tracer
        if tr is not None and tr.should_trace(req):
            req.traced = True
            tr.begin_request(req)
        return req.request_id

    def abort(self, request_id, reason="aborted"):
        """Cancel a request in any live state (queued, mid-prefill,
        decoding, or preempted awaiting re-admission): the scheduler drops
        it from its queues, its KV blocks return to the pool, and its host
        record is released. The request object itself stays valid — already
        emitted `output_ids` remain readable by whoever holds it. `reason`
        labels the terminal trace span / request-log line (the supervisor
        passes ``error:<ExceptionClass>`` for poison-isolated requests).
        Returns True if a live request was aborted, False if the id is
        unknown or the request already finished."""
        req = self._requests.get(request_id)
        if req is None or req.finished:
            return False
        self.scheduler.abort(req)
        del self._requests[request_id]
        self._finalize(req, reason)
        return True

    def requeue(self, request_id):
        """Re-queue a live request by preempt-by-recompute: its KV blocks
        return to the pool and the request re-enters the waiting queue to
        replay from scratch (arrival order preserved). The supervisor's
        poison-isolation path uses this on every row of a failed step —
        the engine holds no partial step state, so recompute is the one
        correctness-preserving way to retire a step that may never have
        reached the device. Returns True if the request is (now) queued,
        False for unknown/finished ids."""
        req = self._requests.get(request_id)
        if req is None or req.finished:
            return False
        if req.state == WAITING:
            return True          # already queued (e.g. a prior probe)
        return self.scheduler.preempt(req)

    def live_requests(self):
        """Ids of requests not yet finished or aborted, in no particular
        order (the supervisor's abort-everything fallback set)."""
        return [rid for rid, r in self._requests.items() if not r.finished]

    def peek_request(self, request_id):
        """The request record (live OR finished-but-unreleased), else
        None — unlike `get_request` this never raises. The frontend's
        post-recovery reconciliation uses it to find requests that
        finished inside a step whose emission was lost."""
        return self._requests.get(request_id)

    def has_unfinished(self):
        """Requests not yet finished, or a step launched and not read."""
        return self._in_flight is not None or self.scheduler.has_unfinished()

    def get_request(self, request_id):
        return self._requests[request_id]

    def release(self, request_id):
        """Drop a finished request's host-side record (prompt + outputs).
        A long-running engine must release requests after reading their
        outputs or `_requests` grows without bound; `generate`/`stream`
        release automatically."""
        req = self._requests.pop(request_id)
        if not req.finished:
            self._requests[request_id] = req
            raise ValueError(
                f"request {request_id} is still {req.state}; release only "
                "finished requests"
            )

    # -- compiled step -----------------------------------------------------

    def _get_step_fn(self, B, W):
        """The unified ragged step program at width bucket ``W`` — one
        jitted executable per (batch, width); kinds no longer key
        programs. Its tokens lie on one token axis made of the groups of
        `step_groups`; every row feeds ``count`` chunk tokens plus ``k``
        drafted candidates (``count + k <=`` its group's width). The
        program runs the forward over the whole axis, gathers the
        ``K + 1`` scored positions starting at each row's ``last_idx``
        (K = the width's draft capacity), and finishes the WHOLE per-token
        decision on device — sampling, speculative accept/rollback,
        non-finite containment — returning one packed int32 array
        ``[B, K + 3]``, a request's row its row of the decode group:
        emitted-run tokens ``[:, :K + 1]``, accept length ``[:, K + 1]``,
        row-finite flag ``[:, K + 2]``. The host reads it with a single
        device→host transfer."""
        if (B, W) in self._step_fns:
            return self._step_fns[(B, W)]
        import jax
        import jax.numpy as jnp

        from .block_pool import PagedGroup
        from .spec import spec_emit_arrays

        model = self.model
        metrics = self.metrics

        smesh = self._smesh
        K = self._draft_capacity(W)
        quantized = self.pool.quantized
        quant_ops = self.quant_collectives
        shapes = self.step_groups(W)

        from ..models.lora import gather_adapter_rows

        def _decide(logits, ids, groups, parts, key):
            # the scored window: K + 1 consecutive positions starting at
            # each row's last chunk token — position last_idx + j scores
            # the distribution following fed token last_idx + j, which is
            # exactly what sampling (j = 0) and draft verification
            # (j >= 1) need. Rows without drafts just use slot 0.
            if len(groups) == 1:
                rows = parts[0].__getitem__
                last_idx, spec_lens = rows("last_idx"), rows("spec_lens")
                win = last_idx[:, None] + jnp.arange(K + 1)[None, :]
                win = jnp.clip(win, 0, W - 1)
                lg = jnp.take_along_axis(
                    logits, win[..., None], axis=1).astype(jnp.float32)
                win_ids = jnp.take_along_axis(ids, win, axis=1)
            else:
                # the same window on the flat axis: a row's slots begin at
                # its group's offset + row x the group's width. A request
                # is decided in ITS row of the decode group whichever group
                # fed it (`src`: that row, or the row of a later group that
                # names it in `lanes`), so the sampler draws for
                # `max_batch` rows, each request's the draw it would have
                # had in a step of one rectangle, and the result keeps its
                # shape
                win, src, n_rows = [], jnp.arange(B), 0
                for g, a in zip(groups, parts):
                    w = a["last_idx"][:, None] + jnp.arange(K + 1)[None, :]
                    win.append(jnp.clip(w, 0, g.width - 1) + g.offset
                               + g.width * jnp.arange(g.rows)[:, None])
                    if n_rows:
                        named = g.lanes[None, :] == jnp.arange(B)[:, None]
                        src = jnp.where(named.any(axis=1),
                                        n_rows + jnp.argmax(named, axis=1),
                                        src)
                    n_rows += g.rows

                def rows(name):
                    return jnp.concatenate([a[name] for a in parts])[src]

                last_idx, spec_lens = rows("last_idx"), rows("spec_lens")
                win = jnp.concatenate(win)[src]
                lg = jnp.take(logits[0], win, axis=0).astype(jnp.float32)
                win_ids = jnp.take(ids[0], win, axis=0)
            if smesh is not None:
                # THE one sanctioned boundary all-gather (analysis
                # contract IR001): materialize the scored positions'
                # full vocab rows replicated ONCE, so every sampler
                # reduction below (argmax, top-k/top-p, categorical,
                # rejection accept, isfinite) runs collective-free
                # instead of each paying its own partial-gather pair on
                # vocab-sharded rows — and the sampled tokens are
                # bit-identical across tp degrees (same key, same rows)
                lg = jax.lax.with_sharding_constraint(lg, smesh.replicated())
            # non-finite containment (the TrainMonitor discipline applied
            # to serving) over the row's LIVE window positions only (the
            # pending token + its drafted candidates); padded tail slots
            # attend through the null block and are never emitted, so
            # their logits must not poison the row
            live = jnp.arange(K + 1)[None, :] <= spec_lens[:, None]
            pos_ok = jnp.isfinite(lg).all(axis=-1)
            row_ok = jnp.where(live, pos_ok, True).all(axis=-1)
            # sampling + the speculative accept/rollback decision, all
            # compiled (serving/spec.py is the spec): the emitted run and
            # its length come back ready to publish
            run, n_acc = spec_emit_arrays(
                lg, win_ids, spec_lens, rows("temps"), rows("top_ks"),
                rows("top_ps"), key
            )
            packed = jnp.concatenate(
                [run, n_acc[:, None], row_ok.astype(jnp.int32)[:, None]],
                axis=1,
            )
            return packed

        layouts, _ = self._step_layout(W)
        paged_names = tuple(self.pool.paged)
        n_kv = len(paged_names) + (2 if quantized else 0)
        lane_names = tuple(self.pool.lane_state)
        n_arena = n_kv + len(lane_names)
        stats = bool(self._stat_names)

        # ONE signature for both arena kinds: (params, buffers, *arenas,
        # lora_tables, packed, prev, key). The arenas are the payload pair,
        # plus the f32 scale sidecars right after it when the arena is int8,
        # plus the pool's lane state after those where a model keeps any;
        # `packed` is the step's whole host input (`step_input_layout`, a
        # group after the other), `prev` the previous step's result and
        # `key` the engine's sampling key, both on the device
        def step(params, buffers, *rest):
            arenas, (lora_tables, packed, prev, key) = (rest[:n_arena],
                                                        rest[n_arena:])
            parts = [unpack_step_input(packed, fields) for fields in layouts]
            # a decode row whose token the previous step sampled and the
            # host has not read takes it from that step's result
            a = parts[0]
            fed = prev.reshape(-1)[jnp.maximum(a["src"], 0) * _ROW_WORDS]
            a["ids"] = a["ids"].at[:, 0].set(
                jnp.where(a["src"] >= 0, fed, a["ids"][:, 0]))
            # the engine's stream: one split a step, the new key handed
            # back as the program's LAST output
            key, sub = jax.random.split(key)
            # runs at TRACE time only — the test's recompile alarm
            metrics.inc("jit_traces")
            groups, offset = [], 0
            for (n, width), a in zip(shapes, parts):
                groups.append(PagedGroup(
                    offset, n, width, a["tables"], a["slots"], a["offs"],
                    a["qpos"], a["q_start"], a["kv_live"],
                    # per-row live width for the ragged kernel: chunk
                    # tokens through last_idx plus the drafted candidates
                    q_lens=a["last_idx"] + 1 + a["spec_lens"],
                    # an int8 arena: the scatter's touched-block lists
                    # ride the host input
                    touched=a.get("touched"), touch_idx=a.get("touch_idx"),
                    # per-row adapter rows gathered INSIDE the program
                    # (models/lora.py) — None when the engine has no
                    # adapter slots, keeping the trace byte-identical to
                    # the pre-LoRA one
                    lora=gather_adapter_rows(lora_tables,
                                             a["adapter_slots"]),
                    fresh=a.get("fresh"), lanes=a.get("lanes")))
                offset += n * width
            state = PagedState(
                dict(zip(paged_names, arenas)), groups,
                mesh=None if smesh is None else smesh.mesh,
                k_scale=arenas[n_kv - 2] if quantized else None,
                v_scale=arenas[n_kv - 1] if quantized else None,
                quant_collectives=quant_ops,
                lane_state=(dict(zip(lane_names, arenas[n_kv:]))
                            if lane_names else None))
            ids = state.join([a["ids"] for a in parts])
            # mask the process-global TRAINING mesh for the trace (thread-
            # local — a concurrent training trace on another thread keeps
            # its mesh): the serving step's sharding is fully explicit
            # (in_shardings + PagedState.constrain), but the TP layers'
            # dp/mp sharding constraints consult
            # distributed.mesh.get_mesh() — a mesh left installed by
            # fleet.init/init_mesh would stamp its (differently-deviced)
            # NamedShardings into this program and the call would reject
            # the engine's own placement
            from ..distributed.mesh import suppress_mesh

            with suppress_mesh():
                (logits, _), _ = functional_call(
                    model, params, buffers, args=(ids,),
                    kwargs={"caches": state}, training=False,
                )
            out = _decide(logits, ids, state.groups, parts, sub)
            if stats:
                # the model's counts behind the rows' results: still ONE
                # array for the host to read
                out = jnp.concatenate([out.reshape(-1), state.step_stats])
            return (out, *state.arenas(), key)

        # donated arena state: payload arenas, plus the f32 scale
        # sidecars when the arena is int8. The key is NOT donated: a
        # launch that raises leaves `self._key` the key it was
        arena_args = tuple(range(2, 2 + n_arena))
        if smesh is None:
            fn = jax.jit(step,
                         # jaxlint: disable=JL004 -- single-device arena donation, deliberately ungated (gating would copy the whole arena every step on CPU); the aliasing it relies on is machine-checked by IR contract IR002 (analysis/contracts.py) on the lowered tp=1 programs
                         donate_argnums=arena_args)
        else:
            # mesh-aware program, same (B, W) keying: weights and arenas
            # pinned to their tp shardings, the packed host input, the
            # previous result and the key (and the packed result and the
            # new key out) replicated.
            # Arena donation routes through the JL004 gate — the
            # host-platform CPU mesh miscompiles donated sharded buffers,
            # so donation is off exactly there and in-place on real
            # accelerators.
            from ..parallel.spmd import mesh_donate_argnums

            rep = smesh.replicated()
            arena = smesh.arena_sharding()
            in_sh = (self._param_shardings, self._buffer_shardings,
                     ) + (arena,) * n_arena + (self._lora_shardings,
                     rep, rep, rep)
            out_sh = (rep,) + (arena,) * n_arena + (rep,)
            fn = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=mesh_donate_argnums(arena_args))
        self._step_fns[(B, W)] = fn
        return fn

    def _draft_capacity(self, W):
        """Draft capacity compiled into a width-``W`` program — the ONE
        formula behind both the traced packed layout ``[B, K + 3]`` and
        the host-side parse of it (a drift between the two would read
        accept lengths out of token columns). Wide programs always carry
        the full verify window (a drafted row can ride a mixed step),
        narrow ones what fits; width 1 degenerates K to 0 and the window
        to the plain one-token sampler."""
        return min(self.num_spec_tokens if self.spec_decoding else 0, W - 1)

    def _touched_width(self, W):
        """Columns in the quantized step's per-row ``touched`` block
        list: ``W`` consecutive fed positions straddle at most
        `blocks_per_row` arena blocks (the writer's count, ops/pallas/
        paged_kv_append.py), plus slot 0 reserved
        for the null block — part of the compiled (B, W) shape key, so
        it must be THE one formula for both tracing and marshalling."""
        return blocks_per_row(W, self.block_size) + 1

    def step_groups(self, W):
        """The shape of the width-``W`` program's token axis: its groups
        ``((rows, width), ...)``, each a rectangle of rows with metadata
        of its own, side by side on ONE axis of ``sum(rows x width)``
        slots (`PagedState`). A bucket no wider than the decode group
        (one token and the draft capacity) is that one rectangle of
        ``max_batch`` rows: the all-decode steps, and a drafted
        pure-decode step. A wider bucket is the width of the CHUNK group
        behind the decode group: the rows that feed more than the decode
        group holds, `chunk_rows` of them a step (as many whole chunks as
        ``token_budget`` holds; the scheduler plans no more), so a step
        that carries a prompt chunk computes the slots of the chunk and
        one a decoding lane, not ``max_batch`` chunks."""
        sched = self.scheduler
        if W <= sched.decode_width:
            return ((self.max_batch, W),)
        return ((self.max_batch, sched.decode_width), (sched.chunk_rows, W))

    def _step_layout(self, W):
        """The width-``W`` program's packed host input: ``([fields of a
        group, ...], words)``, `step_input_layout` of each of
        `step_groups` one after the other, offsets from the array's
        start."""
        if W in self._layouts:
            return self._layouts[W]
        layouts, words = [], 0
        for i, (n, width) in enumerate(self.step_groups(W)):
            fields, size = step_input_layout(
                n, width, self.max_blocks,
                self._touched_width(width) if self.pool.quantized else None,
                bool(self.pool.lane_state),
                # a later group names the decode group's row each of its
                # rows stands in for; the decode group's rows may feed a
                # token the device holds
                lanes=i > 0, src=i == 0)
            layouts.append({name: (words + off, shape, dtype)
                            for name, (off, shape, dtype) in fields.items()})
            words += size
        self._layouts[W] = layouts, words
        return layouts, words

    def expected_program_count(self):
        """THE program-count contract, in one place: the engine compiles
        at most one executable per ragged width bucket — steady state
        traces each touched bucket exactly once, so ``jit_traces <=
        expected_program_count()`` with equality once traffic has
        exercised every width. Tests and the retrace sentinel both
        derive from this instead of hardcoding per-kind counts."""
        return len(self.width_buckets)

    def _width_for(self, w):
        """Smallest ragged width bucket covering a plan whose widest row
        feeds ``w`` tokens (the scheduler caps row widths at the top
        bucket, so this always resolves)."""
        for b in self.width_buckets:
            if b >= w:
                return b
        raise AssertionError(
            f"step width {w} exceeds the top width bucket "
            f"{self.width_buckets[-1]} — scheduler width capping broke"
        )

    # -- lowered-program surface (analysis/ir.py "hlolint") ----------------

    def step_program_shapes(self):
        """{name: (B, W)} for every program this engine would compile —
        one unified ragged step per width bucket, named ``w<width>``.
        The IR contract checker lowers exactly these."""
        return {f"w{W}": (self.max_batch, W) for W in self.width_buckets}

    def lowered_step_programs(self, kinds=None):
        """AOT-lower the engine's compiled-step programs WITHOUT serving
        traffic: {name: jax.stages.Lowered} for each width bucket in
        `step_program_shapes` (or the `kinds` name subset). Weights and
        the KV arenas pass as their real placed arrays (so shardings and
        donation lower exactly as a served step would); the host-
        marshalled inputs pass as ShapeDtypeStructs. Nothing executes —
        ``.compile()`` on a result yields the artifact hlolint parses
        (post-SPMD HLO text, cost/memory analysis, input_output_alias).
        Lowering re-traces outside the jit dispatch cache, so the
        ``jit_traces`` counter is snapshotted and restored — the
        recompile sentinel must never blame an analysis pass."""
        shapes = self.step_program_shapes()
        if kinds is not None:
            shapes = {k: shapes[k] for k in kinds}
        snap = self.metrics.counters.get("jit_traces", 0)
        lowered = {}
        try:
            for name, (B, W) in shapes.items():
                lowered[name] = self._get_step_fn(B, W).lower(
                    *self.step_program_args(W))
        finally:
            # restore even when a lower() raises mid-loop: the recompile
            # sentinel must never blame serving for analysis traces
            self.metrics.counters["jit_traces"] = snap
        return lowered

    def _arenas(self):
        """The pool's arena arrays in the step program's order."""
        return self.pool.arenas()

    def step_program_args(self, W):
        """THE statement of the width-``W`` step program's arguments, in
        its order ``(params, buffers, *arenas, lora_tables, packed, prev,
        key)``: weights, arenas and adapter tables as their real placed
        arrays, the packed host input (`step_input_layout`), the previous
        step's result and the sampling key as ShapeDtypeStructs.
        `lowered_step_programs` lowers with exactly these; tests and AOT
        tools map them to shapes of their own."""
        import jax
        import jax.numpy as jnp

        _, words = self._step_layout(W)
        return (self._params, self._buffers, *self._arenas(),
                self._lora_tables,
                jax.ShapeDtypeStruct((words,), jnp.int32),
                jax.ShapeDtypeStruct(self._prev.shape, self._prev.dtype),
                jax.ShapeDtypeStruct(self._key.shape, self._key.dtype))

    def step_program_spec(self):
        """Flat-signature facts the donation contract (IR002) checks the
        lowered programs against: where the donated KV arena inputs land
        in the flat parameter numbering, where the updated arenas land in
        the flat outputs, and whether arena donation is expected to alias
        on this engine (single-chip engines donate unconditionally; mesh
        engines route through `parallel.spmd.mesh_donate_argnums`, which
        turns donation off on the cpu host platform). The unified program
        returns ``(packed, k_arena, v_arena, key)`` — the two f32 scale
        sidecars after the arenas when the arena is int8, the advanced
        sampling key always LAST — so the arena state lands at outputs
        (1, 2[, 3, 4]) for every width."""
        import jax

        n_state = (len(jax.tree_util.tree_leaves(self._params))
                   + len(jax.tree_util.tree_leaves(self._buffers)))
        if self._smesh is None:
            donation_on = True
        else:
            # deliberately NOT derived from mesh_donate_argnums: the
            # contract's "expected" side must be an independent statement
            # of the policy (sharded donation is off on the cpu host
            # platform), or a broken/bypassed gate would move both sides
            # together and IR002 could never trip (the seeded regression
            # in tests/test_ir_contracts.py patches the gate ungated and
            # must fail the contract)
            donation_on = jax.default_backend() != "cpu"
        n_arena = len(self.pool.arenas())
        return {
            "arena_param_indices": tuple(
                range(n_state, n_state + n_arena)),
            "arena_output_indices": {
                name: tuple(range(1, 1 + n_arena))
                for name in self.step_program_shapes()
            },
            "donation_expected": donation_on,
        }

    def swap_program_shapes(self):
        """{name: chunk_width} for the host-tier swap copy programs
        (kv_tier.py) this engine would compile — empty when the tier is
        off. The IR contract checker lowers exactly these alongside the
        step programs."""
        if self.tier is None:
            return {}
        return {"swap_out": self.tier.swap_chunk,
                "swap_in": self.tier.swap_chunk}

    def lowered_swap_programs(self):
        """AOT-lower the tier's swap gather/scatter WITHOUT executing
        them: {name: jax.stages.Lowered}. The tier's own lazily-built jit
        callables are lowered (not re-built copies), so shardings and
        donation lower exactly as a served swap would — a silent
        full-arena-copy regression in either program (the PR 4 eager-COW
        bug class) shows up in the artifact's cost/alias analysis."""
        import jax
        import jax.numpy as jnp

        if self.tier is None:
            return {}
        t = self.tier
        c = t.swap_chunk
        L, H, Bs, D = t._shape
        dt = self.pool.k.dtype
        idx = jax.ShapeDtypeStruct((c,), jnp.int32)
        chunk = jax.ShapeDtypeStruct((L, H, c, Bs, D), dt)
        if self.pool.quantized:
            sc_chunk = jax.ShapeDtypeStruct((L, H, c), jnp.float32)
            return {
                "swap_out": t._gather_jit().lower(
                    self.pool.k, self.pool.v, self.pool.k_scale,
                    self.pool.v_scale, idx),
                "swap_in": t._scatter_jit().lower(
                    self.pool.k, self.pool.v, self.pool.k_scale,
                    self.pool.v_scale, chunk, chunk, sc_chunk, sc_chunk,
                    idx),
            }
        return {
            "swap_out": t._gather_jit().lower(self.pool.k, self.pool.v,
                                              idx),
            "swap_in": t._scatter_jit().lower(self.pool.k, self.pool.v,
                                              chunk, chunk, idx),
        }

    def swap_program_spec(self):
        """IR002 facts for the swap programs: the swap-in scatter donates
        both arenas (params 0, 1 -> outputs 0, 1) under the same policy
        as the step program — unconditionally single-chip, gated off on
        the cpu host platform when sharded; the swap-out gather must
        donate NOTHING (the arena stays live under it — an alias there
        would corrupt the pool). Stated independently of the gate, like
        `step_program_spec` (a bypassed gate must move only one side)."""
        import jax

        if self._smesh is None:
            donation_on = True
        else:
            donation_on = jax.default_backend() != "cpu"
        n_arena = 4 if self.pool.quantized else 2
        return {
            "arena_param_indices": tuple(range(n_arena)),
            "arena_output_indices": {"swap_in": tuple(range(n_arena))},
            "donation_expected": donation_on,
            "no_alias": ("swap_out",),
        }

    def _run_step(self, fn, packed, step_id=0, t_disp=0.0, args=None):
        """Hand the step's packed host input to the device and dispatch
        the unified step program; returns the DEVICE result array (the
        caller's single np.asarray on it is the step's ONE host sync). The
        program takes the previous step's result as it lies on the device
        (`_prev`), from which it gathers the tokens the host has not read,
        splits the sampling key itself and returns the advanced one, stored
        here without being read; a launch that raises assigns nothing, so
        the key (never donated) stays the one the failed step was offered
        and a retry samples with it again. While tracing, `args` (the step
        span's) gets ``h2d_arrays``, the number of host arrays handed over
        (one, on every step of every engine), ``h2d_bytes`` its size and
        ``h2d_ms`` the time from ``t_disp`` (the start of the ``dispatch``
        phase) to just before the program is launched: that one transfer.
        The launch then runs under the step's annotation (`Tracer.launch`:
        the join key to a device capture and, with the ``launch_ns``
        stamped before it, the step's clock pair)."""
        import jax

        dev = jax.device_put(packed, self._rep)
        tr = self.tracer
        if tr is None:
            launch = contextlib.nullcontext()
        else:
            args.update(h2d_arrays=1, h2d_bytes=packed.nbytes,
                        h2d_ms=(time.monotonic() - t_disp) * 1e3)
            launch = tr.launch(step_id, args)
        with launch:
            out, *arenas, self._key = fn(
                self._params, self._buffers, *self._arenas(),
                self._lora_tables, dev, self._prev, self._key)
        self.pool.set_arenas(arenas)
        # a result with drafts is wider than the next step takes
        self._prev = out if out.shape == self._no_prev.shape \
            else self._no_prev
        return out

    # -- fault hooks (serving/faults.py; armed plans only) -----------------

    def _fire_step_faults(self):
        """Evaluate the step-scoped fault points against this step's plan.
        Only reached when a FaultPlan is installed (the caller's one
        pointer test); order is degrade -> hang -> raise so a combined
        plan slows/wedges the step before failing it."""
        plan = faults._PLAN
        tr = self.tracer
        fp = plan.match("slow_step_ms", step=self.step_count,
                        request_ids=self.last_planned)
        if fp is not None:
            if tr is not None:
                tr.supervisor_instant("fault[slow_step_ms]",
                                      {"step": self.step_count, "ms": fp.ms})
            time.sleep((fp.ms or 0.0) / 1e3)
        fp = plan.match("step_hang", step=self.step_count,
                        request_ids=self.last_planned)
        if fp is not None:
            if tr is not None:
                tr.supervisor_instant("fault[step_hang]",
                                      {"step": self.step_count})
            plan.hang(fp)
        fp = plan.match("step_raise", step=self.step_count,
                        request_ids=self.last_planned)
        if fp is not None:
            if tr is not None:
                tr.supervisor_instant("fault[step_raise]",
                                      {"step": self.step_count})
            raise FaultInjected(
                "step_raise",
                None if fp.exc is None
                else f"injected step fault ({fp.exc})",
            )

    def _corrupt_row_ok(self, placed, row_ok):
        """``step_nonfinite_logits``: report the matched rows' logits as
        non-finite, driving the containment path below exactly as a real
        numerically-poisoned forward would. Only reached when a plan is
        installed."""
        plan = faults._PLAN
        # np.asarray of a device array is typically a read-only view
        row_ok = np.array(row_ok)
        for i, row in placed:
            fp = plan.match("step_nonfinite_logits", step=self.step_count,
                            request_ids=(row.req.request_id,))
            if fp is not None:
                if self.tracer is not None:
                    self.tracer.supervisor_instant(
                        "fault[step_nonfinite_logits]",
                        {"step": self.step_count,
                         "request_id": row.req.request_id})
                row_ok[i] = False
        return row_ok

    def _poison(self, req, detail):
        """Contain one numerically-poisoned row: abort ONLY this request
        with a structured error reason, never publishing the blocks its
        own prefill wrote (their KV is suspect; blocks matched FROM the
        cache at admission are republished — other holders vouch for
        them). The supervisor relays ``step_faults`` to the frontend so
        the consumer sees a terminal ``error`` event."""
        req.block_hashes = req.block_hashes[:req.num_matched_blocks]
        self.metrics.inc("nonfinite_rows")
        self.step_faults.append((req.request_id, detail))
        self.abort(req.request_id, reason=f"error:{detail}")
        if self.recorder is not None:
            # after the abort: the bundle carries the victim's FINAL
            # ledger decomposition (record never raises — postmortem.py)
            self.recorder.record("nonfinite_row", detail=detail, victim=req)

    # -- one engine step ---------------------------------------------------

    def step(self, only=None):
        """Run one mixed (or pure-decode) step; returns [StepOutput] for
        every request that produced a token this step. ``only`` restricts
        the plan (admission included) to that set of request ids — the
        supervisor's bisection probes use it to step half the suspects of
        a failed batch while everyone else holds still. Rows the engine
        had to contain this step (non-finite logits) emit no StepOutput;
        they are aborted internally and reported in ``self.step_faults``
        as ``(request_id, detail)`` pairs.

        At most one step is in flight ahead of the host. Where the engine
        may (`_may_launch_ahead`), a call plans the NEXT step as if the
        step in flight had emitted (a row that emits there has one more
        token, fed by the device from that step's result: section `src`),
        launches it, and only then reads the step in flight and emits its
        tokens: the device goes from one step to the next while the host
        reads, emits and plans. A request whose last token is in flight is
        not planned again; one that ends otherwise (eos, abort, deadline, a
        non-finite row) is found at the read, and the token the step behind
        made for it is dropped at ITS read. A call still returns one step's
        outputs, in the order a synchronous loop returns them. A call that
        may not launch ahead (drafts, ``only``, a fault plan, a host KV
        tier, or a plan that needs a block the pool does not have) reads
        the step in flight first, then plans and runs its own step
        synchronously as before, and returns both steps' outputs. Anything
        raised drains: a step whose result was not read is taken back
        (`_unlaunch`) and ``last_planned`` names the rows of every step the
        call touched."""
        self._guard_thread("step()")
        self.step_faults = []
        ahead = only is None and self._may_launch_ahead()
        steps, outs = [], []     # the steps this call touched, in order
        t_mark = time.monotonic()
        try:
            own = self._in_flight is None
            if own:
                self._in_flight = self._launch(only)
            if self._in_flight is not None:
                steps.append(self._in_flight)
            nxt = None
            if self._in_flight is not None and ahead:
                try:
                    nxt = self._launch(ahead=True)
                except MustDrain:
                    ahead = False
                if nxt is not None:
                    steps.append(nxt)
            if self._in_flight is not None:
                outs += self._finish(self._in_flight)
                t_mark = self._count_step(self._in_flight, t_mark)
            self._in_flight = nxt
            if not own and not ahead:
                # this call's own step, planned on read tokens
                s = self._in_flight = self._launch(only)
                if s is not None:
                    steps.append(s)
                    outs += self._finish(s)
                    t_mark = self._count_step(s, t_mark)
                    self._in_flight = None
        except BaseException:
            for s in reversed(steps):
                if not s.read:
                    self._unlaunch(s)
            self._in_flight = None
            touched = [r.req.request_id for s in steps for r in s.rows]
            self.last_planned = list(dict.fromkeys(
                touched + list(self.last_planned)))
            raise
        read = [s for s in steps if s.read]
        if not read:
            return outs
        self.last_planned = list(dict.fromkeys(
            r.req.request_id for s in read for r in s.rows))
        self.metrics.set_gauge(
            "tokens_in_flight",
            sum(r.num_tokens for r in self.scheduler.running),
        )
        usable = self.pool.num_blocks - 1
        self.metrics.set_gauge(
            "block_utilization", (usable - self.pool.num_free) / usable
        )
        self.metrics.set_gauge("num_running", len(self.scheduler.running))
        self.metrics.set_gauge("num_waiting", len(self.scheduler.waiting))
        if self.policy is not None:
            # whole-family replacement: classes whose queue drained (or
            # tenants whose window emptied) drop off the scrape instead
            # of freezing at their last value
            depth = {}
            for req in self.scheduler.waiting:
                lbl = tuple(sorted(self.policy.class_labels(req).items()))
                depth[lbl] = depth.get(lbl, 0) + 1
            self.metrics.set_labeled_gauges(
                "policy_queue_depth",
                [(dict(lbl), n) for lbl, n in depth.items()])
            self.metrics.set_labeled_gauges(
                "policy_served_share",
                [({"tenant": t}, s)
                 for t, s in self.policy.served_shares().items()])
        c = self.metrics.counters
        # recompile sentinel: steady state means jit_traces == compiled
        # programs (each width bucket's program traces exactly once, and
        # the table can never outgrow expected_program_count() — THE
        # one-place program-count contract). A surplus trace is a
        # RE-trace of an existing program — some input's shape/dtype is
        # drifting per step, and every retrace pays a full XLA compile
        # on the serving hot path.
        retraces = int(c.get("jit_traces", 0)) - len(self._step_fns)
        self.metrics.set_gauge("jit_retraces", max(retraces, 0))
        if (retraces > 0 or
                len(self._step_fns) > self.expected_program_count()) \
                and not self._retrace_warned:
            self._retrace_warned = True
            warnings.warn(
                f"LLMEngine recompile sentinel: {max(retraces, 0)} "
                f"re-trace(s) of already-compiled step programs "
                f"({len(self._step_fns)} programs compiled, "
                f"{self.expected_program_count()} width buckets, "
                f"{int(c['jit_traces'])} traces) — a step input's shape "
                "or dtype is varying between steps; steady-state serving "
                "compiles at most one program per ragged width bucket, "
                "each exactly once",
                RuntimeWarning, stacklevel=2,
            )
        n_steps = (c.get("mixed_steps", 0) + c.get("decode_steps", 0)
                   + c.get("verify_steps", 0))
        if n_steps:
            self.metrics.set_gauge(
                "tokens_per_step", c.get("generated_tokens", 0) / n_steps
            )
        if self.spec_decoding and c.get("spec_proposed_tokens"):
            self.metrics.set_gauge(
                "spec_acceptance_rate",
                c["spec_accepted_tokens"] / c["spec_proposed_tokens"],
            )
            self.metrics.set_gauge(
                "spec_mean_accepted_len",
                c["spec_accepted_tokens"] / c["spec_drafted_rows"],
            )
        if self.prefix_cache:
            self.metrics.set_gauge(
                "prefix_cached_blocks", self.pool.num_cached_blocks
            )
            lookup = self.metrics.counters.get("prefix_cache_lookup_tokens", 0)
            if lookup:
                self.metrics.set_gauge(
                    "prefix_cache_hit_rate",
                    self.metrics.counters.get("prefix_cache_hit_tokens", 0)
                    / lookup,
                )
        return outs

    def _may_launch_ahead(self):
        """Whether the next step may be planned and launched while the one
        in flight is unread, by what the engine can observe: no drafter
        (it proposes from tokens on the host), no fault plan (its hooks
        match steps and rows the host has read) and no host KV tier (its
        `flush_saves` orders arena writes against the plan). Where a plan
        made ahead needs a block the pool does not have, the scheduler
        says so (`MustDrain`) and the call drains instead."""
        return (self.scheduler.drafter is None and faults._PLAN is None
                and self.tier is None)

    def _launch(self, only=None, ahead=False):
        """Plan the next step and launch it: its `_Launched`, or None where
        nothing is planned. ``ahead``: a step is in flight unread and this
        one is planned as if it had emitted (the scheduler raises
        `MustDrain` where that plan would preempt or defer)."""
        tr = self.tracer
        t_plan0 = time.monotonic() if tr is not None else 0.0
        # cleared BEFORE planning: if schedule() itself raises (config
        # error, injected alloc pressure) the supervisor must not recover
        # against an earlier plan
        self.last_planned = []
        rows = self.scheduler.schedule(only=only, ahead=ahead)
        if self.policy is not None:
            # deadline early-rejects decided during admission: surface
            # each as an aborted request on the step_faults channel (the
            # supervisor relays faults as failures, so frontend streams
            # get a terminal "error" event with the policy reason) —
            # drained BEFORE the empty-plan early return so a step whose
            # only outcome was rejection still finalizes its victims
            for req, reason in self.scheduler.drain_policy_rejects():
                self.metrics.inc("policy_early_rejections")
                self.metrics.inc_labeled("policy_early_rejections",
                                         self.policy.class_labels(req))
                self.step_faults.append((req.request_id, reason))
                self.abort(req.request_id, reason=reason)
        if self.tier is not None:
            # arena-write ordering (kv_tier.py rule 1): demotions buffered
            # by this plan's evictions must gather their bytes before the
            # step program's donated scatters land on those blocks
            self.tier.flush_saves()
        if not rows:
            return None
        self.step_count += 1
        self.last_planned = [row.req.request_id for row in rows]
        if faults._PLAN is not None:
            self._fire_step_faults()
        # ONE program shape per step — the smallest ragged width bucket
        # covering the widest planned row (chunk tokens + drafts). The
        # dominant all-decode steps resolve to width 1; step KINDS are
        # metrics/trace labels only and no longer key programs.
        W = self._width_for(max(r.count + len(r.draft) for r in rows))
        step_id = tr.next_step_id() if tr is not None else 0
        t_plan1 = time.monotonic() if tr is not None else 0.0
        s = self._run_rows(rows, W, step_id)
        if any(r.count > 1 for r in rows):
            s.kind = "mixed"
        elif any(r.draft for r in rows):
            s.kind = "verify"
        else:
            s.kind = "decode"
        if tr is not None:
            s.phases["plan"] = (t_plan0, t_plan1)
        return s

    def _unlaunch(self, s):
        """Take back what launching `s` told the host (its rows' tokens
        counted in the arena, its samples unread) for a step whose result
        will never be read, and feed the next step no token of it."""
        for row, gen in zip(s.rows, s.gens):
            req = row.req
            if req.state == RUNNING and req.preemptions == gen:
                req.num_cached -= row.count
                req.unread -= row.emit
        self._prev = self._no_prev

    def _count_step(self, s, t_mark):
        """A read step's counters and its wall time: from `t_mark` (the
        call's start, or the read before it in the same call) to now."""
        now = time.monotonic()
        self.metrics.observe(f"{s.kind}_step", now - t_mark)
        if self.policy is not None:
            self.policy.observe_step(now - t_mark)
        self.metrics.inc(f"{s.kind}_steps")
        return now

    def _row_arrays(self, S):
        """A fresh packed host input for the unified ragged step and its
        named views, a dict a group (so fill sites cannot drift apart on a
        future per-row field): ``(packed, [{name: view}, ...])``. Every
        section starts zeroed — idle rows have no live block (``kv_live``
        0: the ragged kernel runs no iteration there), read the all-zeros
        adapter slot 0 and, with an int8 arena, touch only the null block
        (``touched`` slot 0, ``touch_idx`` 0) — but ``top_ps``, which
        reads 1.0, ``lanes``, which names no lane, and ``src``, which names
        no row of the previous result (-1). A buffer a step was
        launched with is never written again: a `device_put` on the CPU
        backend may alias it."""
        layouts, words = self._step_layout(S)
        packed = np.zeros(words, np.int32)
        parts = []
        for fields in layouts:
            a = {name: packed[off:off + math.prod(shape)].view(dtype)
                       .reshape(shape)
                 for name, (off, shape, dtype) in fields.items()}
            a["top_ps"][:] = 1.0
            if "lanes" in a:
                a["lanes"][:] = self.max_batch
            if "src" in a:
                a["src"][:] = -1
            parts.append(a)
        return packed, parts

    def _fill_row(self, a, i, req, start, w, S):
        """Everything about row `i` that does not depend on WHICH tokens
        are fed: scatter targets for positions [start, start+w), the block
        table, and the per-row sampling knobs."""
        a["qpos"][i, :w] = np.arange(start, start + w)
        a["slots"][i], a["offs"][i] = self.pool.positions_to_slots(
            req.blocks, start, w, S
        )
        a["tables"][i] = self.pool.table_for(req.blocks, self.max_blocks)
        a["temps"][i] = req.temperature
        a["top_ks"][i] = req.top_k or 0
        a["top_ps"][i] = 1.0 if req.top_p is None else req.top_p
        a["q_start"][i] = start
        a["kv_live"][i] = (start + w - 1) // self.block_size + 1
        a["adapter_slots"][i] = req.adapter_slot
        if self.pool.quantized:
            # unique non-null blocks this row's scatter writes, listed
            # after the null slot; invalid/pad tokens keep touch_idx 0
            # and requantize only the null block (whose scale pins at
            # the floor, see _quantize_scatter)
            sl = a["slots"][i, :w]
            uniq = np.unique(sl[sl != 0])
            a["touched"][i, 1:1 + len(uniq)] = uniq
            lut = {int(b): j + 1 for j, b in enumerate(uniq)}
            a["touch_idx"][i, :w] = [lut.get(int(s), 0) for s in sl]

    def _place_rows(self, rows, groups):
        """Where each planned row rides in a step of `groups`: ``(at,
        lanes)``, ``at[i]`` its ``(group, row)`` and ``lanes[i]`` its row
        of the decode group, which is also its row of the result: its
        place in the plan or, where the pool holds state a lane, the
        request's own lane, every step (rows between them idle: no live
        block, nothing moves). A row that fits the decode group rides
        there; a wider one takes the chunk group's next row (the scheduler
        plans no more of them than it has) and names its lane there."""
        lanes = ([row.req.lane for row in rows] if self.pool.lane_state
                 else list(range(len(rows))))
        at, chunk_rows = [], 0
        for lane, row in zip(lanes, rows):
            if row.count + len(row.draft) <= groups[0][1]:
                at.append((0, lane))
                continue
            if chunk_rows == groups[1][0]:
                raise AssertionError(
                    f"more than {chunk_rows} rows wider than the decode "
                    f"group in a step whose groups are {groups} — "
                    "scheduler chunk-row capping broke")
            at.append((1, chunk_rows))
            chunk_rows += 1
        return at, lanes

    def _run_rows(self, rows, W, step_id=0):
        """Build and launch one unified ragged step at width bucket `W`;
        returns its `_Launched` (`_finish` reads and emits it). Every
        scheduled row feeds its `count` chunk tokens at positions
        [start, start+count) plus its (possibly empty) drafted candidates
        after them; a decode row whose token the step in flight
        (`_in_flight`) samples names that step's result row in ``src`` and
        the program gathers it there. The program samples each emitting
        row's next token, verifies its drafts, and decides the accepted run
        ON DEVICE. Once launched, the rows' fed tokens count as in the arena
        and an emitting row's sample as unread (`Request.unread`): what the
        next plan may assume before the read."""
        tr = self.tracer
        t_build = time.monotonic() if tr is not None else 0.0
        behind = self._in_flight
        packed, parts = self._row_arrays(W)
        groups = self.step_groups(W)
        lane_state = bool(self.pool.lane_state)
        at, lanes = self._place_rows(rows, groups)
        for (g, i), lane, row in zip(at, lanes, rows):
            req, start, count, k = row.req, row.start, row.count, len(row.draft)
            a = parts[g]
            if g:
                a["lanes"][i] = lane
            if req.unread:
                # its pending token is the step in flight's sample: the
                # device takes it from that step's result
                a["src"][i] = behind.result_row[req]
            elif start == req.num_tokens - 1:
                # decode fast path: the single pending token is always the
                # last one — skip rebuilding prompt+outputs every step
                a["ids"][i, 0] = req.last_token
            else:
                a["ids"][i, :count] = req.all_ids[start:start + count]
            if k:
                # drafts only attach to emitting rows, fed right after
                # the row's pending (last chunk) token
                a["ids"][i, count:count + k] = row.draft
            a["last_idx"][i] = count - 1
            a["spec_lens"][i] = k
            if lane_state:
                # position 0 has no history: the first chunk, also of a
                # request re-admitted after preemption
                a["fresh"][i] = start == 0
            self._fill_row(a, i, req, start, count + k, groups[g][1])
        fn = self._get_step_fn(self.max_batch, W)
        K = self._draft_capacity(W)
        args, phases = {}, {}
        if tr is not None:
            # what the ragged kernel walks for this step, a head a layer,
            # summed over the groups' launches: rows past the planned ones
            # carry no request (q_len 0); and what the append's writer
            # copies back, an arena a layer
            live = total = blocks = 0
            for (_, width), a in zip(groups, parts):
                q_lens = np.where(a["kv_live"] > 0,
                                  a["last_idx"] + 1 + a["spec_lens"], 0)
                lv, tot = ragged_grid_steps(q_lens, a["kv_live"], width,
                                            self.block_size)
                live, total = live + lv, total + tot
                blocks += append_blocks(a["slots"])
            args.update(
                grid_live=live, grid_total=total, append_blocks=blocks,
                # the token slots of the program that ran, beside the
                # `fed_tokens` that were live in them
                slots=sum(n * width for n, width in groups),
                # launched while the step before it was unread
                ahead=int(behind is not None))
        t_disp = time.monotonic() if tr is not None else 0.0
        out = self._run_step(fn, packed, step_id=step_id, t_disp=t_disp,
                             args=args)
        if tr is not None:
            phases.update(build=(t_build, t_disp),
                          dispatch=(t_disp, time.monotonic()))
        if behind is not None:
            self.metrics.inc("steps_launched_ahead")
        for row in rows:
            row.req.num_cached += row.count
            row.req.unread += row.emit
        return _Launched(rows, lanes, W, K, out, step_id, phases, args)

    def _finish(self, s):
        """Read the launched step `s` (THE host sync: one packed [B, K+3]
        transfer carries the emitted runs, accept lengths and row-finite
        flags) and publish it; returns its StepOutputs. A row whose request
        ended or was requeued since the launch is dropped: the token made
        for it is never emitted. Rejected speculative tails roll back:
        their KV slots are stale (overwritten before they are ever
        attended, exactly like any future position) and their reserved
        blocks return to the pool via `reclaim_spec_blocks`. While tracing,
        the step's span is recorded here, with its phases from the launch
        (plan, build, dispatch) and from this read (sync, emit)."""
        tr = self.tracer
        t_sync = time.monotonic() if tr is not None else 0.0
        out = np.asarray(s.out)
        s.read = True
        self.metrics.inc("host_syncs")
        K = s.K
        if self._stat_names:
            n = len(self._stat_names)
            if tr is not None:
                s.args.update(zip(self._stat_names, map(int, out[-n:])))
            out = out[:-n].reshape(self.max_batch, K + 3)
        run, n_accs, row_ok = (out[:, :K + 1], out[:, K + 1],
                               out[:, K + 2])
        if faults._PLAN is not None:
            row_ok = self._corrupt_row_ok(zip(s.lanes, s.rows), row_ok)
        t_emit = time.monotonic() if tr is not None else 0.0
        t_disp = s.phases["dispatch"][0] if tr is not None else 0.0
        outs = []
        for i, row, gen in zip(s.lanes, s.rows, s.gens):
            req, k = row.req, len(row.draft)
            if req.state != RUNNING or req.preemptions != gen:
                continue   # ended or requeued while this step ran
            req.unread -= row.emit
            if not row_ok[i]:
                # NaN/Inf logits: abort this row only — its KV and token
                # are garbage; everyone else's step output is unaffected
                self._poison(req, "nonfinite_logits")
                continue
            n_acc = min(int(n_accs[i]), k)
            if k:
                self.metrics.inc("spec_drafted_rows")
                self.metrics.inc("spec_proposed_tokens", k)
                self.metrics.inc("spec_accepted_tokens", n_acc)
                req.spec_accepted += n_acc
            # the fed run [chunk tokens, accepted drafts] is real sequence
            # content, so its KV is valid: the chunk was counted at the
            # launch, the accepted drafts are counted BEFORE emitting (an
            # eos inside the run finishes the request, and release
            # publishes full prompt blocks off num_cached)
            req.num_cached += n_acc
            if self.policy is not None:
                # fairness accounting charges device work actually
                # consumed: fed chunk tokens + accepted drafts
                self.policy.note_served(req, row.count + n_acc)
            if tr is not None and req.traced:
                tr.row_span(
                    req,
                    ("verify" if k else
                     "prefill_chunk" if row.count > 1 else "decode"),
                    t_disp, t_emit,
                    {"step": s.step_id, "start": row.start,
                     "count": row.count, "emit": row.emit,
                     **({"drafted": k, "accepted": n_acc} if k else {})})
            if not row.emit:
                continue
            # emitted run: accepted drafts then the stop-slot token,
            # already assembled on device
            for t in run[i, :n_acc + 1]:
                outs.append(self._emit(req, int(t)))
                if req.finished:
                    break
            if k and not req.finished:
                self.scheduler.reclaim_spec_blocks(req)
        if tr is not None:
            s.phases.update(sync=(t_sync, t_emit),
                            emit=(t_emit, time.monotonic()))
            rows = s.rows
            tr.record_step(s.step_id, s.kind, s.phases, {
                "rows": len(rows),
                "width": s.W,
                "decode_rows": sum(1 for r in rows
                                   if r.count == 1 and not r.draft),
                "prefill_rows": sum(1 for r in rows if r.count > 1),
                "spec_lanes": sum(1 for r in rows if r.draft),
                "fed_tokens": sum(r.count + len(r.draft) for r in rows),
                "emitted_tokens": len(outs),
                # every fed token runs the layers `loop_steps` times and
                # writes `cache_slabs` slabs; a GPT: 1 and its layers
                "loop_steps": getattr(self.model.cfg, "loop_steps", 1),
                "cache_slabs": self.kv_slabs,
                # tokens of context the rows attend over, this step's
                # included: what attention reads of every slab
                "kv_tokens": sum(r.start + r.count + len(r.draft)
                                 for r in rows),
                **s.args,
            })
        return outs

    def _emit(self, req, token):
        if not req.output_ids:
            now = time.monotonic()
            req.first_token_time = now
            self.metrics.observe(
                "ttft", now - req.arrival_time, interval=False
            )
            if self.slo is not None:
                # the first token closes prefill: decode begins
                self.slo.transition(req, "decode_compute", now)
            if req.traced:
                self.tracer.first_token(req, now)
        req.output_ids.append(token)
        self.metrics.inc("generated_tokens")
        done = (
            len(req.output_ids) >= req.max_new_tokens
            or (req.eos_token_id is not None and token == req.eos_token_id)
        )
        if done:
            if self.slo is not None:
                # `emit` covers final-token bookkeeping: finish, block
                # release/publish, terminal logging (its open timestamp
                # doubles as the last token's emission time for TPOT)
                self.slo.transition(req, "emit")
            self.scheduler.finish(req)
            self.metrics.inc("requests_finished")
            self._finalize(req, "finished")
        return StepOutput(req.request_id, token, done)

    def _finalize(self, req, reason):
        """Request-terminal observability (finish AND abort funnel here):
        close the lifecycle trace span, close the SLO ledger's phase
        clock (rollups + histograms), and emit the one-line JSON summary
        log / feed the flight recorder's tail ring. All no-ops in the
        default configuration."""
        if req.adapter is not None and self._adapter_inflight:
            # adapter pin released on ANY terminal path (finish, abort,
            # policy reject) — unload/LRU only evicts zero-inflight slots
            n = self._adapter_inflight.get(req.adapter, 0)
            if n > 0:
                self._adapter_inflight[req.adapter] = n - 1
        if req.traced:
            self.tracer.end_request(req, reason)
        if self.slo is None:
            return   # request_log/recorder imply a ledger (constructor)
        now = time.monotonic()
        summary = self.slo.finalize(req, reason, now)
        if not self.request_log and self.recorder is None:
            return
        ms = lambda t: None if t is None else round(t * 1e3, 3)  # noqa: E731
        line = {
            "event": "request_done",
            "request_id": str(req.request_id),
            "reason": reason,
            "tenant": req.tenant,
            "priority": req.priority,
            "adapter": req.adapter,
            "policy_reject": (reason if reason.startswith("policy_reject")
                              else None),
            "deadline_s": req.deadline_s,
            "deadline": summary["deadline"],
            "prompt_tokens": len(req.prompt_ids),
            "output_tokens": len(req.output_ids),
            "prefix_hit_tokens": req.prefix_hit_tokens,
            "spec_accepted_tokens": req.spec_accepted,
            "preemptions": req.preemptions,
            "queue_wait_ms": ms(None if req.admit_time is None
                                else req.admit_time - req.arrival_time),
            "ttft_ms": ms(summary["ttft_s"]),
            "tpot_ms": ms(summary["tpot_s"]),
            # the ledger's e2e, so the line's phase_<name>_ms fields sum
            # to total_ms by construction (the tested invariant)
            "total_ms": ms(summary["e2e_s"]),
        }
        for p, v in summary["phases_ms"].items():
            line[f"phase_{p}_ms"] = v
        if self.recorder is not None:
            self.recorder.note_request_line(line)
        if self.request_log:
            _request_log.info(json.dumps(line, sort_keys=True))

    def pool_stats(self):
        """Saturation gauges for /healthz (serving/server.py) and
        operators: block-pool occupancy split by tier plus scheduler queue
        depths — enough to see saturation without scraping /metrics."""
        usable = self.pool.num_blocks - 1
        stats = {
            "kv_dtype": self.pool.kv_dtype,
            "kv_bytes_per_block": self.pool.bytes_per_block(),
            "blocks_total": usable,
            "blocks_truly_free": self.pool.num_truly_free,
            "blocks_cached_free": self.pool.num_cached_blocks,
            "blocks_allocated": usable - self.pool.num_free,
            "requests_running": len(self.scheduler.running),
            "requests_waiting": len(self.scheduler.waiting),
        }
        if self.tier is not None:
            # host-tier occupancy + swap/migration counters ride the same
            # dict, so /healthz "pool" and the /metrics pool_* gauges can
            # never disagree (they both render exactly this)
            stats.update(self.tier.stats())
        if self.policy is not None:
            # dict-valued: the server's numeric-only pool_* gauge filter
            # skips it, /healthz renders it verbatim
            stats["policy"] = self.policy.snapshot(
                waiting=self.scheduler.waiting,
                running=self.scheduler.running)
        if self.lora_slots:
            stats["lora"] = {
                "slots": self.lora_slots,
                "rank": self.lora_rank,
                "loaded": sorted(self._adapters),
                "inflight": {k: v for k, v in
                             self._adapter_inflight.items() if v},
            }
        return stats

    # -- host-tier migration (serving/router.py drain/eject hooks) ---------

    def export_kv_tier(self, demote=True):
        """Serialize this engine's reusable prefix blocks for an
        in-process handoff to another replica (the router's rolling-drain
        / ejection migration). With ``demote=True`` every DEVICE
        cached-free block is first saved into the host tier (the blocks
        stay device-resident and matchable — demotion copies, it does not
        evict), so a drained replica hands over its full warm set, not
        just what eviction pressure already spilled. Returns the payload
        for `import_kv_tier`, or None when the tier is off.

        ``demote=True`` requires a QUIESCENT (drained/idle) engine — it
        gathers from the device arena. ``demote=False`` is safe on a
        LIVE engine (the ejection path): it only reads settled host
        slabs under the tier lock, skipping in-flight saves."""
        if self.tier is None:
            return None
        if demote:
            for b, h in self.pool.cached_blocks():
                self.tier.save(h, b)
            self.tier.settle()
        return self.tier.export()

    def import_kv_tier(self, payload):
        """Adopt another replica's exported host tier into ours (geometry
        must match — see `KVTier.import_payload`). Returns blocks
        imported (0 when the tier is off or payload is None)."""
        if self.tier is None or payload is None:
            return 0
        return self.tier.import_payload(payload)

    def close(self):
        """Release engine-owned background resources (the tier's drain
        thread). Idempotent; safe on a tierless engine."""
        if self.tier is not None:
            self.tier.close()

    # -- conveniences ------------------------------------------------------

    def _guard_thread(self, what):
        """The PR 16 race, closed at the throat: while an AsyncLLMEngine's
        background loop owns this engine, any OTHER thread calling the
        synchronous drive surface would interleave two schedulers over one
        block pool and one donated arena — silent KV corruption at worst,
        a trace-cache stampede at best. The async frontend stamps its
        thread into ``_engine_thread`` on start(); a live foreign caller
        gets a pointed error instead of corrupted state. The owning
        thread itself passes (that IS the async loop stepping)."""
        owner = self._engine_thread
        if (owner is not None and owner.is_alive()
                and threading.current_thread() is not owner):
            raise RuntimeError(
                f"{what} called while an AsyncLLMEngine background loop "
                f"({owner.name}) is driving this engine — two schedulers "
                "would interleave over one block pool. Submit through "
                "the AsyncLLMEngine (submit()/stream()), or stop() it "
                "before driving the engine synchronously."
            )

    def stream(self, prompt_ids, **kwargs):
        """Add one request and yield its StepOutputs as tokens land; other
        in-flight requests keep decoding in the same steps."""
        self._guard_thread("stream()")
        rid = self.add_request(prompt_ids, **kwargs)
        req = self._requests[rid]
        emitted = 0
        while True:
            if emitted < len(req.output_ids):
                tok = req.output_ids[emitted]
                emitted += 1
                last = req.finished and emitted == len(req.output_ids)
                yield StepOutput(rid, tok, last)
                if last:
                    self.release(rid)
                    return
                continue
            if req.finished:
                self.release(rid)
                return
            self.step()

    def generate(self, prompts, **kwargs):
        """Batch convenience: add every prompt, run to completion, return
        each request's generated token list (in input order)."""
        self._guard_thread("generate()")
        rids = [self.add_request(p, **kwargs) for p in prompts]
        while self.has_unfinished():
            self.step()
        outs = [list(self._requests[r].output_ids) for r in rids]
        for r in rids:
            self.release(r)
        return outs
