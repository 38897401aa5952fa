"""paddle_tpu.serving — continuous-batching LLM engine with a paged KV cache.

The production decode path the ROADMAP north-star asks for: `LLMEngine`
admits requests mid-flight (FCFS, chunked prefill under a per-step token
budget, preemption-by-recompute), stores K/V in a head-major block-paged
arena (PAPERS.md "Ragged Paged Attention"), attends through a ragged
Pallas kernel on TPU (XLA gather fallback elsewhere,
ops/pallas/paged_attention.py), and compiles at most THREE XLA programs —
one mixed prefill+decode step, one pure-decode step, and (speculative
decoding only) one verify step — regardless of traffic or prompt lengths.
Automatic prefix caching (ref-counted content-hashed blocks with a
cached-free LRU tier and copy-on-write) is on by default — shared system
prompts/few-shot templates skip their prefill on every hit; disable with
``LLMEngine(prefix_cache=False)``. A host-memory KV tier
(serving/kv_tier.py, ``LLMEngine(host_kv_blocks=N)``) catches cached
blocks the device LRU evicts, swaps them back on a prefix hit via a
donated scatter dispatched at plan time, and doubles as the fleet's
block-transport substrate for zero-rewarm drains and cross-replica
migration. See README "Tiered KV cache".
Speculative decoding (serving/spec.py: prompt-lookup n-gram drafting +
batched parallel verification, no draft model) is OFF by default — enable
with ``LLMEngine(spec_decoding=True)`` to score up to
``num_spec_tokens + 1`` decode positions per step; greedy outputs stay
token-for-token identical to non-speculative decode.

**Tensor-parallel serving** (serving/sharded.py): pass ``mesh=N`` (or a
`build_serving_mesh` handle) to shard weights and the head-major KV arena
over a ``tp`` NamedSharding mesh — attention heads and FFN columns on
``tp``, block tables/scheduler/prefix-cache refcounts host-side and
unchanged, still one unified ragged program compiled per width bucket
programs. Greedy sharded output is token-for-token identical to the
single-chip engine. See README "Sharded serving".

**Replica-fleet routing** (serving/router.py): `ReplicaRouter` fronts N
`AsyncLLMEngine` replicas (each optionally tp-sharded) — shared prefixes
consistent-hash to a home replica so the prefix-cache win survives
fan-out, cache-cold traffic spreads least-loaded, and the PR 9 health
states drive ejection, half-open probe re-admission, retry-elsewhere
(safe-retry: only zero-token requests replay), deadline-aware early
rejection, and rolling drain. `RouterServer` (server.py, or
``python -m paddle_tpu.serving.server --replicas N``) is the fleet HTTP
surface. See README "Fleet routing".

**Elastic fleet** (serving/lifecycle.py + serving/autoscale.py +
distributed/checkpoint.py streaming load): replicas are born by
streaming a sharded checkpoint straight to mesh placement —
``LLMEngine(checkpoint_path=..., mesh=N)`` on a ``skeleton_init()``
model never materializes the full tree on any host or chip
(``param_hbm_bytes`` asserts the bound) — carry an explicit
cold → loading → warm → serving → draining → stopped lifecycle
(`ReplicaLifecycle`, on ``/healthz`` and ``/metrics``; ``warmup=True``
precompiles every width bucket so the first served request retraces
nothing), and are spawned/retired by the SLO-driven `AutoScaler` on the
router (windowed deadline attainment + predicted queue wait →
factory-spawned scale-up with a measured spawn-TTFT bound, drain +
KV-migration scale-down; decisions at ``GET /debug/autoscale``). See
README "Elastic fleet".

Quickstart::

    from paddle_tpu.models.gpt import gpt_tiny
    from paddle_tpu.serving import LLMEngine

    engine = LLMEngine(gpt_tiny(attn_impl="xla"), block_size=16, max_batch=4)
    rid = engine.add_request([1, 2, 3], max_new_tokens=8)   # non-blocking
    for out in engine.stream([4, 5, 6, 7], max_new_tokens=8):
        print(out.token, out.finished)                       # overlaps rid
    print(engine.get_request(rid).output_ids)
    print(engine.metrics.snapshot())

The async serving frontend (`AsyncLLMEngine` in frontend.py) runs the step
loop in a background thread and fans tokens out to per-request asyncio
streams with admission control, deadlines, cancellation, and graceful
drain — and runs every step under the fault-tolerance layer
(supervisor.py): poison-request isolation by bisection, a stuck-step
watchdog, crash-safe thread exit, and non-finite containment, all
testable on demand via deterministic fault injection (faults.py,
`faults.install`; the demo server arms ``PADDLE_TPU_FAULTS``). See README
"Failure model".
`ServingServer` (server.py, stdlib-only) exposes it over HTTP:
OpenAI-style `/v1/completions` with SSE streaming, `/healthz` (with pool
saturation gauges), and a Prometheus `/metrics` endpoint. Observability
(serving/trace.py, ``LLMEngine(trace=...)``): a ring-buffered per-request
lifecycle + engine-step tracer exporting Perfetto-loadable JSON at
``GET /debug/trace``, joinable to device xplane captures by step id;
``request_log=True`` adds one JSON summary log line per request. The SLO
ledger (serving/slo.py, ``slo=True``) decomposes every request's wall
time into exhaustive phases (queued / prefill / decode / preempted /
stalled / emit — they sum to e2e by construction), rolls up
per-tenant/priority classes (p95 TTFT, TPOT, deadline attainment) at
``GET /debug/slo``, and exports true labeled Prometheus histograms; the
fault flight recorder (serving/postmortem.py, ``postmortem_dir=``) writes
one pruned on-disk postmortem bundle per supervisor fault event, listable
at ``GET /debug/postmortem``.
See README "Observability".
"""
from . import faults  # noqa: F401
from .block_pool import (  # noqa: F401
    BlockPool,
    PagedState,
    chain_block_hashes,
)
from .engine import LLMEngine, StepOutput  # noqa: F401
from .faults import FaultInjected, FaultPlan, FaultPoint  # noqa: F401
from .frontend import (  # noqa: F401
    AsyncLLMEngine,
    EngineClosedError,
    EngineOverloadedError,
    RequestStream,
)
from .autoscale import AutoScaler  # noqa: F401
from .kv_tier import KVTier  # noqa: F401
from .lifecycle import LifecycleError, ReplicaLifecycle  # noqa: F401
from .metrics import ServingMetrics  # noqa: F401
from .policy import SchedulingPolicy, as_policy  # noqa: F401
from .postmortem import FlightRecorder  # noqa: F401
from .router import (  # noqa: F401
    Replica,
    ReplicaRouter,
    RoutedStream,
)
from .scheduler import Request, Scheduler  # noqa: F401
from .slo import SLOLedger  # noqa: F401
from .server import RouterServer, ServingServer  # noqa: F401
from .sharded import (  # noqa: F401
    ServingMesh,
    as_serving_mesh,
    build_serving_mesh,
    kv_capacity_blocks,
    serving_collective_budget,
    serving_param_specs,
)
from .spec import NgramDrafter, apply_top_k_top_p  # noqa: F401
from .supervisor import (  # noqa: F401
    EngineHealth,
    EngineSupervisor,
    StepWatchdog,
)
from .trace import EngineTracer  # noqa: F401
