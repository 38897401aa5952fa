"""Tiered KV cache (serving/kv_tier.py): host-memory block tier with
swap-back, cross-replica migration, and zero-rewarm drains.

The acceptance bar is TOKEN parity: a greedy serve whose prefix lands as
host-tier hits must be token-for-token identical to a cold serve and to a
device-warm serve — single-chip, tp=2 (per-shard slabs), and with
speculative decoding on. Around that anchor: churn-sweep accounting
(refcounts drain, host slots balance, pool returns to idle across
swap-in/swap-out/COW/preempt/abort interleavings), the /debug/kvtier
surfaces, the /healthz-vs-/metrics pool agreement with the new host-tier
gauges, the rolling-drain migration handoff (zero failed requests,
post-drain host hits), and a witnessed churn serve covering
``KVTier._lock`` (acyclic, JL009-covered).
"""
import asyncio
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import (
    AsyncLLMEngine,
    LLMEngine,
    ReplicaRouter,
    RouterServer,
    ServingServer,
)

from test_serving_router import _parse_prom


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def ref_engine(model):
    """One shared no-tier engine for reference outputs (the chaos-file
    discipline: fresh step programs per reference run would dominate
    this file's wall time)."""
    return LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("host_kv_blocks", 24)
    return LLMEngine(model, **kw)


def _idle(engine):
    assert engine.pool._refcount == {}
    assert engine.pool.num_free == engine.pool.num_blocks - 1


def _tier_consistent(tier):
    """Host-slot conservation: every slot is exactly one of free or
    indexed, and nothing is pending after a settle."""
    tier.settle()
    with tier._lock:
        assert tier._pending == {}
        assert tier._save_buf == []
        used = set(tier._index.values())
        assert len(used) == len(tier._index)          # no slot aliasing
        assert used.isdisjoint(tier._free_slots)
        assert len(used) + len(tier._free_slots) == tier.host_blocks


def _churn(engine, rounds=3, seed=5):
    """Over-capacity distinct-prefix traffic: fills the device pool and
    forces LRU evictions (host-tier demotions) every round."""
    for r in range(rounds):
        engine.generate(_prompts((17, 25, 19), seed=seed + 7 * r),
                        max_new_tokens=4, temperature=0.0)


# -- token parity: host-warm == cold == device-warm ---------------------------


def test_host_warm_matches_cold_and_device_warm(model, ref_engine):
    """THE tier acceptance criterion, single-chip: a document prompt is
    served cold, churned out of the device cache (demoted to host), then
    re-served — the re-serve must swap blocks BACK in (swap_ins > 0) and
    emit tokens identical to the cold serve. A back-to-back device-warm
    re-serve (no churn) stays identical too and never touches the tier."""
    doc = _prompts((24,), seed=1)[0]                   # three full blocks
    tails = _prompts((3, 5), seed=2)
    prompts = [doc + t for t in tails]
    refs = ref_engine.generate(prompts, max_new_tokens=6, temperature=0.0)

    engine = _engine(model, num_blocks=12)             # 11 usable: tight
    cold = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert cold == refs

    warm = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert warm == refs                                # device-warm
    ins_before = engine.tier.swap_ins

    _churn(engine)                                     # demote doc blocks
    engine.tier.settle()
    assert engine.tier.swap_outs > 0
    hostwarm = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    assert hostwarm == refs                            # host-warm parity
    assert engine.tier.swap_ins > ins_before           # came from host
    assert engine.tier.swap_in_hit_tokens >= \
        (engine.tier.swap_ins - ins_before) * engine.pool.block_size
    _idle(engine)
    _tier_consistent(engine.tier)
    engine.close()


def test_tp2_per_shard_slabs_and_spec_on_parity(model, ref_engine):
    """tp=2 + speculative decoding: the tier keeps one slab per head
    range (no cross-chip gather on save), and a host-warm serve stays
    token-identical to the single-chip cold reference."""
    doc = _prompts((24,), seed=1)[0]
    tails = _prompts((3, 5), seed=2)
    prompts = [doc + t for t in tails]
    refs = ref_engine.generate(prompts, max_new_tokens=6, temperature=0.0)

    engine = _engine(model, num_blocks=12, mesh=2, spec_decoding=True,
                     num_spec_tokens=3)
    tier = engine.tier
    assert [(h0, h1) for h0, h1, _, _ in tier._slabs] == [(0, 1), (1, 2)]
    assert engine.generate(prompts, max_new_tokens=6,
                           temperature=0.0) == refs
    _churn(engine)
    tier.settle()
    assert tier.swap_outs > 0
    assert engine.generate(prompts, max_new_tokens=6,
                           temperature=0.0) == refs   # host-warm parity
    assert tier.swap_ins > 0
    _idle(engine)
    _tier_consistent(tier)
    engine.close()


def test_cross_topology_migration_parity(model, ref_engine):
    """Migration is topology-portable: a tp=2 engine's export (payloads
    are full-logical [L, H, bs, D]) imports into a single-chip engine
    and serves host-warm tokens identical to the cold reference."""
    doc = _prompts((24,), seed=1)[0]
    prompts = [doc + t for t in _prompts((3,), seed=2)]
    refs = ref_engine.generate(prompts, max_new_tokens=6, temperature=0.0)

    src = _engine(model, num_blocks=12, mesh=2)
    src.generate(prompts, max_new_tokens=6, temperature=0.0)
    payload = src.export_kv_tier(demote=True)          # quiescent: demote
    assert payload is not None and payload["entries"]

    dst = _engine(model, num_blocks=12)
    n = dst.import_kv_tier(payload)
    assert n == len(payload["entries"])
    assert dst.tier.migrated_blocks_in == n
    assert dst.generate(prompts, max_new_tokens=6, temperature=0.0) == refs
    assert dst.tier.swap_ins > 0                       # served FROM import
    # geometry mismatches refuse loudly instead of serving foreign KV
    bad = dict(payload, block_size=payload["block_size"] + 1)
    with pytest.raises(ValueError, match="geometry mismatch"):
        dst.import_kv_tier(bad)
    src.close()
    dst.close()


# -- churn sweep: accounting across interleavings -----------------------------


def test_churn_sweep_interleavings_leave_pool_and_tier_idle(model):
    """Randomized rounds of shared-prefix traffic over a pool too small
    to hold it — swap-outs, swap-back hits, COW on shared tails,
    preemption, and mid-flight aborts all interleave — and EVERY round
    ends with refcounts drained, the pool's free count restored, and the
    host tier's slot accounting balanced."""
    rs = np.random.RandomState(11)
    engine = _engine(model, num_blocks=10, block_size=4, host_kv_blocks=16,
                     host_swap_chunk=2)
    prefixes = [rs.randint(0, 128, (12,)).tolist() for _ in range(3)]
    idle_free = engine.pool.num_free
    for rnd in range(4):
        reqs = []
        for _ in range(int(rs.randint(3, 6))):
            p = (prefixes[rs.randint(len(prefixes))]
                 + rs.randint(0, 128, (rs.randint(0, 7),)).tolist())
            reqs.append(engine.add_request(
                p, max_new_tokens=int(rs.randint(2, 7)), temperature=0.0))
        doomed = set(rs.choice(reqs, size=len(reqs) // 3,
                               replace=False).tolist())
        steps = 0
        while engine.has_unfinished():
            engine.step()
            steps += 1
            if steps == 2:
                for rid in doomed:
                    engine.abort(rid)
        for rid in reqs:
            if rid not in doomed:
                engine.release(rid)
        assert engine.pool._refcount == {}, f"round {rnd}"
        assert engine.pool.num_free == idle_free, f"round {rnd}"
        _tier_consistent(engine.tier)
    assert engine.tier.swap_outs > 0          # the sweep exercised demotion
    assert engine.tier.swap_ins > 0           # ... and swap-back
    assert engine.metrics.counters.get("preemptions", 0) > 0
    assert engine.metrics.counters.get("prefix_cache_cow_copies", 0) > 0
    engine.close()


def test_tier_lru_eviction_keeps_newest(model):
    """Host capacity pressure: with a tier smaller than the churn, the
    OLDEST host entries are evicted and the slot accounting still
    balances (no leak, no aliasing)."""
    engine = _engine(model, num_blocks=10, host_kv_blocks=4)
    _churn(engine, rounds=4)
    tier = engine.tier
    _tier_consistent(tier)
    with tier._lock:
        assert len(tier._index) == tier.host_blocks       # full, not over
    assert tier.swap_outs > tier.host_blocks              # evicted + reused
    engine.close()


# -- observability: /debug/kvtier + pool agreement ----------------------------


async def _http(port, method, path, obj=None):
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(obj).encode() if obj is not None else b""
    writer.write(
        (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
         f"Content-Type: application/json\r\n"
         f"Content-Length: {len(data)}\r\n\r\n").encode() + data)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, OSError):
        pass
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ")[1]), body


def test_debug_kvtier_endpoint_and_pool_agreement(model):
    """/debug/kvtier 404s with a hint when the tier is off, dumps the
    snapshot when on; the /healthz pool dict (now carrying host-tier
    stats) agrees number-for-number with the /metrics pool_* gauges, and
    every new family is HELP'd and TYPE'd (the exposition lock)."""
    doc = _prompts((24,), seed=1)[0]

    async def main():
        eng_off = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
        off = ServingServer(eng_off, host="127.0.0.1", port=0)
        await off.start()
        off_status, off_body = await _http(off.port, "GET", "/debug/kvtier")
        await off.shutdown()

        eng = _engine(model, num_blocks=12)
        server = ServingServer(eng, host="127.0.0.1", port=0)
        await server.start()
        await server.engine.submit(
            doc, max_new_tokens=4, temperature=0.0).collect()
        # churn THROUGH the frontend (the engine thread owns step
        # dispatch — a direct generate would race arena donation)
        for r in range(2):
            for p in _prompts((17, 25, 19), seed=5 + 7 * r):
                await server.engine.submit(
                    p, max_new_tokens=4, temperature=0.0).collect()
        await asyncio.to_thread(eng.tier.settle)
        # re-serve the doc: its churned-out blocks swap back in, so the
        # swap_ins counter families render on the scrape below
        await server.engine.submit(
            doc, max_new_tokens=4, temperature=0.0).collect()
        dbg = await _http(server.port, "GET", "/debug/kvtier")
        met = await _http(server.port, "GET", "/metrics")
        hz = await _http(server.port, "GET", "/healthz")
        await server.shutdown()
        return off_status, off_body, dbg, met, hz

    off_status, off_body, dbg, met, hz = asyncio.run(main())
    assert off_status == 404
    assert b"host_kv_blocks" in off_body                 # the hint

    assert dbg[0] == 200
    snap = json.loads(dbg[1])
    assert snap["host_blocks_total"] == 24
    assert snap["swap_outs"] > 0
    assert snap["host_blocks_used"] == len(snap["resident"])
    assert snap["shards"] == [[0, 2]]                    # single-chip slab
    assert snap["block_shape"][3] * snap["block_shape"][1] == 32  # H*D

    text = met[1].decode()
    types, samples = _parse_prom(text)                   # every line parses
    pre = "paddle_tpu_serving_"
    gauges = {n: v for n, lab, v in samples if n.startswith(pre + "pool_")}
    health = json.loads(hz[1])
    want = {f"{pre}pool_{k}": float(v) for k, v in health["pool"].items()
            if not isinstance(v, str)}                   # kv_dtype: info fam
    assert gauges == want                                # same live numbers
    assert gauges[pre + "pool_host_blocks_total"] == 24
    assert gauges[pre + "pool_swap_outs"] > 0
    for fam in ("pool_host_blocks_total", "pool_host_blocks_used",
                "pool_swap_ins", "pool_swap_outs", "pool_swap_in_hit_tokens",
                "pool_migrated_blocks_out", "pool_migrated_blocks_in"):
        assert types[pre + fam] == "gauge", fam
        assert f"# HELP {pre}{fam} " in text, fam
    # the tier's own counters are first-class families too
    for fam in ("swap_ins_total", "swap_outs_total",
                "swap_in_hit_tokens_total"):
        assert pre + fam in {n for n, _, _ in samples}, fam


def test_router_debug_kvtier_merges_replicas(model):
    """The fleet view: RouterServer /debug/kvtier returns one snapshot
    per replica keyed by name (404 with a hint when no replica runs the
    tier)."""
    async def main():
        bare = ReplicaRouter(
            [AsyncLLMEngine(LLMEngine(model, block_size=8, max_batch=4,
                                      max_seq_len=64)) for _ in range(2)],
            sweep_interval_s=0.05)
        off = RouterServer(bare, port=0)
        await off.start()
        off_resp = await _http(off.port, "GET", "/debug/kvtier")
        await off.shutdown()

        router = ReplicaRouter(
            [AsyncLLMEngine(_engine(model)) for _ in range(2)],
            sweep_interval_s=0.05)
        server = RouterServer(router, port=0)
        await server.start()
        resp = await _http(server.port, "GET", "/debug/kvtier")
        await server.shutdown()
        return off_resp, resp

    off_resp, resp = asyncio.run(main())
    assert off_resp[0] == 404 and b"host_kv_blocks" in off_resp[1]
    assert resp[0] == 200
    snaps = json.loads(resp[1])
    assert set(snaps) == {"r0", "r1"}
    assert all(s["host_blocks_total"] == 24 for s in snaps.values())


# -- zero-rewarm drains -------------------------------------------------------


def test_rolling_drain_migrates_and_serves_host_warm(model, ref_engine):
    """THE drain acceptance criterion: a rolling drain with a factory
    restarts every replica, the old home's cache rides along through the
    host tier (router_migrations fires), zero requests fail, and a
    post-drain re-serve of the warmed prefixes hits the NEW engines'
    host tier (swap_ins > 0) with token-identical output."""
    shared = _prompts((16,), seed=3)[0]
    prompts = [shared + t for t in _prompts((3, 5, 4), seed=4)]
    refs = ref_engine.generate(prompts, max_new_tokens=6, temperature=0.0)

    def factory(i):
        return AsyncLLMEngine(_engine(model, num_blocks=12))

    async def main():
        router = ReplicaRouter(
            [AsyncLLMEngine(_engine(model, num_blocks=12))
             for _ in range(2)],
            factory=factory, sweep_interval_s=0.05)
        await router.start()
        warm = [await (await router.submit(
            p, max_new_tokens=6, temperature=0.0)).collect()
            for p in prompts]
        # live traffic THROUGH the drain: nothing may fail
        streams = [await router.submit(p, max_new_tokens=6, temperature=0.0)
                   for p in prompts]
        drained = await router.rolling_drain()
        mid = [await s.collect() for s in streams]
        post = [await (await router.submit(
            p, max_new_tokens=6, temperature=0.0)).collect()
            for p in prompts]
        swap_ins = sum(r.engine.engine.tier.swap_ins
                       for r in router.replicas)
        c = dict(router.metrics.counters)
        await router.shutdown()
        return drained, warm, mid, post, swap_ins, c

    drained, warm, mid, post, swap_ins, c = asyncio.run(main())
    assert drained == ["r0", "r1"]
    assert c["router_restarts"] == 2
    assert c["router_migrations"] >= 1
    assert c["router_migrated_blocks"] > 0
    assert c.get("router_requests_failed", 0) == 0       # zero-rewarm AND
    for got, ref in zip(warm + mid + post, refs * 3):    # zero-failure
        toks, reason = got
        assert reason == "length" and toks == ref
    # the post-drain serve was warmed from the MIGRATED host blocks, not
    # recompute: the fresh engines swapped prefix blocks back in
    assert swap_ins > 0


def test_ejection_salvages_host_tier_to_live_replicas(model):
    """The live-export path (demote=False): salvaging an ejected
    replica's SETTLED host blocks into its peers touches only slabs —
    safe on a non-quiescent engine — and the peers adopt them."""
    async def main():
        router = ReplicaRouter(
            [AsyncLLMEngine(_engine(model, num_blocks=10))
             for _ in range(2)],
            sweep_interval_s=0.05)
        await router.start()
        victim = router.replicas[0]
        # force demotions on the victim so its host tier holds blocks
        # (through its frontend — the engine thread owns step dispatch)
        eng = victim.engine.engine
        for r in range(2):
            for p in _prompts((17, 25, 19), seed=5 + 7 * r):
                await victim.engine.submit(
                    p, max_new_tokens=4, temperature=0.0).collect()
        await asyncio.to_thread(eng.tier.settle)
        await router._migrate_from(victim)
        peer = router.replicas[1].engine.engine
        c = dict(router.metrics.counters)
        got = peer.tier.migrated_blocks_in
        await router.shutdown()
        return c, got

    c, got = asyncio.run(main())
    assert c["router_migrations"] == 1
    assert got > 0 and c["router_migrated_blocks"] == got


# -- concurrency: the witness covers KVTier._lock -----------------------------


def test_witnessed_tier_churn_acyclic_and_covered(model):
    """A witnessed churn serve with the tier on: the drain thread's slab
    writes, the engine thread's flush/restore, and a loop-thread debug
    snapshot all take ``KVTier._lock`` concurrently — the observed graph
    must be acyclic, must contain the tier's lock, and every observed
    edge must be covered by the static JL009 model (gaps == [])."""
    from paddle_tpu.analysis import witness

    w = witness.install()
    try:
        engine = _engine(model, num_blocks=10, slo=True)
        _churn(engine, rounds=2)
        engine.tier.debug_snapshot()        # scrape-thread acquisition
        engine.tier.settle()
        engine.slo.rollup()
        _idle(engine)
        engine.close()
        w.check_acyclic()
        g = w.observed_graph()
        assert any("kv_tier.py" in n["ctor"] for n in g["nodes"]), g["nodes"]
        gaps = witness.cross_check(w)
        assert gaps == [], "\n".join(gaps)
    finally:
        witness.uninstall()
