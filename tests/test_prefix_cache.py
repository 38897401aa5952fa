"""Automatic prefix caching: ref-counted KV block reuse with COW + LRU.

Acceptance criteria from the prefix-caching issue:

- serving the same prompt list twice (second pass warm) yields
  token-identical output to a cold-cache serve, with
  ``prefix_cache_hit_tokens > 0`` on the warm pass;
- `copy_blocks` backs a real copy-on-write path (src immutable after the
  copy, dst independently writable);
- after ANY interleaving of cache hits, COW appends, preemptions, and
  aborts, every block's refcount is 0 in the free/cached tiers and
  ``num_free`` returns to the idle count (the churn sweep is `slow`; a
  smoke variant stays in tier-1).
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import BlockPool, LLMEngine, chain_block_hashes
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.scheduler import Request, Scheduler


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _prompts(lengths, seed=0, shared=0):
    rs = np.random.RandomState(seed)
    prefix = rs.randint(0, 128, (shared,)).tolist()
    return [prefix + rs.randint(0, 128, (n - shared,)).tolist()
            for n in lengths]


def _reference(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n, temperature=0.0)
    return out.numpy()[0, len(prompt):].tolist()


def _pool(num_blocks=16, block_size=4, metrics=None):
    return BlockPool(num_blocks=num_blocks, num_layers=1,
                     block_size=block_size, num_heads=1, head_dim=4,
                     metrics=metrics)


def assert_pool_idle(pool):
    """Every block is at refcount 0 in the free or cached tier, the two
    hash maps are exact inverses, and num_free is back to the idle count."""
    assert pool._refcount == {}
    assert pool.num_free == pool.num_blocks - 1
    assert {h: b for b, h in pool._block_hash.items()} == pool._hash_index
    for b in pool._cached:
        assert b in pool._block_hash
    tiers = set(pool._free) | set(pool._cached)
    assert len(tiers) == pool.num_blocks - 1 and 0 not in tiers


# -- hashing ---------------------------------------------------------------

def test_chain_block_hashes_commit_to_whole_prefix():
    a = chain_block_hashes([1, 2, 3, 4, 5, 6, 7, 8, 9], 4)
    b = chain_block_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
    assert len(a) == 2 and a == b  # partial tail block hashes nothing
    # divergence in block 0 changes EVERY downstream hash (chained)
    c = chain_block_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)
    assert c[0] != a[0] and c[1] != a[1]
    # same block-0, divergent block-1
    d = chain_block_hashes([1, 2, 3, 4, 9, 6, 7, 8], 4)
    assert d[0] == a[0] and d[1] != a[1]
    assert chain_block_hashes([1, 2, 3], 4) == []


# -- pool tiers ------------------------------------------------------------

def test_release_publishes_to_cached_tier_and_matches():
    pool = _pool()
    hashes = chain_block_hashes(list(range(8)), 4)
    blocks = pool.allocate(2)
    assert pool.num_free == 13
    pool.release(blocks, hashes)
    # cached-free: both tiers count as free, blocks matchable
    assert pool.num_free == 15 and pool.num_cached_blocks == 2
    hit = pool.match_prefix(hashes)
    assert hit == blocks and pool.refcount(hit[0]) == 1
    assert pool.num_free == 13 and pool.num_cached_blocks == 0
    # a second request shares the SAME pinned blocks (live sharing)
    hit2 = pool.match_prefix(hashes)
    assert hit2 == blocks and pool.refcount(hit[0]) == 2
    pool.release(hit, hashes)
    pool.release(hit2, hashes)
    assert_pool_idle(pool)


def test_match_stops_at_first_miss():
    pool = _pool()
    hashes = chain_block_hashes(list(range(12)), 4)
    blocks = pool.allocate(3)
    pool.release(blocks, hashes[:2])  # block 2 never published
    assert pool.match_prefix(hashes) == blocks[:2]
    other = chain_block_hashes(list(range(50, 62)), 4)
    assert pool.match_prefix(other) == []  # miss at block 0 pins nothing
    pool.release(blocks[:2])


def test_allocate_prefers_truly_free_and_evicts_lru():
    metrics = ServingMetrics()
    pool = _pool(num_blocks=6, metrics=metrics)  # 5 usable
    h1 = chain_block_hashes([1] * 4, 4)
    h2 = chain_block_hashes([2] * 4, 4)
    b1 = pool.allocate(1)
    b2 = pool.allocate(1)
    pool.release(b1, h1)  # cached first -> LRU-oldest
    pool.release(b2, h2)
    # 3 truly free + 2 cached; allocating 3 must not touch the cache
    assert pool.allocate(3) is not None
    assert pool.num_cached_blocks == 2 and pool.evictions == 0
    # 4th allocation evicts the LRU entry (b1), keeping b2 matchable
    got = pool.allocate(1)
    assert got == b1 and pool.evictions == 1
    assert metrics.counters["prefix_cache_evictions"] == 1
    assert pool.match_prefix(h1) == [] and pool.match_prefix(h2) == b2
    # b2's pin took the last free block: the pool is truly dry now
    assert pool.allocate(1) is None


def test_match_refreshes_lru_position():
    pool = _pool(num_blocks=6)
    h1 = chain_block_hashes([1] * 4, 4)
    h2 = chain_block_hashes([2] * 4, 4)
    b1 = pool.allocate(1)
    b2 = pool.allocate(1)
    pool.release(b1, h1)
    pool.release(b2, h2)
    # touch b1: match + release moves it to the MRU end
    pool.release(pool.match_prefix(h1), h1)
    pool.allocate(3)          # drain truly-free
    evicted = pool.allocate(1)  # evicts the LRU entry — now b2
    assert evicted == b2
    assert pool.match_prefix(h1) == b1 and pool.match_prefix(h2) == []


def test_refcount_underflow_and_null_guards():
    pool = _pool()
    blocks = pool.allocate(2)
    pool.release(blocks)
    with pytest.raises(ValueError, match="double free"):
        pool.release([blocks[0]])
    with pytest.raises(ValueError, match="null"):
        pool.release([0])
    # shared block: each holder releases exactly once, the third raises
    h = chain_block_hashes([7] * 4, 4)
    b = pool.allocate(1)
    pool.release(b, h)
    pool.match_prefix(h)
    pool.match_prefix(h)
    pool.release(b)
    pool.release(b, h)
    with pytest.raises(ValueError, match="double free"):
        pool.release(b)
    assert pool.match_prefix(h) == b  # still cached after the guard fired
    pool.release(b, h)


def test_duplicate_content_release_frees_truly():
    pool = _pool()
    h = chain_block_hashes([3] * 4, 4)
    b1 = pool.allocate(1)
    b2 = pool.allocate(1)
    pool.release(b1, h)       # b1 owns the hash
    pool.release(b2, h)       # duplicate content -> truly free, no alias
    assert pool.num_cached_blocks == 1
    assert pool.match_prefix(h) == b1
    pool.release(b1, h)
    assert_pool_idle(pool)


def test_hashless_release_of_published_block_drops_index_entry():
    pool = _pool()
    h = chain_block_hashes([5] * 4, 4)
    b = pool.allocate(1)
    pool.release(b, h)
    pinned = pool.match_prefix(h)
    pool.release(pinned)  # e.g. tail block partially rewritten: no hash
    assert pool.match_prefix(h) == []  # never hands out a freed block
    assert_pool_idle(pool)


# -- copy-on-write ---------------------------------------------------------

def test_copy_blocks_src_immutable_dst_independent():
    import jax.numpy as jnp

    pool = _pool(num_blocks=8)
    (src,) = pool.allocate(1)
    pool.k = pool.k.at[:, :, src].set(3.0)
    pool.v = pool.v.at[:, :, src].set(4.0)
    (dst,) = pool.allocate(1)
    pool.copy_blocks([src], [dst])
    np.testing.assert_array_equal(np.asarray(pool.k[:, :, dst]), 3.0)
    np.testing.assert_array_equal(np.asarray(pool.v[:, :, dst]), 4.0)
    # dst independently writable: src keeps its bits
    pool.k = pool.k.at[:, :, dst, 0].set(9.0)
    np.testing.assert_array_equal(np.asarray(pool.k[:, :, src]), 3.0)
    # src immutable from dst's perspective too
    pool.k = pool.k.at[:, :, src].set(5.0)
    assert float(jnp.max(pool.k[:, :, dst])) == 9.0
    pool.release([src, dst])


def test_scheduler_cow_on_shared_tail_block():
    """Two requests pin the SAME fully-cached prompt: each one's first
    step feeds the last prompt token, whose scatter targets the shared
    tail block — the first writer gets a private copy (content preserved),
    the second finds the block private again and writes in place."""
    metrics = ServingMetrics()
    pool = _pool(num_blocks=16, metrics=metrics)
    prompt = list(range(8))
    hashes = chain_block_hashes(prompt, 4)
    blocks = pool.allocate(2)
    pool.k = pool.k.at[:, :, blocks[1]].set(7.0)  # recognizable content
    pool.release(blocks, hashes)

    sched = Scheduler(pool, max_batch=2, token_budget=8, prefill_chunk=8,
                      metrics=metrics)
    r1 = Request(prompt, max_new_tokens=4)
    r2 = Request(prompt, max_new_tokens=4)
    r1.block_hashes = list(hashes)
    r2.block_hashes = list(hashes)
    sched.add(r1)
    sched.add(r2)
    rows = sched.schedule()
    # both matched 2 blocks, capped at num_tokens-1 -> one pending token
    assert [(w.req, w.start, w.count, w.emit) for w in rows] == [
        (r1, 7, 1, True), (r2, 7, 1, True)
    ]
    # hit tokens count MATCHED blocks (2 x 8), not the num_tokens-1 cap —
    # a fully-cached prompt is a 100% hit
    assert metrics.counters["prefix_cache_hit_tokens"] == 16
    assert metrics.counters["prefix_cache_cow_copies"] == 1
    # r1 (planned first) copied; r2 kept the original, now private to it
    assert r1.blocks[0] == r2.blocks[0] == blocks[0]  # full block: shared
    assert r1.blocks[1] != blocks[1] and r2.blocks[1] == blocks[1]
    np.testing.assert_array_equal(
        np.asarray(pool.k[:, :, r1.blocks[1]]), 7.0)  # content came along
    for r in rows:
        r.req.num_cached += r.count
    sched.finish(r1)
    sched.finish(r2)
    assert_pool_idle(pool)


def test_scheduler_hit_skips_budget_and_starts_at_first_uncached():
    """A 12-token prompt with its first 8 tokens cached prefills ONLY the
    remaining 4 under a 4-token budget — the whole prompt would need 3
    steps cold, and the cached tokens are never charged to the budget."""
    pool = _pool(num_blocks=32)
    prompt = list(range(12))
    hashes = chain_block_hashes(prompt, 4)
    blocks = pool.allocate(3)
    pool.release(blocks, hashes[:2])  # only blocks 0,1 published
    sched = Scheduler(pool, max_batch=2, token_budget=4, prefill_chunk=4)
    req = Request(prompt, max_new_tokens=2)
    req.block_hashes = list(hashes)
    sched.add(req)
    (row,) = sched.schedule()
    assert (row.start, row.count, row.emit) == (8, 4, True)
    assert req.num_cached == 8 and req.blocks[:2] == blocks[:2]


def test_early_abort_of_fully_cached_request_keeps_index_intact():
    """Review regression: aborting (or preempting) a fully-cached request
    BEFORE its first step must republish ALL matched blocks — num_cached
    is capped below the last block boundary, but that block's content is
    still valid, and dropping its index entry would decay hot shared
    prefixes under deadline/disconnect abort load."""
    pool = _pool(num_blocks=16)
    prompt = list(range(8))
    hashes = chain_block_hashes(prompt, 4)
    blocks = pool.allocate(2)
    pool.release(blocks, hashes)
    sched = Scheduler(pool, max_batch=2, token_budget=8, prefill_chunk=8)
    req = Request(prompt, max_new_tokens=4)
    req.block_hashes = list(hashes)
    sched.add(req)
    sched.schedule()  # match pins both blocks, num_cached capped at 7
    assert req.num_cached == 7 and req.num_matched_blocks == 2
    sched.abort(req)  # before any step ran
    assert pool.match_prefix(hashes) == blocks  # BOTH still matchable
    pool.release(blocks, hashes)
    assert_pool_idle(pool)


def test_preempted_victim_repins_its_own_published_blocks():
    """Preemption releases blocks WITH their hashes: if they survive in
    the cached tier until re-admission, the replay pins them instead of
    recomputing the prompt."""
    pool = _pool(num_blocks=32)
    prompt = list(range(8))
    sched = Scheduler(pool, max_batch=2, token_budget=8, prefill_chunk=8)
    req = Request(prompt, max_new_tokens=4)
    req.block_hashes = chain_block_hashes(prompt, 4)
    sched.add(req)
    (row,) = sched.schedule()
    assert (row.start, row.count) == (0, 8)  # cold: nothing published yet
    req.num_cached += row.count
    held = list(req.blocks)
    sched._preempt(req)
    assert pool.num_cached_blocks == 2  # both full blocks published
    (row,) = sched.schedule()
    # replay starts at the capped hit (7 of 8 tokens), reusing the blocks
    assert (row.start, row.count) == (7, 1)
    assert req.blocks[0] == held[0]
    sched.finish(req)
    assert_pool_idle(pool)


# -- engine end-to-end -----------------------------------------------------

def test_warm_serve_token_identical_with_hits(model):
    """THE acceptance test: same batch served twice through one engine —
    the warm pass is token-for-token identical, reports hit tokens, and
    still compiles nothing new; a cache-disabled engine agrees."""
    prompts = _prompts((21, 25, 29), seed=3, shared=18)
    prompts.append(_prompts((16,), seed=4)[0])  # fully-cached-prompt edge
    engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
    assert engine.prefix_cache
    cold = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    hits_cold = engine.metrics.counters.get("prefix_cache_hit_tokens", 0)
    warm = engine.generate(prompts, max_new_tokens=6, temperature=0.0)
    hits_warm = engine.metrics.counters["prefix_cache_hit_tokens"] - hits_cold
    assert warm == cold
    assert hits_warm > 0
    assert engine.metrics.counters["jit_traces"] == 2
    assert engine.metrics.gauges["prefix_cache_hit_rate"] > 0
    off = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                    prefix_cache=False)
    assert off.generate(prompts, max_new_tokens=6, temperature=0.0) == cold
    for p, o in zip(prompts, cold):
        assert o == _reference(model, p, 6)
    assert_pool_idle(engine.pool)


def test_cache_hit_serve_matches_reference_mid_traffic(model):
    """Warm requests joining COLD traffic mid-decode stay exact: a shared
    prefix is published by an early finisher while a longer stranger is
    still decoding, then a warm request rides the same steps."""
    p_shared = _prompts((14,), seed=5)[0]
    p_other = _prompts((9,), seed=6)[0]
    engine = LLMEngine(model, block_size=4, max_batch=4, max_seq_len=64)
    r1 = engine.add_request(p_shared, max_new_tokens=4, temperature=0.0)
    r2 = engine.add_request(p_other, max_new_tokens=12, temperature=0.0)
    while not engine.get_request(r1).finished:
        engine.step()
    # r1 finished -> its prefix published; r2 still decoding
    r3 = engine.add_request(p_shared + [5, 9], max_new_tokens=4,
                            temperature=0.0)
    hits0 = engine.metrics.counters.get("prefix_cache_hit_tokens", 0)
    while engine.has_unfinished():
        engine.step()
    assert engine.metrics.counters["prefix_cache_hit_tokens"] > hits0
    assert engine.get_request(r1).output_ids == _reference(model, p_shared, 4)
    assert engine.get_request(r2).output_ids == _reference(model, p_other, 12)
    assert engine.get_request(r3).output_ids == _reference(
        model, p_shared + [5, 9], 4)
    assert_pool_idle(engine.pool)


def test_prefix_cache_disable_flag(model):
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       prefix_cache=False)
    prompts = _prompts((17, 19), seed=7, shared=16)
    out1 = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
    out2 = engine.generate(prompts, max_new_tokens=4, temperature=0.0)
    assert out1 == out2
    assert "prefix_cache_hit_tokens" not in engine.metrics.counters
    assert "prefix_cache_lookup_tokens" not in engine.metrics.counters
    assert engine.pool.num_cached_blocks == 0
    # on by default; the flag turns it off
    assert LLMEngine(model, block_size=8, max_batch=2,
                     max_seq_len=64).prefix_cache


# -- pool-invariant churn sweep (issue satellite) --------------------------

def _churn(model, rounds, seed):
    """Interleave cache hits, COW appends, preemptions, evictions, and
    aborts through a deliberately tiny pool, checking exactness for every
    surviving request and the pool invariant after every round."""
    rs = np.random.RandomState(seed)
    engine = LLMEngine(model, block_size=4, num_blocks=10, max_batch=3,
                       max_seq_len=64, prefill_chunk=8)
    idle_free = engine.pool.num_blocks - 1
    prefixes = [rs.randint(0, 128, (8,)).tolist() for _ in range(3)]
    for rnd in range(rounds):
        reqs = []
        for i in range(rs.randint(2, 5)):
            # tail 0 = the prompt IS a published prefix: the fully-cached
            # match caps at num_tokens-1 and appends through COW
            p = (prefixes[rs.randint(len(prefixes))]
                 + rs.randint(0, 128, (rs.randint(0, 9),)).tolist())
            reqs.append(engine.add_request(
                p, max_new_tokens=int(rs.randint(2, 8)), temperature=0.0))
        doomed = set(rs.choice(reqs, size=len(reqs) // 3, replace=False)
                     .tolist()) if len(reqs) >= 3 else set()
        steps = 0
        while engine.has_unfinished():
            engine.step()
            steps += 1
            if steps == 2:
                for rid in doomed:
                    engine.abort(rid)
        for rid in reqs:
            if rid in doomed:
                continue
            req = engine.get_request(rid)
            prompt = req.prompt_ids
            assert req.output_ids == _reference(
                model, prompt, req.max_new_tokens), f"round {rnd}"
            engine.release(rid)
        # every round ends idle: refcounts all zero, num_free restored
        assert engine.pool.num_free == idle_free, f"round {rnd}"
        assert_pool_idle(engine.pool)
    c = engine.metrics.counters
    # the sweep must actually exercise the mechanisms it claims to
    assert c.get("prefix_cache_hit_tokens", 0) > 0
    return c


def test_cache_churn_smoke(model):
    """Always-on tier-1 smoke: few rounds, same invariant checks."""
    c = _churn(model, rounds=3, seed=0)
    assert c.get("requests_aborted", 0) > 0


@pytest.mark.slow
def test_cache_churn_soak(model):
    """Soak-style sweep across more rounds and seeds (slow tier): enough
    churn that hits, COW, evictions, aborts, AND preemptions all fire."""
    merged = {}
    for seed in (1, 2):
        c = _churn(model, rounds=8, seed=seed)
        for k, v in c.items():
            merged[k] = merged.get(k, 0) + v
    assert merged.get("preemptions", 0) > 0
    assert merged.get("prefix_cache_evictions", 0) > 0
    assert merged.get("prefix_cache_cow_copies", 0) > 0
    assert merged.get("requests_aborted", 0) > 0
