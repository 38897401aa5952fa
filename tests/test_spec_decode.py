"""Speculative decoding: prompt-lookup drafting + batched verification.

Acceptance criteria from the spec-decoding issue:

- with GREEDY sampling, speculative output is token-for-token identical to
  non-speculative output for the same requests, prefix caching on AND off,
  across mixed continuous-batching traffic;
- after any interleaving of accepts, full rejections, preemptions, and
  aborts the pool returns to its idle free-block count with all refcounts
  zero (the churn-sweep pattern from tests/test_prefix_cache.py);
- the compiled-program count stays bounded by the engine's ragged
  width buckets (`expected_program_count()`) regardless of request mix;
- acceptance-rate metrics are wired: `spec_proposed_tokens` /
  `spec_accepted_tokens` counters, `spec_acceptance_rate` /
  `spec_mean_accepted_len` / `tokens_per_step` gauges, snapshot and
  Prometheus exposition.

Acceptance-sensitive paths use oracle/adversarial drafters (a drafter that
proposes the model's true continuation, or deliberate garbage) so the
tests pin behavior at 100% and 0% acceptance independent of what the
random tiny model happens to emit; the NgramDrafter itself is unit-tested
on host.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import LLMEngine, NgramDrafter
from paddle_tpu.serving.spec import apply_top_k_top_p


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


def assert_pool_idle(pool):
    assert pool._refcount == {}
    assert pool.num_free == pool.num_blocks - 1
    assert {h: b for b, h in pool._block_hash.items()} == pool._hash_index


class OracleDrafter(NgramDrafter):
    """Drafts the model's TRUE greedy continuation (precomputed per
    prompt): every drafted token verifies, pinning the accept path at
    100% acceptance regardless of the model's own repetitiveness."""

    def __init__(self, continuations, num_spec_tokens=4):
        super().__init__(num_spec_tokens=num_spec_tokens)
        self._cont = continuations  # prompt tuple -> full greedy output

    def propose(self, all_ids, max_tokens=None):
        cap = self.num_spec_tokens
        if max_tokens is not None:
            cap = min(cap, int(max_tokens))
        for p, out in self._cont.items():
            if tuple(all_ids[:len(p)]) == p:
                done = len(all_ids) - len(p)
                if all_ids[len(p):] != out[:done]:
                    return []  # a sampled/diverged path: oracle blind
                return out[done:done + cap]
        return []


class GarbageDrafter(NgramDrafter):
    """Adversarial drafter: always proposes out-of-distribution tokens
    (vocab - 1 - last_token mod vocab style), so greedy verification
    rejects EVERY draft — output must still be exact and every reserved
    block must roll back."""

    def propose(self, all_ids, max_tokens=None):
        cap = self.num_spec_tokens
        if max_tokens is not None:
            cap = min(cap, int(max_tokens))
        return [(all_ids[-1] + 1 + i) % 127 for i in range(cap)]


# -- drafter units (host only, no model) -----------------------------------

def test_ngram_drafter_match_and_no_match():
    d = NgramDrafter(num_spec_tokens=4, max_ngram=3, min_ngram=1)
    # suffix [7, 8] occurred earlier, followed by 9, 10, 11
    assert d.propose([7, 8, 9, 10, 11, 3, 7, 8]) == [9, 10, 11, 3]
    # cap respected
    assert d.propose([7, 8, 9, 10, 11, 3, 7, 8], 2) == [9, 10]
    # no earlier occurrence of any suffix n-gram
    assert d.propose([1, 2, 3, 4, 5]) == []
    # the most recent match with a FULL draft window wins (i=0 here); the
    # nearer match at i=2 could only supply a truncated draft
    assert d.propose([5, 1, 5, 2, 9, 5]) == [1, 5, 2, 9]
    # with a smaller cap the nearer match has the full window and wins
    assert d.propose([5, 1, 5, 2, 9, 5], 3) == [2, 9, 5]


def test_ngram_drafter_prefers_longer_ngrams():
    d = NgramDrafter(num_spec_tokens=3, max_ngram=3, min_ngram=1)
    # trigram [1,2,3] matched at the start beats the more recent unigram 3
    assert d.propose([1, 2, 3, 7, 7, 3, 4, 1, 2, 3]) == [7, 7, 3]


def test_ngram_drafter_short_history_and_caps():
    d = NgramDrafter(num_spec_tokens=4)
    assert d.propose([5]) == []          # nothing before the suffix
    assert d.propose([5, 5]) == [5]      # 1-token history match
    assert d.propose([5, 5], 0) == []    # zero cap: no draft
    assert d.propose([], 4) == []
    with pytest.raises(ValueError):
        NgramDrafter(num_spec_tokens=0)
    with pytest.raises(ValueError):
        NgramDrafter(min_ngram=0)


def test_ngram_drafter_proposal_includes_overlap():
    d = NgramDrafter(num_spec_tokens=6, max_ngram=2)
    # periodic history: the most recent earlier [1, 2] sits right before
    # the suffix, and its continuation reads INTO the suffix region (the
    # draft may propose tokens the sequence just emitted — that is the
    # whole trick on cyclic output)
    assert d.propose([1, 2, 1, 2, 1, 2]) == [1, 2]
    assert d.propose([3, 1, 2, 1, 2, 1]) == [2, 1]


# -- top-k / top-p processing ----------------------------------------------

def test_apply_top_k_top_p_masks_support():
    import jax.numpy as jnp

    lg = jnp.asarray([[0.0, 1.0, 2.0, 3.0, 4.0],
                      [4.0, 3.0, 2.0, 1.0, 0.0]], jnp.float32)
    # top_k=2 keeps the two largest per row
    out = apply_top_k_top_p(lg, jnp.asarray([2, 2]), jnp.asarray([1.0, 1.0]))
    assert np.isfinite(np.asarray(out)).tolist() == [
        [False, False, False, True, True], [True, True, False, False, False]]
    # top_k=0 / top_p=1.0 are no-ops
    out = apply_top_k_top_p(lg, jnp.asarray([0, 0]), jnp.asarray([1.0, 1.0]))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(lg))
    # tiny top_p keeps only the argmax (nucleus of one)
    out = apply_top_k_top_p(lg, jnp.asarray([0, 0]),
                            jnp.asarray([1e-4, 1e-4]))
    finite = np.isfinite(np.asarray(out))
    assert finite.sum() == 2 and finite[0, 4] and finite[1, 0]
    # top_k and top_p compose (k first, then nucleus over the survivors)
    out = apply_top_k_top_p(lg, jnp.asarray([3, 3]),
                            jnp.asarray([0.5, 0.5]))
    assert np.isfinite(np.asarray(out)).sum(axis=1).max() <= 3
    # top_p just under 1.0: float32 cumsum may never reach p — the cut
    # must keep (nearly) everything, NOT collapse to the argmax
    flat = jnp.zeros((1, 50000), jnp.float32)  # uniform: worst cumsum case
    out = apply_top_k_top_p(flat, jnp.asarray([0]),
                            jnp.asarray([0.9999999]))
    assert np.isfinite(np.asarray(out)).sum() == 50000


def test_engine_sampler_top_k_top_p_restrict_support(model):
    """Sampled serving tokens stay inside the top-k support: with top_k=1
    sampling at any temperature IS greedy (the only surviving token is the
    argmax), so the output must equal the greedy reference."""
    prompts = _prompts((6, 11), seed=3)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    outs = engine.generate(prompts, max_new_tokens=6, temperature=1.5,
                           top_k=1)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 6)
    # a tiny nucleus behaves the same way (top-1 always survives top-p)
    outs = engine.generate(prompts, max_new_tokens=6, temperature=1.5,
                           top_p=1e-6)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 6)
    with pytest.raises(ValueError):
        engine.add_request(prompts[0], top_p=1.5)
    with pytest.raises(ValueError):
        engine.add_request(prompts[0], top_k=-3)


def test_verify_rejection_sampling_respects_top_k(model):
    """Spec-on sampling with top_k=1 must also equal greedy: the verify
    step's rejection test and residual/bonus samples all draw from the
    SAME top-k/top-p-processed distribution as the decode sampler."""
    prompts = _prompts((7, 12), seed=9)
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       spec_decoding=True, num_spec_tokens=3)
    outs = engine.generate(prompts, max_new_tokens=8, temperature=2.0,
                           top_k=1)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 8)
    assert_pool_idle(engine.pool)


# -- greedy parity ---------------------------------------------------------

@pytest.mark.slow  # tier-1 headroom (PR 19): heaviest always-on case; tier-2 covers it
def test_spec_greedy_parity_mixed_batch(model):
    """THE acceptance test: the same overlapping request mix served by a
    spec-enabled engine and a plain engine is token-for-token identical,
    with prefix caching on AND off, and the spec engine stays inside its
    `expected_program_count()` width buckets."""
    prompts = _prompts((5, 9, 21, 13), seed=1, shared=4)
    for prefix_cache in (True, False):
        base = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                         prefix_cache=prefix_cache)
        want = base.generate(prompts, max_new_tokens=10, temperature=0.0)
        eng = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                        prefix_cache=prefix_cache, spec_decoding=True,
                        num_spec_tokens=4)
        got = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
        assert got == want, f"prefix_cache={prefix_cache}"
        got2 = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
        assert got2 == want  # warm pass (cache hits + spec) still exact
        traces = eng.metrics.counters["jit_traces"]
        assert traces <= eng.expected_program_count() == 3, traces
        assert eng.metrics.counters["verify_steps"] > 0
        assert_pool_idle(eng.pool)
    for p, o in zip(prompts, want):
        assert o == _reference(model, p, 10)


def test_spec_oracle_drafter_accepts_everything(model):
    """With a drafter proposing the model's true continuation, every
    drafted token is accepted (rate 1.0), decode finishes in ~1/(k+1) of
    the steps, and the output is exact."""
    prompts = _prompts((6, 9), seed=2)
    refs = {tuple(p): _reference(model, p, 12) for p in prompts}
    base = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    base.generate(prompts, max_new_tokens=12, temperature=0.0)
    base_steps = (base.metrics.counters["decode_steps"]
                  + base.metrics.counters["mixed_steps"])

    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                    spec_decoding=True, num_spec_tokens=4)
    eng.scheduler.drafter = OracleDrafter(refs, num_spec_tokens=4)
    outs = eng.generate(prompts, max_new_tokens=12, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o == refs[tuple(p)]
    c = eng.metrics.counters
    assert c["spec_accepted_tokens"] == c["spec_proposed_tokens"] > 0
    assert eng.metrics.gauges["spec_acceptance_rate"] == 1.0
    spec_steps = (c["decode_steps"] + c["mixed_steps"] + c["verify_steps"])
    assert spec_steps < base_steps  # fewer invocations for the same tokens
    assert eng.metrics.gauges["tokens_per_step"] > 1.0
    assert_pool_idle(eng.pool)


def test_spec_full_rejection_is_exact_and_rolls_back(model):
    """An adversarial drafter whose every candidate is rejected: outputs
    stay exact (the stop-slot token is the model's own), acceptance is
    0.0, and every speculative block reservation rolls back — the pool
    ends idle with zero refcounts."""
    prompts = _prompts((6, 10), seed=4)
    eng = LLMEngine(model, block_size=4, max_batch=2, max_seq_len=64,
                    spec_decoding=True, num_spec_tokens=4)
    eng.scheduler.drafter = GarbageDrafter(num_spec_tokens=4)
    outs = eng.generate(prompts, max_new_tokens=10, temperature=0.0)
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 10)
    c = eng.metrics.counters
    assert c["spec_proposed_tokens"] > 0
    assert c["spec_accepted_tokens"] == 0
    assert eng.metrics.gauges["spec_acceptance_rate"] == 0.0
    assert eng.metrics.counters["verify_steps"] > 0
    assert_pool_idle(eng.pool)


def test_spec_mid_verify_abort_block_accounting(model):
    """Abort a request immediately after a verify step that reserved and
    partially rolled back speculative blocks (and one mid-prefill), while
    another spec request keeps decoding exactly."""
    p1, p2 = _prompts((9, 30), seed=5)
    eng = LLMEngine(model, block_size=4, max_batch=2, max_seq_len=64,
                    prefill_chunk=8, spec_decoding=True, num_spec_tokens=4)
    eng.scheduler.drafter = GarbageDrafter(num_spec_tokens=4)
    r1 = eng.add_request(p1, max_new_tokens=12, temperature=0.0)
    eng.step()            # p1 prefill
    eng.step()            # first decode/verify round for p1
    r2 = eng.add_request(p2, max_new_tokens=12, temperature=0.0)
    eng.step()            # p2 mid-prefill, p1 verifying
    assert eng.abort(r2)  # abort mid-prefill
    eng.step()            # p1 verify right after the abort
    assert eng.abort(r1)  # abort right after a verify (spec tail live)
    assert not eng.has_unfinished()
    assert_pool_idle(eng.pool)
    # a fresh request serves exactly after the churn
    (out,) = eng.generate([p1], max_new_tokens=6, temperature=0.0)
    assert out == _reference(model, p1, 6)
    assert_pool_idle(eng.pool)


def test_spec_eos_inside_accepted_run(model):
    """When eos lands inside the accepted run, emission truncates at eos
    and the request finishes — trailing accepted drafts are discarded."""
    (p,) = _prompts((7,), seed=6)
    ref = _reference(model, p, 12)
    eos = ref[2]  # force a stop mid-run
    base = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64)
    (want,) = base.generate([p], max_new_tokens=12, temperature=0.0,
                            eos_token_id=eos)
    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                    spec_decoding=True, num_spec_tokens=4)
    eng.scheduler.drafter = OracleDrafter({tuple(p): ref}, num_spec_tokens=4)
    (got,) = eng.generate([p], max_new_tokens=12, temperature=0.0,
                          eos_token_id=eos)
    assert got == want == ref[:ref.index(eos) + 1]
    assert_pool_idle(eng.pool)


# -- knobs -----------------------------------------------------------------

def test_spec_default_off_and_per_request_optout(model):
    assert not LLMEngine(model, block_size=8, max_batch=2,
                         max_seq_len=64).spec_decoding  # default OFF

    # per-request opt-out on a spec engine: no drafts for that request
    prompts = _prompts((6, 8), seed=7)
    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                    spec_decoding=True, num_spec_tokens=4)
    eng.scheduler.drafter = GarbageDrafter(num_spec_tokens=4)
    outs = eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                        spec_decoding=False)
    assert eng.metrics.counters.get("spec_proposed_tokens", 0) == 0
    assert eng.metrics.counters.get("verify_steps", 0) == 0
    for p, o in zip(prompts, outs):
        assert o == _reference(model, p, 6)
    # per-request num_spec_tokens caps (never raises) the draft length
    eng.generate(prompts, max_new_tokens=6, temperature=0.0,
                 num_spec_tokens=1)
    assert eng.metrics.counters["spec_drafted_rows"] == \
        eng.metrics.counters["spec_proposed_tokens"]


def test_spec_metrics_flow_to_snapshot_and_prometheus(model):
    prompts = _prompts((6, 9), seed=8)
    eng = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                    spec_decoding=True, num_spec_tokens=3)
    refs = {tuple(p): _reference(model, p, 8) for p in prompts}
    eng.scheduler.drafter = OracleDrafter(refs, num_spec_tokens=3)
    eng.generate(prompts, max_new_tokens=8, temperature=0.0)
    snap = eng.metrics.snapshot()
    assert snap["counters"]["spec_proposed_tokens"] > 0
    assert snap["gauges"]["spec_acceptance_rate"] == 1.0
    assert snap["gauges"]["tokens_per_step"] > 1.0
    assert "verify_step" in snap["latency"]
    text = eng.metrics.prometheus_text()
    assert "paddle_tpu_serving_spec_accepted_tokens_total" in text
    assert "paddle_tpu_serving_spec_acceptance_rate" in text
    assert "paddle_tpu_serving_verify_step_seconds_count" in text


# -- churn sweep (pool-invariant soak) -------------------------------------

def _churn(model, rounds, seed, drafter=None):
    """Interleave spec accepts/rejections, prefix-cache hits, preemptions,
    and aborts through a deliberately tiny pool; exactness for every
    surviving request and the idle-pool invariant after every round."""
    rs = np.random.RandomState(seed)
    engine = LLMEngine(model, block_size=4, num_blocks=10, max_batch=3,
                       max_seq_len=64, prefill_chunk=8, spec_decoding=True,
                       num_spec_tokens=3)
    if drafter is not None:
        engine.scheduler.drafter = drafter
    idle_free = engine.pool.num_blocks - 1
    prefixes = [rs.randint(0, 128, (8,)).tolist() for _ in range(3)]
    for rnd in range(rounds):
        reqs = []
        for _ in range(rs.randint(2, 5)):
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
            assert req.output_ids == _reference(
                model, req.prompt_ids, req.max_new_tokens), f"round {rnd}"
            engine.release(rid)
        assert engine.pool.num_free == idle_free, f"round {rnd}"
        assert engine.pool._refcount == {}, f"round {rnd}"
    return engine.metrics.counters


def test_spec_churn_smoke(model):
    """Always-on tier-1 smoke: n-gram drafting + spec verify under abort
    churn in a tiny pool, every output exact, pool idle every round.
    Drafted rows may ride mixed steps now (ragged widths), so the
    exercised-speculation signal is drafted rows, not verify-kind
    steps."""
    c = _churn(model, rounds=3, seed=0)
    assert c.get("spec_drafted_rows", 0) > 0
    assert c.get("spec_proposed_tokens", 0) > 0
    assert c.get("requests_aborted", 0) > 0


@pytest.mark.slow
def test_spec_churn_soak(model):
    """Soak across seeds and drafters (real n-gram AND always-reject):
    enough churn that accepts, full rejections, preemptions, evictions,
    and aborts all fire with speculation on."""
    merged = {}
    for seed, drafter in ((1, None), (2, None),
                          (3, GarbageDrafter(num_spec_tokens=3))):
        c = _churn(model, rounds=8, seed=seed, drafter=drafter)
        for k, v in c.items():
            merged[k] = merged.get(k, 0) + v
    assert merged.get("spec_proposed_tokens", 0) > 0
    assert merged.get("spec_accepted_tokens", 0) > 0
    assert merged.get("preemptions", 0) > 0
    assert merged.get("requests_aborted", 0) > 0


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))
