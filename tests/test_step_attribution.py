"""What a step span and the latency summary say of where a step's time and
the kernel's grid went (serving/engine.py `_run_rows`, `_run_step`;
scheduler.py `_admit`; ops/pallas/paged_attention.py `ragged_grid_steps`):

- ``grid_live`` / ``grid_total``: the iterations of the ragged kernel's
  walk and, added to them, the grid steps that ran none, a layer, against a
  brute-force walk of the kernel's own conditions;
- ``append_blocks``: the blocks the append's writer copies back, an arena a
  layer, against a count by hand;
- ``h2d_arrays`` / ``h2d_bytes`` / ``h2d_ms``: the host arrays handed to
  the device (one: the packed step input), its size by the layout's own
  count, and what the hand-over took, inside the ``dispatch`` phase;
- ``queue_wait``: arrival to first admission, observed like ``ttft``, with
  tracing on or off.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.ops.pallas.paged_attention import (
    _pages_per_fetch,
    _q_tile,
    ragged_grid_steps,
)
from paddle_tpu.serving import LLMEngine

PHASES = ("plan", "build", "dispatch", "sync", "emit")


def _walk(q_lens, kv_live, width, block_size):
    """The kernel's grid `(B, nq)`, step by step, with
    its own conditions (`_ragged_kernel`: a tile runs its loop where `qb *
    qt < q_lens[i]`, one iteration a fetch of `pages` pages until the row's
    `kv_live` are walked): `(iterations, iterations + grid steps that ran
    none)`."""
    qt = _q_tile(width)
    pages = _pages_per_fetch(block_size)
    live = idle = 0
    for q_len, kl in zip(q_lens, kv_live):
        for qb in range(width // qt):
            ran = 0
            if qb * qt < q_len:
                page = 0
                while page < kl:
                    ran += 1
                    page += pages
            live += ran
            idle += not ran
    return live, live + idle


# (q_lens, kv_live, width, block_size); q_len 0 = a lane with no request,
# whose kv_live the engine leaves at 0
GRID_CASES = {
    "decode-w1": ([1, 1, 1, 1], [3, 16, 1, 7], 1, 16),
    "decode-w1-empty-lanes": ([1, 0, 1, 0], [5, 0, 2, 0], 1, 8),
    "verify-w5": ([5, 3, 1, 0], [2, 4, 8, 0], 5, 8),
    "chunk-w128-full": ([128, 128], [8, 12], 128, 16),
    "chunk-w128-ragged": ([128, 37, 9, 8], [16, 5, 1, 3], 128, 16),
    "decode-row-rides-w128": ([128, 1, 1, 0], [9, 4, 16, 0], 128, 16),
    "long-rows-w1": ([1, 1], [128, 65], 1, 16),
    "small-blocks-w1": ([1, 1, 1], [33, 32, 31], 1, 4),
    "all-lanes-empty": ([0, 0], [0, 0], 1, 4),
}


@pytest.mark.parametrize("case", sorted(GRID_CASES))
def test_ragged_grid_steps_counts_the_kernels_live_steps(case):
    q_lens, kv_live, width, block_size = GRID_CASES[case]
    got = ragged_grid_steps(np.array(q_lens, np.int32),
                            np.array(kv_live, np.int32), width, block_size)
    assert got == _walk(q_lens, kv_live, width, block_size)
    assert all(type(x) is int for x in got)     # a span arg: plain JSON
    assert 0 <= got[0] <= got[1]


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                    max_seq_len=64, attn_impl="xla", dropout=0.0)
    m = GPT(cfg)
    m.eval()
    return m


def _prompts(lengths, seed=0):
    rs = np.random.RandomState(seed)
    return [rs.randint(0, 128, (n,)).tolist() for n in lengths]


def _engine(model, **kw):
    kw.setdefault("block_size", 8)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_seq_len", 64)
    return LLMEngine(model, **kw)


def _steps(engine):
    """`[(step span, {phase: span})]` of the engine's trace, in order."""
    evs = [e for e in engine.tracer.chrome_trace()["traceEvents"]
           if e["ph"] == "X" and e["pid"] == 1 and e["tid"] == 0]
    phases = {}
    for e in evs:
        if e["name"] in PHASES:
            phases.setdefault(e["args"]["step"], {})[e["name"]] = e
    return [(e, phases[e["args"]["step"]]) for e in evs
            if e["name"].startswith("step[")]


@pytest.mark.parametrize("kv_dtype", [None, "int8"],
                         ids=["bf16-arena", "int8-arena"])
def test_every_step_span_says_grid_and_handover(model, kv_dtype):
    """Mixed, decode and (with speculation) verify steps alike."""
    engine = _engine(model, prefill_chunk=8, trace=1.0, kv_dtype=kv_dtype,
                     spec_decoding=True)
    # a repeated pattern, so the n-gram drafter proposes and verify steps run
    prompts = _prompts((20, 7), seed=1) + [[5, 6, 7] * 6]
    engine.generate(prompts, max_new_tokens=6)
    steps = _steps(engine)
    assert {s["args"]["kind"] for s, _ in steps} >= {"mixed", "decode"}
    for span, phases in steps:
        a = span["args"]
        assert "host_syncs" not in a
        # the launches of the step's groups, summed: every query tile of
        # every row of every group (`step_groups`)
        groups = engine.step_groups(a["width"])
        nq = sum(n * (w // _q_tile(w)) for n, w in groups)
        assert a["slots"] == sum(n * w for n, w in groups) >= a["fed_tokens"]
        # every planned row fetches at least its own first pages, and
        # every query tile of every row either iterates or is skipped
        assert a["rows"] <= a["grid_live"] <= a["grid_total"]
        assert a["grid_total"] >= nq
        assert a["grid_total"] - a["grid_live"] <= nq
        # one packed buffer a step, whatever the arena: its sections are
        # a group's thirteen host arrays (fifteen with an int8 arena) and
        # one more: `src` in the decode group, `lanes` in a group behind it
        layouts, words = engine._step_layout(a["width"])
        assert a["h2d_arrays"] == 1
        assert [len(fields) for fields in layouts] == [
            (15 if kv_dtype else 13) + 1] * len(groups)
        assert a["h2d_bytes"] == 4 * words
        # ts/dur are rounded to 3 decimals of a microsecond
        assert 0.0 <= a["h2d_ms"] <= phases["dispatch"]["dur"] / 1e3 + 1e-5
    assert engine.metrics.counters["host_syncs"] == len(steps)


def test_grid_live_follows_the_rows_contexts(model, monkeypatch):
    """A decode step's live count is the rows' fetches: one query tile a
    row and one iteration a fetch, so it grows by one each time a row's
    context crosses a fetch's edge (here cut to one 8-token page, so that
    contexts a test can hold cross some), and nothing is skipped while
    every lane is busy."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    monkeypatch.setattr(pa, "_KV_FETCH_TOKENS", 8)
    # (a chunk row each: both prompts go in with the first step)
    engine = _engine(model, trace=1.0, max_batch=2, token_budget=2 * 64)
    engine.generate(_prompts((7, 15), seed=3), max_new_tokens=10)
    decode = [s["args"] for s, _ in _steps(engine)
              if s["args"]["kind"] == "decode" and s["args"]["rows"] == 2]
    assert len(decode) >= 8
    # step n feeds positions 7+n and 15+n (block_size 8): fetches made
    for n, a in enumerate(decode):
        assert a["grid_live"] == (7 + n) // 8 + 1 + (15 + n) // 8 + 1
        assert a["grid_total"] == a["grid_live"]
    assert decode[-1]["grid_live"] > decode[0]["grid_live"]


def test_append_blocks_is_the_blocks_a_step_writes(model):
    """`append_blocks`: what the append's writer copies back, an arena a
    layer (`ops/pallas/paged_kv_append.py append_blocks`), by hand. Blocks
    of 8: prompts of 7 and 15 tokens from position 0 fill one and two; a
    decode step writes one token a row, one block each; rows that run out
    leave lanes that write none."""
    engine = _engine(model, trace=1.0, max_batch=2, prefill_chunk=16,
                     token_budget=2 * 16)
    for prompt, n in zip(_prompts((7, 15), seed=3), (10, 6)):
        engine.add_request(prompt, max_new_tokens=n)
    while engine.has_unfinished():
        engine.step()
    steps = [s["args"] for s, _ in _steps(engine)]
    assert steps[0]["kind"] == "mixed" and steps[0]["fed_tokens"] == 22
    assert steps[0]["append_blocks"] == 1 + 2
    decode = [a for a in steps if a["kind"] == "decode"]
    assert {a["rows"] for a in decode} == {1, 2}
    for a in decode:
        assert a["append_blocks"] == a["rows"]


@pytest.mark.parametrize("trace", [None, 1.0], ids=["untraced", "traced"])
def test_queue_wait_is_observed_once_a_request_admitted(model, trace):
    engine = _engine(model, max_batch=2, trace=trace)
    engine.generate(_prompts((9, 14, 6, 11, 5), seed=7), max_new_tokens=4)
    lat = engine.metrics.latency_summary()
    assert lat["queue_wait"]["count"] == 5 == lat["ttft"]["count"]
    # admission comes before the first token, request by request
    assert lat["queue_wait"]["total_ms"] <= lat["ttft"]["total_ms"]
    assert "queue_wait" in engine.metrics.snapshot()["latency"]
    text = engine.metrics.prometheus_text()
    assert "# HELP paddle_tpu_serving_queue_wait_seconds Request arrival " \
        "to its first admission into a lane" in text
    assert "paddle_tpu_serving_queue_wait_seconds_count 5" in text


def test_queue_wait_counts_first_admissions_only(model):
    """A preempted request is admitted twice and waited in the queue once."""
    engine = _engine(model, max_batch=2, num_blocks=7, prefix_cache=False)
    engine.generate(_prompts((14, 15), seed=4), max_new_tokens=12)
    assert engine.metrics.counters.get("preemptions", 0) >= 1
    assert engine.metrics.latency_summary()["queue_wait"]["count"] == 2


def test_a_request_behind_a_full_batch_waits_out_the_steps_before_it(model):
    engine = _engine(model, max_batch=1, trace=1.0)
    engine.generate(_prompts((9, 6), seed=5), max_new_tokens=3)
    lat = engine.metrics.latency_summary()["queue_wait"]
    assert lat["count"] == 2
    # the second request arrived before the first step and got the lane
    # only after the first one's last: it waited out every step in between
    # (a step is planned before the one in flight is read: spans overlap)
    steps = _steps(engine)
    first_request = [s for s, _ in steps][:3]      # prefill + 2 decodes
    waited_ms = (max(s["ts"] + s["dur"] for s in first_request)
                 - min(s["ts"] for s in first_request)) / 1e3
    assert lat["max_ms"] >= waited_ms
    assert lat["max_ms"] >= max(s["dur"] for s in first_request) / 1e3
