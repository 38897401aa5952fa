"""ServingServer: the HTTP/SSE surface over AsyncLLMEngine.

Everything runs in-process over loopback (127.0.0.1, ephemeral ports — no
egress) on the tier-1 CPU invocation. `test_server_smoke_streamed` is the
always-on fast path: boot, stream one greedy completion token-for-token
equal to the engine reference, scrape /healthz + /metrics, drain cleanly.
The long mixed-traffic soak is `-m slow`.
"""
import asyncio
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPT, GPTConfig
from paddle_tpu.serving import LLMEngine, ServingServer


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


def _reference(model, prompt, n):
    ids = paddle.to_tensor(np.asarray([prompt], np.int64))
    out = model.generate(ids, max_new_tokens=n, temperature=0.0)
    return out.numpy()[0, len(prompt):].tolist()


def _idle(engine):
    return engine.pool.num_free == engine.pool.num_blocks - 1


async def _wait_for(cond, timeout=30.0, msg="condition"):
    t0 = time.monotonic()
    while not cond():
        if time.monotonic() - t0 > timeout:
            raise AssertionError(f"timed out waiting for {msg}")
        await asyncio.sleep(0.01)


async def _http(port, method, path, obj=None, trailer=b""):
    """One loopback HTTP exchange; returns (status, body_bytes).
    `trailer` sends stray bytes after the body (some clients emit a
    trailing CRLF) — they must be drained, never read as a disconnect."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(obj).encode() if obj is not None else b""
    writer.write(
        (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
         f"Content-Type: application/json\r\n"
         f"Content-Length: {len(data)}\r\n\r\n").encode() + data + trailer
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    try:
        await writer.wait_closed()
    except (ConnectionError, OSError):
        pass
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ")[1]), body


def _sse_tokens(body):
    """Parse an SSE body -> (token list, final finish_reason, saw_done)."""
    toks, reason, done = [], None, False
    for line in body.decode().splitlines():
        if not line.startswith("data: "):
            continue
        payload = line[len("data: "):]
        if payload == "[DONE]":
            done = True
            continue
        chunk = json.loads(payload)
        choice = chunk["choices"][0]
        toks.extend(choice["token_ids"])
        if choice["finish_reason"] is not None:
            reason = choice["finish_reason"]
    return toks, reason, done


async def _start_server(model, **kw):
    engine = LLMEngine(model, block_size=8, max_batch=kw.pop("max_batch", 4),
                       max_seq_len=64)
    server = ServingServer(engine, host="127.0.0.1", port=0, **kw)
    await server.start()
    return engine, server


def test_server_smoke_streamed(model):
    """Always-on smoke: boot in-process, stream one greedy completion
    (token-for-token the engine reference), check /healthz, drain."""
    (p,) = _prompts((7,), seed=1)
    ref = _reference(model, p, 6)

    async def main():
        engine, server = await _start_server(model)
        status, body = await _http(
            server.port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": 6, "stream": True},
            trailer=b"\r\n",  # stray client bytes must not read as hangup
        )
        hstatus, hbody = await _http(server.port, "GET", "/healthz")
        await server.shutdown(drain=True)
        return engine, server, status, body, hstatus, json.loads(hbody)

    engine, server, status, body, hstatus, health = asyncio.run(main())
    assert status == 200
    toks, reason, done = _sse_tokens(body)
    assert toks == ref
    assert reason == "length" and done
    assert hstatus == 200 and health["status"] == "ok"
    assert _idle(engine)
    assert not server.engine._thread.is_alive()  # drained, no hung tasks


def test_server_non_streaming_and_metrics(model):
    (p,) = _prompts((9,), seed=2)
    ref = _reference(model, p, 5)

    async def main():
        engine, server = await _start_server(model)
        status, body = await _http(
            server.port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": 5},
        )
        mstatus, metrics = await _http(server.port, "GET", "/metrics")
        bstatus, _ = await _http(server.port, "POST", "/v1/completions",
                                 {"prompt": "not token ids"})
        tstatus, _ = await _http(server.port, "POST", "/v1/completions",
                                 {"prompt": p, "timeout_s": "soon"})
        nstatus, _ = await _http(server.port, "GET", "/nope")
        await server.shutdown()
        return engine, status, json.loads(body), mstatus, metrics.decode(), \
            bstatus, tstatus, nstatus

    engine, status, out, mstatus, metrics, bstatus, tstatus, nstatus = \
        asyncio.run(main())
    assert status == 200
    assert out["object"] == "text_completion"
    assert out["choices"][0]["token_ids"] == ref
    assert out["choices"][0]["finish_reason"] == "length"
    assert out["usage"] == {"prompt_tokens": 9, "completion_tokens": 5,
                            "total_tokens": 14}
    assert mstatus == 200
    assert "paddle_tpu_serving_requests_added_total 1" in metrics
    assert "paddle_tpu_serving_generated_tokens_total 5" in metrics
    assert 'quantile="0.95"' in metrics  # step-latency summaries
    assert bstatus == 400 and tstatus == 400 and nstatus == 404
    assert engine.metrics.counters["requests_added"] == 1  # no slot leaked
    assert _idle(engine)


def test_server_full_wait_queue_yields_429(model):
    """Admission control over HTTP: with one lane and no wait queue, a
    second request is rejected 429 while the first is in flight — never
    queued unboundedly."""
    p1, p2 = _prompts((4, 5), seed=3)

    async def main():
        engine, server = await _start_server(model, max_batch=1,
                                             max_waiting=0)
        # occupy the single slot straight through the frontend; the HTTP
        # request below races nothing — admission is synchronous
        st = server.engine.submit(p1, max_new_tokens=40, temperature=0.0)
        status, body = await _http(
            server.port, "POST", "/v1/completions",
            {"prompt": p2, "max_tokens": 2},
        )
        await st.collect()
        # slot free again: same request now admitted
        status2, _ = await _http(server.port, "POST", "/v1/completions",
                                 {"prompt": p2, "max_tokens": 2})
        await server.shutdown()
        return engine, status, json.loads(body), status2

    engine, status, body, status2 = asyncio.run(main())
    assert status == 429
    assert body["error"]["type"] == "overloaded"
    assert status2 == 200
    assert engine.metrics.counters["requests_rejected"] == 1
    assert _idle(engine)


def test_server_client_disconnect_aborts_and_frees(model):
    """A client that drops its SSE connection mid-stream: the request is
    aborted, its KV blocks return to the pool, and the server keeps
    serving (next request is token-exact)."""
    p1, p2 = _prompts((6, 8), seed=4)
    ref2 = _reference(model, p2, 4)

    async def main():
        engine, server = await _start_server(model)
        reader, writer = await asyncio.open_connection(
            "127.0.0.1", server.port)
        data = json.dumps({"prompt": p1, "max_tokens": 56,
                           "stream": True}).encode()
        writer.write(
            (f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
             f"Content-Type: application/json\r\n"
             f"Content-Length: {len(data)}\r\n\r\n").encode() + data
        )
        await writer.drain()
        # wait for the first SSE chunk, then vanish
        while True:
            line = await reader.readline()
            if line.startswith(b"data: "):
                break
        writer.close()
        try:
            await writer.wait_closed()
        except (ConnectionError, OSError):
            pass
        await _wait_for(
            lambda: engine.metrics.counters["requests_cancelled"] >= 1
            and _idle(engine),
            msg="disconnect abort + block reclamation",
        )
        status, body = await _http(server.port, "POST", "/v1/completions",
                                   {"prompt": p2, "max_tokens": 4})
        await server.shutdown(drain=True)
        return engine, status, json.loads(body)

    engine, status, out = asyncio.run(main())
    assert status == 200
    assert out["choices"][0]["token_ids"] == ref2
    assert engine.metrics.counters["client_disconnects"] >= 1
    assert _idle(engine)


def test_server_draining_rejects_with_503(model):
    """While draining, /v1/completions yields 503 and /healthz reports
    draining — in-flight work still completes."""
    (p,) = _prompts((5,), seed=5)

    async def main():
        engine, server = await _start_server(model)
        st = server.engine.submit(p, max_new_tokens=10, temperature=0.0)
        # LB drain pattern: admissions close and /healthz flips to 503
        # while the listener stays up and in-flight work continues
        server.begin_drain()
        status, body = await _http(server.port, "POST", "/v1/completions",
                                   {"prompt": p, "max_tokens": 2})
        hstatus, hbody = await _http(server.port, "GET", "/healthz")
        toks, reason = await st.collect()
        await server.shutdown(drain=True)
        return status, json.loads(body), hstatus, json.loads(hbody), reason

    status, body, hstatus, health, reason = asyncio.run(main())
    assert status == 503 and body["error"]["type"] == "draining"
    assert hstatus == 503 and health["status"] == "draining"
    assert reason == "length"  # the in-flight request finished the drain


def test_server_sampling_and_spec_knobs_passthrough(model):
    """/v1/completions passes top_k/top_p and the speculative-decoding
    overrides through to the engine: top_k=1 at high temperature is
    greedy-exact, a spec-enabled server still serves token-exact greedy
    completions, and the spec series reaches /metrics. The prompt is
    repetitive so the n-gram drafter proposes FULL windows — the ragged
    width gate drops short lone drafts by design."""
    p = [5, 9, 11, 4] * 3
    ref = _reference(model, p, 8)

    async def main():
        engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64,
                           spec_decoding=True, num_spec_tokens=3)
        server = ServingServer(engine, host="127.0.0.1", port=0)
        await server.start()
        status, body = await _http(
            server.port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": 8, "temperature": 3.0, "top_k": 1,
             "top_p": 0.9},
        )
        # per-request opt-out rides the same body
        ostatus, obody = await _http(
            server.port, "POST", "/v1/completions",
            {"prompt": p, "max_tokens": 8, "spec_decoding": False},
        )
        bstatus, _ = await _http(server.port, "POST", "/v1/completions",
                                 {"prompt": p, "top_p": "hot"})
        mstatus, metrics = await _http(server.port, "GET", "/metrics")
        await server.shutdown(drain=True)
        return (engine, status, json.loads(body), ostatus, json.loads(obody),
                bstatus, mstatus, metrics.decode())

    engine, status, out, ostatus, oout, bstatus, mstatus, metrics = \
        asyncio.run(main())
    assert status == 200
    assert out["choices"][0]["token_ids"] == ref  # top_k=1 == greedy
    assert ostatus == 200
    assert oout["choices"][0]["token_ids"] == ref
    assert bstatus == 400
    assert mstatus == 200
    assert "paddle_tpu_serving_spec_proposed_tokens_total" in metrics
    # drafts may ride mixed steps under the unified ragged program, so
    # the drafted-rows counter (not verify-kind steps) is the signal
    assert "paddle_tpu_serving_spec_drafted_rows_total" in metrics
    assert _idle(engine)


@pytest.mark.slow
def test_server_soak_mixed_traffic(model):
    """Soak: waves of streamed/non-streamed/cancelled/timed-out requests
    with a tiny stream queue (forced backpressure). Afterwards the pool is
    at its idle free count, nothing is in flight, and survivors are
    token-exact."""
    prompts = _prompts((4, 6, 9, 12, 5, 7, 10, 8), seed=6)
    refs = {i: _reference(model, p, 8) for i, p in enumerate(prompts)}

    async def main():
        engine = LLMEngine(model, block_size=8, max_batch=4, max_seq_len=64)
        server = ServingServer(engine, host="127.0.0.1", port=0,
                               stream_queue_size=2, max_waiting=32)
        await server.start()
        exact = 0

        async def slow_consumer(p, i):
            # forced backpressure: read nothing until the request is done
            st = server.engine.submit(p, max_new_tokens=8, temperature=0.0)
            await asyncio.wait_for(st.done.wait(), 60.0)
            return await st.collect()

        for wave in range(3):
            tasks = [slow_consumer(prompts[0], 0)]
            for i, p in enumerate(prompts):
                if i % 4 == 3:
                    tasks.append(server.engine.submit(
                        p, max_new_tokens=8, temperature=0.0,
                        timeout_s=0.001 if wave % 2 else 30.0,
                    ).collect())
                else:
                    tasks.append(_http(
                        server.port, "POST", "/v1/completions",
                        {"prompt": p, "max_tokens": 8,
                         "stream": i % 2 == 0},
                    ))
            results = await asyncio.gather(*tasks)
            toks, reason = results[0]  # the starved consumer catches up
            assert toks == refs[0] and reason == "length"
            exact += 1
            for i, r in enumerate(results[1:]):
                if i % 4 == 3:
                    toks, reason = r
                    if reason == "length":
                        assert toks == refs[i]
                        exact += 1
                else:
                    status, body = r
                    assert status == 200
                    if i % 2 == 0:
                        toks, reason, done = _sse_tokens(body)
                        assert done and reason == "length"
                    else:
                        toks = json.loads(body)["choices"][0]["token_ids"]
                    assert toks == refs[i]
                    exact += 1
        await server.shutdown(drain=True)
        return engine, exact

    engine, exact = asyncio.run(main())
    assert exact >= 18  # every non-timeout request was token-exact
    assert engine.metrics.counters["backpressure_drops"] >= 1
    assert _idle(engine)
    assert engine._requests == {}


def test_server_debug_trace_and_healthz_pool(model):
    """Observability surface: /healthz carries the pool saturation gauges
    (no /metrics scrape needed), and /debug/trace serves the Perfetto
    trace when the engine traces — 404 with a hint when it does not."""
    (p,) = _prompts((9,), seed=7)

    async def main():
        # tracing OFF (default engine): /debug/trace is a guided 404
        engine_off, server_off = await _start_server(model)
        t_status, t_body = await _http(server_off.port, "GET", "/debug/trace")
        await server_off.shutdown(drain=True)

        # tracing ON: serve one request, then export
        engine = LLMEngine(model, block_size=8, max_batch=4,
                           max_seq_len=64, trace=1.0)
        server = ServingServer(engine, host="127.0.0.1", port=0)
        await server.start()
        status, _ = await _http(server.port, "POST", "/v1/completions",
                                {"prompt": p, "max_tokens": 4})
        d_status, d_body = await _http(server.port, "GET", "/debug/trace")
        h_status, h_body = await _http(server.port, "GET", "/healthz")
        await server.shutdown(drain=True)
        return (t_status, t_body, status, d_status, json.loads(d_body),
                h_status, json.loads(h_body), engine)

    (t_status, t_body, status, d_status, trace, h_status, health,
     engine) = asyncio.run(main())
    assert t_status == 404 and b"--trace" in t_body
    assert b"LLMEngine(trace=...)" in t_body and b"PADDLE_TPU" not in t_body
    assert status == 200 and d_status == 200
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"request", "ttft", "queued", "decode"} <= names
    assert any(n.startswith("step[") for n in names)
    assert trace["otherData"]["producer"] == "paddle_tpu.serving.trace"
    # healthz saturation gauges: pool tiers + queue depths, all idle now
    assert h_status == 200
    pool = health["pool"]
    assert pool["blocks_total"] == engine.pool.num_blocks - 1
    assert pool["blocks_truly_free"] + pool["blocks_cached_free"] \
        == pool["blocks_total"]
    assert pool["blocks_allocated"] == 0
    assert pool["requests_running"] == 0 and pool["requests_waiting"] == 0
    assert _idle(engine)


def test_server_per_request_trace_flag(model):
    """A request body's "trace": true forces itself into a sampled trace
    (sample fraction 0 of the stream would otherwise skip everyone)."""
    p1, p2 = _prompts((6, 8), seed=8)

    async def main():
        engine = LLMEngine(model, block_size=8, max_batch=4,
                           max_seq_len=64, trace=0.0001)
        server = ServingServer(engine, host="127.0.0.1", port=0)
        await server.start()
        s1, _ = await _http(server.port, "POST", "/v1/completions",
                            {"prompt": p1, "max_tokens": 3})
        s2, _ = await _http(server.port, "POST", "/v1/completions",
                            {"prompt": p2, "max_tokens": 3, "trace": True})
        d_status, d_body = await _http(server.port, "GET", "/debug/trace")
        await server.shutdown(drain=True)
        return s1, s2, d_status, json.loads(d_body)

    s1, s2, d_status, trace = asyncio.run(main())
    assert s1 == s2 == 200 and d_status == 200
    closed = [e for e in trace["traceEvents"] if e["name"] == "request"]
    assert len(closed) == 1              # only the forced request traced
    assert closed[0]["args"]["output_tokens"] == 3


def test_metrics_exposes_pool_saturation_gauges(model):
    """Observability satellite: the /healthz pool split (truly-free vs
    cached-free vs allocated blocks, running/waiting) must ALSO land on
    Prometheus /metrics — with HELP/TYPE — so dashboards never scrape a
    non-Prometheus endpoint. The gauges refresh at scrape time and agree
    with /healthz's live numbers on an idle engine."""
    async def run():
        engine, server = await _start_server(model)
        try:
            await server.engine.submit(
                _prompts((9,))[0], max_new_tokens=4).collect()
            mstatus, mbody = await _http(server.port, "GET", "/metrics")
            hstatus, hbody = await _http(server.port, "GET", "/healthz")
            return engine, mstatus, mbody.decode(), json.loads(hbody)
        finally:
            await server.shutdown()

    engine, mstatus, metrics, health = asyncio.run(run())
    assert mstatus == 200
    gauges = {}
    for line in metrics.splitlines():
        if line.startswith("paddle_tpu_serving_pool_"):
            name, val = line.rsplit(" ", 1)
            gauges[name] = float(val)
    # kv_dtype is the one non-numeric pool stat: /healthz carries the
    # string, /metrics carries it on the `kv` info family, not a gauge
    assert health["pool"]["kv_dtype"] == "float32"
    want = {f"paddle_tpu_serving_pool_{k}": float(v)
            for k, v in health["pool"].items() if not isinstance(v, str)}
    assert gauges == want                      # same live numbers
    assert gauges["paddle_tpu_serving_pool_blocks_total"] > 0
    assert gauges["paddle_tpu_serving_pool_blocks_allocated"] == 0  # idle
    for fam in ("pool_blocks_truly_free", "pool_blocks_cached_free",
                "pool_requests_running", "pool_requests_waiting"):
        assert f"# HELP paddle_tpu_serving_{fam} " in metrics
        assert f"# TYPE paddle_tpu_serving_{fam} gauge" in metrics
