"""A serving cell: the configuration's model behind `ServingServer` on
loopback, load from `loadgen`, one window. Generator, server, engine and
tracer share this one process, because a chip belongs to one process."""
from __future__ import annotations

import asyncio
import time

from .. import correct, device, spec, weights
from ..loadgen import LoadGen
from ..reduce import client, engine_spans
from ..reduce.stats import median
from ..tracing import DeviceTracer

POLL_S = 0.01
GRACE_S = 0.05      # reading goes on this long after the ramp and after the
                    # window, so that a burst cut in two is read whole


def build_engine(config, seed, **extra):
    """`(model, engine)`: the configuration's model with weights from `seed`
    and the engine over it at the configuration's sizes."""
    from paddle_tpu.serving import LLMEngine

    model = weights.build_model(config, seed)
    return model, LLMEngine(model, seed=seed, **config["engine"], **extra)


def run(run, log):
    config, args = run.config, run.args
    # a sampling fraction that small traces no request and every step: the
    # step spans are all the readers use
    traced = {"trace": 1e-9, "trace_buffer": 1 << 20} if args.trace else {}
    model, engine = build_engine(config, args.seed, **traced)
    log(f"engine: {engine.pool.num_blocks} KV blocks of "
        f"{engine.block_size}, widths {engine.width_buckets}")
    compiled = engine.warmup()
    log(f"warm-up compiled {compiled} step programs")
    vocab = spec.model_kwargs(config)["vocab_size"]
    generator = spec.resolve(run.mix["generator"])
    requests = generator(run.mix, args.seed, vocab)
    asyncio.run(_drive(run, engine, requests, log))
    run.memory_peak_bytes = device.memory_peak_bytes()
    params = {k: p._array for k, p in model.named_parameters_dict().items()}
    # the program's state (the arena) is freed before the reference runs
    del engine, model
    run.correct, run.compared = correct.serving(
        config, params, run.samples, args.seed, log,
        control=config["control"] if args.control else ())


async def _drive(run, engine, requests, log):
    from paddle_tpu.serving import ServingServer

    mix, args = run.mix, run.args
    server = ServingServer(engine, host="127.0.0.1", port=0,
                           **run.config.get("server", {}))
    await server.start()
    load = LoadGen(server.port, requests, mix["loop"])
    tracer = DeviceTracer(run.cell["name"]) if args.trace else None
    metrics = engine.metrics
    loop = asyncio.get_running_loop()
    try:
        metrics.reset_schedule()
        at_load = dict(metrics.counters)
        load.start()
        await _ramp(load, mix["ramp"], log)
        before = dict(metrics.counters)
        run.compile_setup = run.clock.snapshot()
        run.t0 = time.monotonic()
        # a closed loop's window holds a fixed amount of work where the mix
        # says how much: it closes when the clients have read that many
        # tokens, and `--seconds` is the cap. Else it is a window of time
        want = (mix.get("window") or {}).get("tokens")
        base = load.tokens_read
        cap = run.t0 + args.seconds
        trace_s = min(run.config["trace_seconds"], args.seconds)
        while True:
            now, read = time.monotonic(), load.tokens_read - base
            if tracer is not None and tracer.due(now, run.t0, cap, trace_s,
                                                 read, want):
                await loop.run_in_executor(None, tracer.start_profiler)
                tracer.mark_start()
            if now >= cap or (want is not None and read >= want):
                break
            await asyncio.sleep(min(POLL_S, max(0.0, cap - now)))
        run.t1 = time.monotonic()
        after = dict(metrics.counters)
        run.compile_window = run.clock.snapshot()
        run.gauges = dict(metrics.gauges)
        run.engine_latency = metrics.latency_summary()
        if tracer is not None:
            tracer.stop()
        await asyncio.sleep(GRACE_S)
        if want is not None:
            run.t1, run.tokens_short = client.close_on_tokens(
                load.samples, run.t0, want, cap)
    finally:
        await load.stop()
        await server.shutdown(drain=False)
    run.counters = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    run.load_counters = {k: after.get(k, 0) - at_load.get(k, 0)
                         for k in after}
    run.load_start = load.started
    run.samples = load.samples
    if tracer is not None:
        tracer.load_into(run)
        run.engine_steps = engine_spans.steps(engine.tracer, load.started,
                                              run.t1)
    sent = [s for s in load.samples
            if s.sent is not None and run.in_window(s.sent)]
    # every request the window saw: sent in it, or in flight when it began
    seen = [s for s in load.samples if s.sent is not None and s.sent <= run.t1
            and (s.ended is None or s.ended >= run.t0)]
    run.attempted = len(seen)
    run.failed = sum(s.failed for s in seen)
    gaps = client.itl_gaps(load.samples, run.t0, run.t1)
    log(f"window: {len(gaps)} token gaps, median "
        f"{(median(gaps) or 0) * 1e3:.1f} ms, largest "
        f"{max(gaps, default=0) * 1e3:.1f} ms")
    if want is not None:
        log(f"window closes on {want} tokens: {run.tokens_short} short at "
            f"the cap of {args.seconds:g}s")
        # a closed loop's next request is admitted by the first plan after
        # it arrives: how long after the burst that ended its predecessor
        # it left says how near a step's edge that is
        bursts = sorted(t for s in load.samples for t in s.token_times)
        left = [(s.sent - max((t for t in bursts if t <= s.sent),
                              default=s.sent)) * 1e3 for s in sent]
        if left:
            log(f"turn-round: the window's {len(left)} requests left "
                f"{min(left):.1f}-{max(left):.1f} ms after the last token "
                "read")
    log(f"window {run.seconds:.2f}s: {len(seen)} requests seen, "
        f"{len(sent)} sent in it, {run.failed} failed, "
        f"{sum(s.ended is not None and run.in_window(s.ended) for s in seen)}"
        f" ended in it; jit_retraces {run.gauges.get('jit_retraces', 0)}; "
        f"counters {run.counters}")


async def _ramp(load, ramp, log):
    """Set-up traffic: the load runs until `first_tokens` requests have had
    their first token, so the window opens on full lanes (and, with shared
    prefixes, on a cache that holds them). A fixed amount of work, not of
    time."""
    t0 = time.monotonic()
    want = int(ramp["first_tokens"])
    while sum(bool(s.token_times) for s in load.samples) < want:
        if time.monotonic() - t0 > ramp["max_s"]:
            raise RuntimeError(
                f"ramp: {want} first tokens did not arrive in "
                f"{ramp['max_s']}s")
        bad = [s for s in load.samples if s.failed]
        if bad:
            raise RuntimeError(f"ramp: request {bad[0].index} failed: "
                               f"HTTP {bad[0].status} {bad[0].error}")
        await asyncio.sleep(POLL_S)
    # the burst that brought the last of them is read whole before the window
    # opens, so that none of its tokens counts as the window's
    await asyncio.sleep(GRACE_S)
    log(f"ramp: {want} first tokens after {time.monotonic() - t0:.2f}s, "
        f"{len(load.samples)} requests sent")
