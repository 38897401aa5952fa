"""Find the knee of a serving configuration under an open-loop mix: the
highest offered rate it sustains. One process, one engine, the rates one after
another, each for `--seconds`, with what is in flight hung up on in between:

    python3 benchmark/sweep.py --config c1p3b-serve --traffic chat \
        --rates 0.5,1,2,4 --seconds 30 --seed 1

A line a rate: offered and completed requests a second, the slope of the
backlog (requests in flight against time, least squares; a sustained rate has
a slope near 0), refusals, medians and tails from when a request was due, and
how late the generator sent. The cells then use fixed rates below and above
the knee found here; a run never searches."""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)

SAMPLE_S = 0.25


def _slope(points):
    """Least-squares slope of `(t, y)` points."""
    n = len(points)
    if n < 2:
        return 0.0
    mt = sum(t for t, _ in points) / n
    my = sum(y for _, y in points) / n
    den = sum((t - mt) ** 2 for t, _ in points)
    return sum((t - mt) * (y - my) for t, y in points) / den if den else 0.0


async def _one_rate(port, mix, rate, seconds, seed, vocab):
    from benchmark import spec
    from benchmark.loadgen import LoadGen
    from benchmark.reduce import client
    from benchmark.reduce.stats import median, quantile

    mix = spec.merged(mix, {"loop": {"rate_rps": rate}})
    load = LoadGen(port, spec.resolve(mix["generator"])(mix, seed, vocab),
                   mix["loop"])
    load.start()
    t0 = time.monotonic()
    backlog = []
    while time.monotonic() - t0 < seconds:
        await asyncio.sleep(SAMPLE_S)
        backlog.append((time.monotonic() - t0,
                        sum(s.sent is not None and s.ended is None
                            for s in load.samples)))
    t1 = time.monotonic()
    await load.stop()
    sent = [s for s in load.samples if s.sent is not None]
    ttfts = client.ttfts(sent, t0, t1)
    gaps = client.itl_gaps(sent, t0, t1)
    late = client.lateness(sent, t0, t1)
    tokens, span = client.out_tok_s(sent, t0, t1)
    ms = lambda x: None if x is None else round(x * 1e3, 2)  # noqa: E731
    return {
        "rate_rps": rate,
        "sent": len(sent),
        "completed_rps": sum(s.ended is not None and not s.failed
                             for s in sent) / (t1 - t0),
        "refused": sum(s.refused for s in sent),
        "failed": sum(s.failed for s in sent),
        # the second half only: the first fills the lanes at any rate
        "backlog_slope_rps": _slope(backlog[len(backlog) // 2:]),
        "backlog_end": backlog[-1][1],
        "first_tokens": len(ttfts),
        "ttft_p50_ms": ms(median(ttfts)),
        "ttft_p95_ms": ms(quantile(ttfts, 95)),
        "itl_p50_ms": ms(median(gaps)),
        "itl_p99_ms": ms(quantile(gaps, 99)),
        "out_tok_s": tokens / span if span else None,
        "late_p50_ms": ms(median(late)),
        "late_max_ms": ms(max(late, default=None)),
    }


async def _sweep(engine, config, mix, args, vocab):
    from paddle_tpu.serving import ServingServer

    server = ServingServer(engine, host="127.0.0.1", port=0,
                           **config.get("server", {}))
    await server.start()
    rows = []
    try:
        for rate in args.rates:
            row = await _one_rate(server.port, mix, rate, args.seconds,
                                  args.seed, vocab)
            print(json.dumps(row), flush=True)
            rows.append(row)
            # what was hung up on is aborted by the engine within a step or
            # two; the next rate starts on empty lanes
            while engine.has_unfinished():
                await asyncio.sleep(0.05)
    finally:
        await server.shutdown(drain=False)
    return rows


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--rates", required=True,
                   type=lambda s: [float(x) for x in s.split(",")])
    p.add_argument("--seconds", type=float, default=30.0)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--lengths-seed", type=int, default=None,
                   help="lay this `lengths_seed` over the mix's: a mix that "
                   "fixes its lengths, tried by hand on lengths it never saw")
    p.add_argument("--out", default=None, help="also write the table here")
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: the rehearsal, at tiny sizes")
    args = p.parse_args()

    from benchmark import device, spec
    from benchmark.runners.serve import build_engine

    bench = spec.load_benchmark()
    entry = spec.by_name(bench["configs"], args.config, "configuration")
    config = spec.load_json(ROOT / entry["file"])
    mix = spec.load_json(spec.HERE / "traffic" / f"{args.traffic}.json")
    if args.platform == "cpu":
        device.rehearse_on_cpu(config["chips"])
        config = spec.merged(config, config["rehearsal"])
        mix = spec.merged(mix, mix["rehearsal"])
    if args.lengths_seed is not None:
        mix = spec.merged(mix, {"lengths_seed": args.lengths_seed})
    found = device.describe()
    why = device.refusal(found, args.platform, config["chips"])
    if why is not None:
        print(f"sweep: {why}; not run", file=sys.stderr)
        return 3

    import paddle_tpu  # noqa: F401

    device.place_compile_cache()
    _, engine = build_engine(config, args.seed)
    engine.warmup()
    rows = asyncio.run(_sweep(engine, config, mix, args,
                              spec.model_kwargs(config)["vocab_size"]))
    result = {"config": args.config, "traffic": args.traffic,
              "seconds": args.seconds, "seed": args.seed, "device": found,
              "rows": rows}
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
