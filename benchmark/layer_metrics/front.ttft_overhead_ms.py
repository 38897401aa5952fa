"""What the front end adds to time to first token: the clients' mean (from
send) minus the engine's own mean (`ServingMetrics.latency_summary`, timed
from `Request.arrival_time` inside the engine), one clock, the same requests.
Means and not medians: the program gives a summary and no samples, and with
first tokens seconds apart two medians of slightly different convention
differ by seconds. Over the whole load, ramp included: a window holds too few
first tokens today."""
from benchmark.reduce import client


def read(run):
    ttfts = client.ttfts(run.samples, run.load_start, run.t1)
    inside = (run.engine_latency or {}).get("ttft")
    if not ttfts or not inside or inside["count"] != len(ttfts):
        return None
    return sum(ttfts) / len(ttfts) * 1e3 - inside["mean_ms"], len(ttfts)
