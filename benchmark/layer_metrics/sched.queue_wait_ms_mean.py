"""Mean time a request waited for a lane: from `Request.arrival_time` to its
first admission by the scheduler, which the program observes as `queue_wait`
on its own clock (`time.monotonic()` inside the engine, the origin and clock
of its `ttft`, so the two subtract: mean `ttft` minus this is prefill). From
`ServingMetrics.latency_summary()` of the whole load, ramp included: a window
admits no request today. Samples are the requests admitted. None where the
program observes no `queue_wait`."""


def read(run):
    waited = (run.engine_latency or {}).get("queue_wait")
    if not waited or not waited["count"]:
        return None
    return waited["mean_ms"], waited["count"]
