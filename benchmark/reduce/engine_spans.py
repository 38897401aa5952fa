"""The program's step spans (`serving/trace.py EngineTracer`: one `step[kind]`
span a step with the phases plan, build, dispatch, sync, emit, on
`time.monotonic()`), read back as plain records."""
from __future__ import annotations

PHASES = ("plan", "build", "dispatch", "sync", "emit")


def steps(tracer, t0, t1):
    """Steps that began and ended inside `[t0, t1]`: `{"id", "kind", "start",
    "end", "phases": {name: (start, end)}, "args"}`, in order."""
    by_id = {}
    for ev in tracer.chrome_trace()["traceEvents"]:
        if ev.get("ph") != "X" or ev.get("pid") != 1 or ev.get("tid") != 0:
            continue
        sid = ev["args"]["step"]
        start = tracer.epoch + ev["ts"] / 1e6
        span = (start, start + ev["dur"] / 1e6)
        rec = by_id.setdefault(sid, {"id": sid, "phases": {}})
        if ev["name"].startswith("step["):
            rec.update(kind=ev["args"]["kind"], start=span[0], end=span[1],
                       args=ev["args"])
        elif ev["name"] in PHASES:
            rec["phases"][ev["name"]] = span
    return [r for _, r in sorted(by_id.items())
            if "kind" in r and r["start"] >= t0 and r["end"] <= t1]
