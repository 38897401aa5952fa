"""A synthetic capture in the vendored xplane schema, shaped like a TPU v5e
one: a device plane whose `XLA Modules` line holds one event a program run
(stat `run_id`), and `/host:CPU` with the `paddle_tpu.step <id>` annotations
on the Python thread and the runtime's enqueue and completion (stat `run_id`),
issue and Done events on its own threads. Times in microseconds on the
capture's clock."""
from paddle_tpu.profiler._xplane import xplane_pb2

ISSUE = "tpu::System::Execute=>IssueSequencedEvent"
DONE = "tpu::System::Execute=>Done"
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"


class _Plane:
    def __init__(self, xs, name):
        self.plane = xs.planes.add()
        self.plane.name = name
        self._ids, self._lines = {}, {}

    def _meta(self, table, name):
        key = (table, name)
        table = getattr(self.plane, table)
        if key not in self._ids:
            mid = len(self._ids) + 1
            table[mid].id = mid
            table[mid].name = name
            self._ids[key] = mid
        return self._ids[key]

    def event(self, line, name, start_us, dur_us, **stats):
        if line not in self._lines:
            ln = self.plane.lines.add()
            ln.name = line
            ln.timestamp_ns = 0
            self._lines[line] = ln
        ev = self._lines[line].events.add()
        ev.metadata_id = self._meta("event_metadata", name)
        ev.offset_ps = round(start_us * 1e6)
        ev.duration_ps = round(dur_us * 1e6)
        for k, v in stats.items():
            st = ev.stats.add()
            st.metadata_id = self._meta("stat_metadata", k)
            st.uint64_value = v


def step_capture(steps, run_ids=True, runtime=True, skew_us=0.0,
                 program="jit_step(123)"):
    """`steps`: dicts with `id`, `launch` (the annotation's start),
    `call` (its length), `dev` (start, end of the run on the device) and,
    optionally, `issue` and `done` (default: 20 us before the run, 30 us
    after it; `done` None plants no Done), `complete` (the completion event
    that carries the run's id; default none), `run_id` (default 100 + id;
    None plants no run) and `program` (default: the capture's). `skew_us`
    is added to the device plane's stamps alone, as a profiler that
    misaligns its planes would. Without `run_ids` no event carries one;
    without `runtime` the host plane holds the annotations alone."""
    xs = xplane_pb2.XSpace()
    host = _Plane(xs, "/host:CPU")
    dev = _Plane(xs, "/device:TPU:0")
    for st in steps:
        rid = st.get("run_id", 100 + st["id"])
        host.event("python3", f"paddle_tpu.step {st['id']}", st["launch"],
                   st["call"])
        d0, d1 = st["dev"]
        if runtime:
            issue = st.get("issue", d0 - 20.0)
            host.event("tfrt-non-blocking-queue/1", ISSUE, issue, 15.0)
            host.event("tfrt-non-blocking-queue/1", ENQUEUE, issue + 5.0,
                       5.0, **({"run_id": rid} if run_ids and rid else {}))
            if st.get("done", True) is not None:
                host.event("futex-default/2", DONE,
                           st.get("done", d1 + 30.0), 10.0)
            if run_ids and rid and "complete" in st:
                host.event("futex-default/2", COMPLETE, st["complete"],
                           10.0, run_id=rid)
        if rid is not None:
            dev.event("XLA Modules", st.get("program", program),
                      d0 + skew_us, d1 - d0,
                      **({"run_id": rid} if run_ids else {}))
            dev.event("XLA Ops", "%fusion.1 = f32[8]{0} fusion(...)",
                      d0 + skew_us, d1 - d0)
    return xs


def step_records(steps, offset_us=0.0, jitter_us=()):
    """The host's records of the same steps (`step_span_records`' shape):
    the tracer's clock reads `offset_us` less than the capture's, and step
    `i`'s `launch_ns` is planted `jitter_us[i]` off its annotation's start.
    A step's `read` (default 60 us after the device's end) ends `sync`."""
    out = []
    for i, st in enumerate(steps):
        j = jitter_us[i] if i < len(jitter_us) else 0.0
        launch = st["launch"] - offset_us + j
        read = st.get("read", st["dev"][1] + 60.0) - offset_us
        out.append({
            "id": st["id"], "kind": st.get("kind", "decode"),
            "start": (launch - 100.0) * 1e-6, "end": (read + 50.0) * 1e-6,
            "phases": {"dispatch": ((launch - 20.0) * 1e-6,
                                    (launch + st["call"]) * 1e-6),
                       "sync": ((launch + st["call"]) * 1e-6, read * 1e-6)},
            "args": {"step": st["id"], "launch_ns": round(launch * 1e3),
                     "width": st.get("width", 1)}})
    return out


def write(xs, path):
    with open(path, "wb") as f:
        f.write(xs.SerializeToString())
    return str(path)
