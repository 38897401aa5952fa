"""Cross-stack trace analysis tool (profiler/xplane.py): capture a real
jax.profiler trace and read op summaries back without TF/TensorBoard."""
import glob
import os
import tempfile

import numpy as np
import pytest


@pytest.mark.slow
def test_summarize_roundtrip():
    import io as _io

    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import xplane

    with tempfile.TemporaryDirectory() as td:
        @jax.jit
        def f(x):
            return jnp.tanh(x @ x.T).sum()

        x = jnp.asarray(np.random.RandomState(0).rand(256, 256).astype(np.float32))
        f(x).block_until_ready()
        with jax.profiler.trace(td):
            for _ in range(3):
                r = f(x)
            r.block_until_ready()
        files = xplane.find_xplane_files(td)
        assert files, os.listdir(td)
        # CPU captures carry host planes; device_only=False must see ops
        summary = xplane.summarize(td, device_only=False)
        assert summary, "no planes parsed"
        total = sum(e["total_ms"] for e in summary.values())
        assert total > 0
        assert any(e["by_category"] for e in summary.values())
        buf = _io.StringIO()
        xplane.print_summary(td, device_only=False, file=buf)
        assert "busy" in buf.getvalue()


def test_interval_union_stats_empty_is_zeroed():
    """An empty interval list (metrics scraped before the first engine
    step) must yield a zeroed stats record, not IndexError (flagged in the
    serving-frontend issue: /metrics can fire before any step lands)."""
    from paddle_tpu.profiler import xplane

    st = xplane.interval_union_stats([])
    assert st == {"span_ms": 0.0, "busy_ms": 0.0, "idle_ms": 0.0,
                  "utilization": 0.0, "n_ops": 0, "top_gaps": []}
    # and the shape still renders through the shared printer
    import io

    buf = io.StringIO()
    xplane.print_schedule_analysis({"empty-plane": st}, file=buf)
    assert "empty-plane" in buf.getvalue()


def test_schedule_analysis_math():
    """Executor-schedule statistics (reference executor_statistics.cc):
    exact busy/idle/gap math on a hand-built device capture."""
    from paddle_tpu.profiler import xplane
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = "matmul.1"
    plane.event_metadata[2].id = 2
    plane.event_metadata[2].name = "fusion.2"
    plane.event_metadata[3].id = 3
    plane.event_metadata[3].name = "allreduce.3"
    line = plane.lines.add()
    line.name = "XLA Ops"
    line.timestamp_ns = 0
    # [0,10ms] matmul, [10,12] fusion (back to back), GAP 8ms, [20,25] ar
    for mid, off_ms, dur_ms in ((1, 0, 10), (2, 10, 2), (3, 20, 5)):
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = int(off_ms * 1e9)
        ev.duration_ps = int(dur_ms * 1e9)

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "cap.xplane.pb")
        with open(path, "wb") as f:
            f.write(xs.SerializeToString())
        st = xplane.schedule_analysis(path)
    s = st["/device:TPU:0"]
    assert s["span_ms"] == 25.0
    assert s["busy_ms"] == 17.0
    assert s["idle_ms"] == 8.0
    assert abs(s["utilization"] - 17.0 / 25.0) < 1e-9
    assert s["top_gaps"][0]["gap_ms"] == 8.0
    assert s["top_gaps"][0]["after_op"] == "fusion.2"
    assert s["top_gaps"][0]["before_op"] == "allreduce.3"


def _device_capture(offset_events, clock_base_ns=0):
    """Minimal one-plane capture with [offset_ms, duration_ms] events."""
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/device:TPU:0"
    plane.event_metadata[1].id = 1
    plane.event_metadata[1].name = "op.1"
    line = plane.lines.add()
    line.name = "XLA Ops"
    line.timestamp_ns = clock_base_ns
    for off_ms, dur_ms in offset_events:
        ev = line.events.add()
        ev.metadata_id = 1
        ev.offset_ps = int(off_ms * 1e9)
        ev.duration_ps = int(dur_ms * 1e9)
    return xs


def test_schedule_analysis_reports_per_capture():
    """Two capture files with the SAME plane name but unrelated clock bases
    must be reported per-capture, NOT unioned into one timeline whose
    inter-capture dead time shows up as a giant idle gap."""
    from paddle_tpu.profiler import xplane

    with tempfile.TemporaryDirectory() as td:
        # capture A: 10ms busy starting at t=0; capture B: 10ms busy whose
        # clock base is 100 SECONDS later (a separate trace session)
        for name, xs in (
            ("a.xplane.pb", _device_capture([(0, 10)], clock_base_ns=0)),
            ("b.xplane.pb", _device_capture([(0, 10)],
                                            clock_base_ns=int(100e9))),
        ):
            with open(os.path.join(td, name), "wb") as f:
                f.write(xs.SerializeToString())
        st = xplane.schedule_analysis(td)
        assert len(st) == 2, st.keys()  # one entry per capture
        for s in st.values():
            # each capture is 100% busy over its own 10ms span — the old
            # union view reported ~100s span with a ~100s idle gap
            assert s["span_ms"] == 10.0
            assert s["busy_ms"] == 10.0
            assert s["idle_ms"] == 0.0
            assert not s["top_gaps"]


@pytest.mark.slow  # tier-1 headroom (PR 19): heaviest always-on case; tier-2 covers it
def test_real_capture_schema_canary():
    """VERDICT residual risk: schema drift in jax's xplane output would
    pass CI (the math tests build captures by hand) and fail in the
    field. Record a REAL `jax.profiler` capture of a tiny jitted loop and
    assert every structural property the tool chain depends on, straight
    off the serialized ``.xplane.pb``:

    - the logdir contains exactly the capture file `find_xplane_files`
      globs for;
    - the vendored minimal proto parses it: planes carry lines, lines
      carry events, and every event's ``metadata_id`` resolves through
      ``event_metadata`` to a non-empty name with a positive duration
      (the exact fields `summarize`/`schedule_analysis` read);
    - the jitted loop is VISIBLE: an op named after our function reaches
      `summarize`'s op table, so event->metadata name resolution works on
      real data, not just hand-built messages;
    - `schedule_analysis` fed the ``.pb`` path (not the dir) yields a
      plane with at least as many ops as the loop ran steps, a positive
      span, and a sane utilization.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import xplane
    from paddle_tpu.profiler._xplane import xplane_pb2

    steps = 5
    with tempfile.TemporaryDirectory() as td:
        @jax.jit
        def tiny_loop_step(x):
            return jnp.tanh(x @ x.T).sum()

        x = jnp.ones((128, 128))
        tiny_loop_step(x).block_until_ready()  # compile outside the trace
        with jax.profiler.trace(td):
            acc = jnp.float32(0.0)
            for _ in range(steps):
                acc = acc + tiny_loop_step(x)
            acc.block_until_ready()

        files = xplane.find_xplane_files(td)
        assert len(files) == 1, os.listdir(td)
        pb = files[0]
        assert pb.endswith(".xplane.pb")

        xs = xplane_pb2.XSpace()
        with open(pb, "rb") as f:
            xs.ParseFromString(f.read())
        event_planes = [p for p in xs.planes
                        if any(line.events for line in p.lines)]
        assert event_planes, [p.name for p in xs.planes]
        n_resolved = 0
        total_dur_ps = 0
        for plane in event_planes:
            em = plane.event_metadata
            for line in plane.lines:
                for ev in line.events:
                    assert ev.metadata_id in em, (plane.name, line.name)
                    assert em[ev.metadata_id].name, ev.metadata_id
                    total_dur_ps += ev.duration_ps
                    n_resolved += 1
        assert n_resolved >= steps
        # durations must carry real time — a schema change that zeroes
        # duration_ps would make every busy/utilization stat silently 0
        assert total_dur_ps > 0

        meta_names = [em[mid].name for plane in event_planes
                      for em in (plane.event_metadata,) for mid in em]
        assert any("tiny_loop_step" in n for n in meta_names)
        # ... and the same op flows through summarize's name resolution
        # (top= wide enough that a fast op is not cut by busy-time rank)
        summary = xplane.summarize(pb, device_only=False, top=100000)
        ops = [name for entry in summary.values()
               for name, _ in entry["by_op"]]
        assert any("tiny_loop_step" in name for name in ops)

        st = xplane.schedule_analysis(pb)
        assert st, "no planes analyzed from the pb file"
        best = max(st.values(), key=lambda s: s["n_ops"])
        assert best["n_ops"] >= steps
        assert best["span_ms"] > 0
        assert 0 < best["utilization"] <= 1.0


def test_schedule_analysis_on_real_cpu_capture():
    """CPU captures have no device plane: the host fallback still yields a
    utilization view."""
    import io as _io

    import jax
    import jax.numpy as jnp

    from paddle_tpu.profiler import xplane

    with tempfile.TemporaryDirectory() as td:
        f = jax.jit(lambda a: jnp.tanh(a @ a.T).sum())
        x = jnp.ones((256, 256))
        f(x).block_until_ready()
        with jax.profiler.trace(td):
            for _ in range(3):
                r = f(x)
            r.block_until_ready()
        st = xplane.schedule_analysis(td)
        assert st, "no planes analyzed"
        s = next(iter(st.values()))
        assert s["span_ms"] > 0 and 0 < s["utilization"] <= 1.0
        buf = _io.StringIO()
        xplane.print_schedule_analysis(td, file=buf)
        assert "util" in buf.getvalue()


# -- serving-trace <-> device-capture join (observability issue) ------------

def _annotated_capture(step_spans):
    """Capture whose host plane carries `paddle_tpu.step <id>` annotation
    events at [offset_ms, dur_ms] — what a jax.profiler trace of a
    tracing-enabled serve contains."""
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    plane = xs.planes.add()
    plane.name = "/host:CPU"
    line = plane.lines.add()
    line.name = "python"
    line.timestamp_ns = 0
    for mid, (sid, off_ms, dur_ms) in enumerate(step_spans, start=1):
        plane.event_metadata[mid].id = mid
        plane.event_metadata[mid].name = f"paddle_tpu.step {sid}"
        ev = line.events.add()
        ev.metadata_id = mid
        ev.offset_ps = int(off_ms * 1e9)
        ev.duration_ps = int(dur_ms * 1e9)
    return xs


def test_engine_step_spans_and_join():
    """`engine_step_spans` maps annotation events to step ids;
    `join_engine_steps` lines them up with the serving trace's host step
    spans, leaving capture fields None where the capture has no data."""
    from paddle_tpu.profiler import xplane

    xs = _annotated_capture([(0, 0.0, 2.0), (1, 3.0, 1.5)])
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "cap.xplane.pb")
        with open(path, "wb") as f:
            f.write(xs.SerializeToString())
        spans = xplane.engine_step_spans(path)
        assert set(spans) == {0, 1}
        assert spans[0]["dur_us"] == pytest.approx(2000.0)
        assert spans[1]["start_us"] == pytest.approx(3000.0)
        assert spans[1]["plane"] == "/host:CPU"

        chrome = {"traceEvents": [
            {"name": "step[decode]", "ph": "X", "pid": 1, "tid": 0,
             "ts": 100.0, "dur": 1900.0, "args": {"step": 0,
                                                  "kind": "decode"}},
            {"name": "step[mixed]", "ph": "X", "pid": 1, "tid": 0,
             "ts": 5000.0, "dur": 800.0, "args": {"step": 7,
                                                  "kind": "mixed"}},
            # phase children and request spans must NOT join
            {"name": "dispatch", "ph": "X", "pid": 1, "tid": 0,
             "ts": 150.0, "dur": 100.0, "args": {"step": 0}},
        ]}
        join = xplane.join_engine_steps(chrome, path)
    rows = join.steps
    assert [r["step"] for r in rows] == [0, 7]
    assert rows[0]["kind"] == "decode"
    # the annotation is the launch CALL on the host, and is named so
    assert rows[0]["launch_call_us"] == pytest.approx(2000.0)
    assert rows[0]["capture_plane"] == "/host:CPU"
    assert rows[1]["launch_call_us"] is None  # step 7 not captured
    # a host-only capture places no run, and says why
    assert rows[0]["device_us"] is None and rows[0]["device_start_us"] is None
    assert "no device plane" in join.clocks[0]["why"]


# -- the step's run on the device, on the tracer's clock (PR 38) -----------

def _steps(n=8, period=5000.0, call=400.0, to_start=700.0, run=3000.0):
    return [{"id": i, "launch": 1000.0 + period * i, "call": call,
             "dev": (1000.0 + period * i + to_start,
                     1000.0 + period * i + to_start + run)}
            for i in range(n)]


def _join(tmp_path, steps, records=None, **capture):
    import _xplane_synth as synth
    from paddle_tpu.profiler import xplane

    path = synth.write(synth.step_capture(steps, **capture),
                       tmp_path / "cap.xplane.pb")
    if records is None:
        records = synth.step_records(steps)
    return xplane.join_engine_steps(records, path)


def test_join_finds_each_steps_run_on_the_device(tmp_path):
    """Every step gets the event of the device plane's `XLA Modules` line
    that it launched, by `run_id` through the host's enqueue event."""
    steps = _steps()
    join = _join(tmp_path, steps)
    clock = join.clocks[0]
    assert clock["why"] is None
    assert clock["steps"] == clock["pairs"] == len(steps)
    for st, r in zip(steps, join.steps):
        assert r["step"] == st["id"] and r["program"] == "jit_step(123)"
        assert r["run_id"] == 100 + st["id"]
        assert r["device_us"] == pytest.approx(3000.0)
        assert r["launch_call_us"] == pytest.approx(400.0)
        # one clock, the tracer's (offset 0 here): the whole chain in order
        assert r["launch_us"] == pytest.approx(st["launch"])
        assert r["enqueue_us"] == pytest.approx(st["dev"][0] - 15.0)
        assert r["device_start_us"] == pytest.approx(st["dev"][0])
        assert r["device_end_us"] == pytest.approx(st["dev"][1])
        assert r["issue_us"] == pytest.approx(st["dev"][0] - 20.0)
        assert r["done_us"] == pytest.approx(st["dev"][1] + 30.0)
        assert r["read_us"] == pytest.approx(st["dev"][1] + 60.0)
    assert clock["violations"] == 0 and clock["shift_us"] == 0.0
    # the steps' mean period, by the device's own clock
    assert clock["period_us"] == pytest.approx(5000.0)


def test_join_without_run_ids_matches_nothing_and_says_why(tmp_path):
    """There is no matching by order: a capture whose host events carry no
    `run_id` gives the annotations' own spans and the reason."""
    join = _join(tmp_path, _steps(), run_ids=False)
    assert "carries a run_id" in join.clocks[0]["why"]
    assert all(r["device_us"] is None for r in join.steps)
    assert join.steps[0]["launch_call_us"] == pytest.approx(400.0)


def test_join_keys_on_a_runs_enqueue_not_on_its_completion(tmp_path):
    """What an overlapped loop produces: the completion event of step N,
    which carries N's `run_id`, lands after annotation N+1 began and before
    N+1's own enqueue. It names nobody: every step keeps its own run."""
    steps = _steps()
    for a, b in zip(steps, steps[1:]):
        a["complete"] = b["launch"] + 50.0    # N+1 enqueues 685 us in
    join = _join(tmp_path, steps)
    assert join.clocks[0]["why"] is None
    assert [r["run_id"] for r in join.steps] == \
        [100 + st["id"] for st in steps]
    assert len({r["run_id"] for r in join.steps}) == len(steps)


def test_join_places_a_loop_that_launches_a_step_before_its_predecessor_ends(
        tmp_path):
    """One step in flight ahead of the host (serving/engine.py `step`): a
    step is launched and issued while the run before it is on the device,
    the runs go back to back, and the capture began inside a run whose step
    it did not see. A Done names no run: handed out one a step, step 0
    would take that run's (the first after its issue), and every later
    step its predecessor's, each before its own run ends. Every run's own
    completion event carries its id, and those alone bound the ends: each
    step is placed, beside its own end."""
    run = 3000.0
    ghost = {"id": 99, "launch": 100.0, "call": 400.0, "run_id": None,
             "dev": (1000.0, 1000.0 + run)}
    steps = [{"id": i, "launch": 1500.0 + run * i, "call": 400.0,
              "issue": 1800.0 + run * i,
              "dev": (1000.0 + run * (i + 1), 1000.0 + run * (i + 2)),
              "complete": 1020.0 + run * (i + 2)} for i in range(8)]
    join = _join(tmp_path, [ghost] + steps, skew_us=-1150.0)
    clock = join.clocks[0]
    assert clock["why"] is None and clock["violations"] == 0
    placed = {r["step"]: r for r in join.steps}
    assert placed[99]["device_start_us"] is None
    for st in steps:
        r = placed[st["id"]]
        assert r["run_id"] == 100 + st["id"]
        assert r["done_us"] == pytest.approx(st["complete"])
        assert r["device_end_us"] <= r["done_us"] <= r["read_us"]


def test_join_holds_steps_of_one_width_to_one_program(tmp_path):
    """Another program's enqueue between a step's annotation and its own
    (a swap, a prefix copy) would hand the step that run: the span's
    `width` names the program, so the join sees it and places nothing."""
    steps = _steps()
    steps[4]["program"] = "jit_swap_out(9)"
    join = _join(tmp_path, steps)
    why = join.clocks[0]["why"]
    assert "step 4 of width 1" in why and "jit_swap_out(9)" in why
    assert all(r["device_us"] is None for r in join.steps)
    # a wider step runs a program of its own, and that is no fault
    steps[4]["width"] = 128
    join = _join(tmp_path, steps)
    assert join.clocks[0]["why"] is None
    assert join.steps[4]["program"] == "jit_swap_out(9)"


def test_join_matches_by_run_id_not_by_position(tmp_path):
    """A run of another program between two steps (a copy-on-write, a
    swap) and steps cut off at the capture's two ends do not move the
    match when the host's enqueue events carry the run's id."""
    import _xplane_synth as synth
    from paddle_tpu.profiler import xplane

    steps = _steps(6)
    xs = synth.step_capture(steps)
    dev = next(p for p in xs.planes if p.name == "/device:TPU:0")
    modules = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    stray = modules.events.add()        # someone else's program, mid-way
    stray.CopyFrom(modules.events[2])
    stray.offset_ps += 3_500_000_000
    del stray.stats[:]
    del modules.events[0]               # the first step's run: not captured
    del modules.events[4]               # nor the last one's (index moved)
    path = synth.write(xs, tmp_path / "cap.xplane.pb")
    join = xplane.join_engine_steps(synth.step_records(steps), path)
    clock = join.clocks[0]
    assert clock["why"] is None
    assert [r["run_id"] for r in join.steps] == \
        [None, 101, 102, 103, 104, None]
    assert clock["steps"] == 4


@pytest.mark.parametrize("missing,says", [
    ((3,), "step 3 lies between joined steps"),
    ((0, 1, 2, 3, 4, 5, 6), "no step's run_id is on the device's line"),
])
def test_join_with_a_missing_run_returns_nothing_and_says_why(
        tmp_path, missing, says):
    import _xplane_synth as synth
    from paddle_tpu.profiler import xplane

    steps = _steps()
    xs = synth.step_capture(steps)
    dev = next(p for p in xs.planes if p.name == "/device:TPU:0")
    modules = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    for i in sorted(missing, reverse=True):     # not on the device's line
        del modules.events[i]
    if len(missing) > 1:                        # the one left: a stranger's
        del modules.events[0].stats[:]
    path = synth.write(xs, tmp_path / "cap.xplane.pb")
    join = xplane.join_engine_steps(synth.step_records(steps), path)
    assert says in join.clocks[0]["why"]
    assert all(r["device_us"] is None and r["device_start_us"] is None
               for r in join.steps)
    # the annotation's own span is still there
    assert join.steps[0]["launch_call_us"] == pytest.approx(400.0)


def test_join_fits_one_clock_from_every_steps_pair(tmp_path):
    """The offset is the median of (annotation's start - `launch_ns`), the
    fit's error their quartile distance; every stamp comes back on the
    tracer's clock."""
    import statistics

    import _xplane_synth as synth

    steps = _steps()
    jitter = (0.0, 2.0, -2.0, 4.0, -4.0, 1.0, -1.0, 30.0)   # one outlier
    records = synth.step_records(steps, offset_us=123456.0, jitter_us=jitter)
    join = _join(tmp_path, steps, records=records)
    clock = join.clocks[0]
    # launch_ns = annotation - offset + jitter
    diffs = [123456.0 - j for j in jitter]
    assert clock["offset_ns"] == pytest.approx(
        statistics.median(diffs) * 1e3)
    q1, _, q3 = statistics.quantiles(diffs, n=4)
    assert clock["fit_us"] == pytest.approx(q3 - q1)
    assert clock["error_us"] == clock["fit_us"]          # nothing shifted
    mid = statistics.median(diffs)
    for st, r in zip(steps, join.steps):
        assert r["device_start_us"] == pytest.approx(st["dev"][0] - mid)
        assert r["read_us"] == pytest.approx(st["dev"][1] + 60.0 - 123456.0)


def test_join_counts_a_planted_causality_violation(tmp_path):
    """One step whose run ends after its read returned: counted, with its
    margin; a shift that mends it is found between the other steps'
    bounds."""
    steps = _steps()
    steps[5]["done"] = steps[5]["dev"][1] + 500.0
    steps[5]["read"] = steps[5]["dev"][1] - 8.0      # heard before the end
    join = _join(tmp_path, steps)
    clock = join.clocks[0]
    assert clock["violations"] == 1
    assert clock["worst_us"] == pytest.approx(8.0)
    # every step is causal for shifts in [-15 (the enqueue, 15 us before
    # the start), -8]: the middle is taken, the width added
    assert clock["causal_width_us"] == pytest.approx(7.0)
    assert clock["shift_us"] == pytest.approx(-11.5)
    assert clock["error_us"] == pytest.approx(clock["fit_us"] + 7.0)
    # the same doubt in the shares' unit: points of the 5 ms period
    assert clock["error_points"] == pytest.approx(
        100 * clock["error_us"] / 5000.0)


def test_join_shifts_a_skewed_device_plane_into_the_causal_interval(tmp_path):
    """What a TPU v5e capture shows: every run stamped 1.15 ms early
    against the host plane. Every step violates; the plane is shifted to
    the middle of the interval that makes all of them causal."""
    steps = _steps()
    join = _join(tmp_path, steps, skew_us=-1150.0)
    clock = join.clocks[0]
    assert clock["violations"] == len(steps)
    assert clock["worst_us"] == pytest.approx(1150.0 - 15.0)
    # enqueue 15 us before the true start, Done 30 us after the true end
    assert clock["causal_width_us"] == pytest.approx(45.0)
    assert clock["shift_us"] == pytest.approx(1150.0 + (30.0 - 15.0) / 2)
    for st, r in zip(steps, join.steps):
        assert r["device_start_us"] == pytest.approx(st["dev"][0] + 7.5)
        assert r["enqueue_us"] <= r["device_start_us"]
        assert r["device_end_us"] <= r["done_us"] <= r["read_us"]


def test_join_places_nothing_where_one_side_only_is_bounded(tmp_path):
    """A skewed device plane with nothing that follows a run's end (no
    Done, no completion event, no `sync` to end): there is no interval to
    take the middle of, so nothing is placed and no share is read."""
    import _xplane_synth as synth
    from paddle_tpu.profiler import xplane

    steps = _steps()
    for st in steps:
        st["done"] = None
    records = synth.step_records(steps)
    for r in records:
        del r["phases"]["sync"]
    join = _join(tmp_path, steps, records=records, skew_us=-1150.0)
    clock = join.clocks[0]
    assert "one side only" in clock["why"] and clock["error_us"] is None
    assert clock["violations"] == len(steps)
    assert all(r["device_us"] == pytest.approx(3000.0) for r in join.steps)
    assert all(r["device_start_us"] is None for r in join.steps)
    assert xplane.split_idle(
        [r for r in join.steps if r["device_start_us"] is not None]) == []


def test_join_refuses_violations_no_one_shift_mends(tmp_path):
    steps = _steps()
    steps[2]["read"] = steps[2]["dev"][1] - 100.0    # needs a shift < -100
    steps[2]["done"] = steps[2]["dev"][1] + 500.0
    join = _join(tmp_path, steps)                    # enqueue: shift >= -15
    clock = join.clocks[0]
    assert "no one shift" in clock["why"] and clock["causal_width_us"] < 0
    # matched, not placed
    assert all(r["device_us"] == pytest.approx(3000.0) for r in join.steps)
    assert all(r["device_start_us"] is None for r in join.steps)


def test_split_idle_cuts_a_period_at_the_read_and_the_next_launch(tmp_path):
    from paddle_tpu.profiler import xplane

    join = _join(tmp_path, _steps())
    parts = xplane.split_idle(join.steps)
    assert len(parts) == len(join.steps) - 1
    for p in parts:
        # period 5,000: run 3,000; heard 60 after the end; the next launch
        # 700 before the next start
        assert p["run"] == pytest.approx(3000.0)
        assert p["readback"] == pytest.approx(60.0)
        assert p["launch"] == pytest.approx(700.0)
        assert p["host"] == pytest.approx(5000.0 - 3000.0 - 60.0 - 700.0)
    # a loop that launches the next step before it has read this one: the
    # parts stay inside the gap and still sum to it
    a, b = dict(join.steps[0]), dict(join.steps[1])
    b["launch_us"] = a["device_end_us"] - 1000.0
    (p,) = xplane.split_idle([a, b])
    gap = b["device_start_us"] - a["device_end_us"]
    assert p["readback"] == pytest.approx(60.0) and p["host"] == 0.0
    assert p["readback"] + p["host"] + p["launch"] == pytest.approx(gap)


def test_step_timeline_cli_prints_the_three_parts(tmp_path, capsys):
    """`python -m paddle_tpu.profiler.xplane <capture> --steps <dump>`:
    what an operator runs on a capture of a live server and its
    /debug/trace."""
    import json

    import _xplane_synth as synth
    from paddle_tpu.profiler import xplane

    steps = _steps()
    path = synth.write(synth.step_capture(steps), tmp_path / "cap.xplane.pb")
    events = []
    for r in synth.step_records(steps):
        events.append({"name": "step[decode]", "ph": "X", "pid": 1,
                       "tid": 0, "ts": r["start"] * 1e6,
                       "dur": (r["end"] - r["start"]) * 1e6,
                       "args": {**r["args"], "kind": "decode"}})
        for name, (s, e) in r["phases"].items():
            events.append({"name": name, "ph": "X", "pid": 1, "tid": 0,
                           "ts": s * 1e6, "dur": (e - s) * 1e6,
                           "args": {"step": r["id"]}})
    dump = tmp_path / "trace.json"
    dump.write_text(json.dumps({"traceEvents": events,
                                "otherData": {"epoch_ns": 0}}))
    assert xplane.main([path, "--steps", str(dump)]) == 0
    out = capsys.readouterr().out
    assert "8 steps matched, 8 clock pairs" in out
    for part in ("run", "readback", "host", "launch", "readback + launch"):
        assert f"  {part}" in out


def test_join_on_real_traced_serve():
    """End to end: a tracing-enabled engine served under
    `jax.profiler.trace` stamps its step ids into the capture, and the
    join recovers device/host rows for the steps the capture covered."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import GPT, GPTConfig
    from paddle_tpu.profiler import xplane
    from paddle_tpu.serving import LLMEngine

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, attn_impl="xla",
                    dropout=0.0)
    model = GPT(cfg)
    model.eval()
    engine = LLMEngine(model, block_size=8, max_batch=2, max_seq_len=64,
                       trace=1.0)
    rs = np.random.RandomState(0)
    # compile outside the capture so the trace records steady-state steps
    engine.generate([rs.randint(0, 128, (9,)).tolist()], max_new_tokens=2)
    with tempfile.TemporaryDirectory() as td:
        with jax.profiler.trace(td):
            engine.generate([rs.randint(0, 128, (7,)).tolist(),
                             rs.randint(0, 128, (12,)).tolist()],
                            max_new_tokens=4)
        spans = xplane.engine_step_spans(td)
        assert spans, "no step annotations reached the capture"
        rows = xplane.join_engine_steps(engine.tracer.chrome_trace(),
                                        td).steps
    joined = [r for r in rows if r["launch_call_us"] is not None]
    assert joined, "no host step span joined to the capture"
    for r in joined:
        assert r["step"] in spans
        assert r["launch_call_us"] > 0
        # the annotation wraps only the dispatch, so it can never exceed
        # the full host step span by more than measurement jitter
        assert r["host_dur_us"] > 0


@pytest.mark.parametrize("name,folded", [
    ("fusion.123", "fusion"),
    ("copy.31.remat2", "copy"),
    ("ragged_paged_attention.7", "ragged_paged_attention"),
    # a v5e capture names an event by the operation's whole HLO text
    ("%copy.315.remat2 = bf16[24,16,2048,16,128]{4,3,2,1,0} "
     "copy(bf16[24,16,2048,16,128]{4,1,3,2,0} %get-tuple-element.3)", "copy"),
    ("%flash_bwd_dkv.5 = (bf16[96,2048,128], bf16[96,2048,128]) "
     "custom-call(%p.1), custom_call_target=\"tpu_custom_call\"",
     "flash_bwd_dkv"),
    ("all-reduce-start.3", "all-reduce-start"),
    ("convolution_add_fusion", "convolution_add_fusion"),
    # a host plane's events are no HLO names and stay whole
    ("$core.py:123 bind", "$core.py:123 bind"),
    ("PjitFunction(step)", "PjitFunction(step)"),
])
def test_category_folds_an_operation_at_its_first_dot(name, folded):
    """One row a kernel and one for the copies in `print_summary`, as
    `benchmark/reduce/intervals.py category` folds them for the ledger."""
    from paddle_tpu.profiler.xplane import _category

    assert _category(name) == folded
