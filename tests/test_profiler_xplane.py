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
        rows = xplane.join_engine_steps(chrome, path)
    assert [r["step"] for r in rows] == [0, 7]
    assert rows[0]["kind"] == "decode"
    assert rows[0]["capture_dur_us"] == pytest.approx(2000.0)
    assert rows[0]["capture_plane"] == "/host:CPU"
    assert rows[1]["capture_dur_us"] is None  # step 7 not captured


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
        rows = xplane.join_engine_steps(engine.tracer.chrome_trace(), td)
    joined = [r for r in rows if r["capture_dur_us"] is not None]
    assert joined, "no host step span joined to the capture"
    for r in joined:
        assert r["step"] in spans
        assert r["capture_dur_us"] > 0
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
