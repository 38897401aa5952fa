"""Training-run observability (profiler/tracing.py + hapi TrainMonitor).

Acceptance criteria from the training-observability issue:

- a traced `Model.fit` exports valid Chrome/Perfetto trace-event JSON
  with exactly the train-step span vocabulary the docs rely on
  (``train_step`` + ``data``/``shard``/``dispatch``/``sync``/``callback``
  phase children) — the schema canary, mirroring
  test_serving_trace.py's;
- tracing OFF is the pre-trace code path: `train_tracer()` is None,
  every hook is one pointer test, and the loss trajectory is identical
  to a traced run (tracing never changes a number);
- `xplane.join_engine_steps` joins training captures by step id exactly
  like serving ones (the dispatch runs under the same
  ``paddle_tpu.step <id>`` annotation);
- `TrainMonitor`: grad global norm in the logs (computed inside the one
  compiled program), non-finite loss detection with an actionable
  message, loss-spike warnings, and the recompile sentinel (warns when
  steady-state training keeps tracing new XLA programs).
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.hapi.callbacks import Callback, TrainMonitor
from paddle_tpu.io import Dataset
from paddle_tpu.profiler import tracing
from paddle_tpu.profiler.tracing import TrainTracer

_PH = {"X", "i", "M"}
_PHASES = {"data", "shard", "dispatch", "sync", "callback"}


@pytest.fixture(autouse=True)
def _fresh_tracing():
    tracing.reset_train_tracing()
    yield
    tracing.reset_train_tracing()


class _Toy(Dataset):
    def __init__(self, n=32, seed=0):
        rs = np.random.RandomState(seed)
        self.x = rs.rand(n, 8).astype(np.float32)
        self.y = rs.randint(0, 4, (n, 1))

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


class _Recorder(Callback):
    def __init__(self):
        super().__init__()
        self.logs = []

    def on_train_batch_end(self, step, logs=None):
        self.logs.append(dict(logs or {}))


def _fit(epochs=1, n=32, batch_size=8, callbacks=None, seed=0, lr=1e-3):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=lr, parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    rec = _Recorder()
    model.fit(_Toy(n), epochs=epochs, batch_size=batch_size, verbose=0,
              shuffle=False, callbacks=[rec] + list(callbacks or []))
    return model, rec


def _validate(trace):
    json.loads(json.dumps(trace))
    for ev in trace["traceEvents"]:
        assert ev["ph"] in _PH, ev
        assert isinstance(ev["name"], str) and ev["name"], ev
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0, ev
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0, ev


# -- schema canary (CI gate against train-trace drift) -----------------------

def test_train_trace_schema_canary():
    tr = tracing.enable_train_tracing()
    _fit(epochs=1)
    trace = tr.chrome_trace()
    _validate(trace)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "train_step" in names
    assert _PHASES <= names, names
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert "paddle-tpu-train" in procs

    steps = [e for e in trace["traceEvents"] if e["name"] == "train_step"]
    assert len(steps) == 4                       # 32 samples / batch 8
    for ev in steps:
        for key in ("step", "batch", "batch_size", "loss", "launch_ns"):
            assert key in ev["args"], ev["args"]
        assert ev["args"]["batch_size"] == 8
    # `Model.fit` launches through `Tracer.launch` as every stack does: the
    # clock pair's monotonic half lies inside the step's `dispatch` phase
    epoch_ns = trace["otherData"]["epoch_ns"]
    dispatch = {e["args"]["step"]: e for e in trace["traceEvents"]
                if e.get("ph") == "X" and e["name"] == "dispatch"}
    for ev in steps:
        d = dispatch[ev["args"]["step"]]
        at_us = (ev["args"]["launch_ns"] - epoch_ns) / 1e3
        assert d["ts"] - 1.0 <= at_us <= d["ts"] + d["dur"] + 1.0
    # step ids are consecutive and spans carry monotonically ordered steps
    assert [e["args"]["batch"] for e in steps] == [0, 1, 2, 3]


def test_phases_nest_inside_their_train_step():
    tr = tracing.enable_train_tracing()
    _fit(epochs=1)
    evs = tr.chrome_trace()["traceEvents"]
    steps = {e["args"]["step"]: e for e in evs
             if e.get("ph") == "X" and e["name"] == "train_step"}
    phases = [e for e in evs if e.get("ph") == "X" and e["name"] in _PHASES]
    assert steps and phases
    eps = 1e-3
    for ph in phases:
        parent = steps[ph["args"]["step"]]
        assert ph["ts"] >= parent["ts"] - eps, (ph, parent)
        assert (ph["ts"] + ph["dur"]
                <= parent["ts"] + parent["dur"] + eps), (ph, parent)
    # every step carries all five phases (fit's full instrumentation)
    by_step = {}
    for ph in phases:
        by_step.setdefault(ph["args"]["step"], set()).add(ph["name"])
    assert all(v == _PHASES for v in by_step.values()), by_step


# -- tracing off is the pre-trace path --------------------------------------

def test_trace_off_loss_trajectory_identical():
    tracing.reset_train_tracing()
    assert tracing.train_tracer() is None       # hook sites see None
    _, rec_off = _fit(epochs=2)
    losses_off = [l["loss"] for l in rec_off.logs]

    tr = tracing.enable_train_tracing()
    _, rec_on = _fit(epochs=2)
    losses_on = [l["loss"] for l in rec_on.logs]
    assert losses_on == losses_off               # tracing never changes math
    assert len(tr.chrome_trace()["traceEvents"]) > 0


def test_environment_does_not_turn_training_tracing_on(monkeypatch):
    """Training is traced only where code asks: `enable_train_tracing` is
    the one switch, and no variable of the environment is a second."""
    monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
    monkeypatch.setenv("PADDLE_TPU_TRACE_BUF", "64")
    tracing.reset_train_tracing()
    assert tracing.train_tracer() is None
    tr = tracing.enable_train_tracing()
    assert isinstance(tr, TrainTracer) and tr.capacity == 65536
    assert tracing.train_tracer() is tr          # stable across calls
    tracing.disable_train_tracing()
    assert tracing.train_tracer() is None


def test_standalone_train_batch_records_span():
    """train_batch outside fit closes its own span (no fit loop to do it)."""
    tr = tracing.enable_train_tracing()
    paddle.seed(0)
    net = nn.Linear(8, 4)
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    x = paddle.to_tensor(np.ones((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4, 1), np.int64))
    model.train_batch([x], [y])
    spans = [e for e in tr.chrome_trace()["traceEvents"]
             if e["name"] == "train_step"]
    assert len(spans) == 1
    names = {e["name"] for e in tr.chrome_trace()["traceEvents"]}
    # standalone: no loader, no callback list — the three core phases only
    assert {"shard", "dispatch", "sync"} <= names
    assert "data" not in names
    assert spans[0]["args"]["launch_ns"] > 0


def test_train_dispatch_span_unit():
    """The one-phase span ShardedTrainStep/pipeline steps record."""
    tr = TrainTracer(capacity=256)
    with tracing.train_dispatch_span(tr, {"source": "unit"}) as sid:
        pass
    evs = tr.chrome_trace()["traceEvents"]
    span = next(e for e in evs if e["name"] == "train_step")
    assert span["args"]["step"] == sid
    assert span["args"]["source"] == "unit"
    child = next(e for e in evs if e["name"] == "dispatch")
    assert child["args"]["step"] == sid
    # the clock pair's monotonic half, stamped inside `dispatch` just
    # before the annotation, as a serving step's is (`Tracer.launch`)
    epoch_ns = tr.chrome_trace()["otherData"]["epoch_ns"]
    at_us = (span["args"]["launch_ns"] - epoch_ns) / 1e3
    assert child["ts"] <= at_us <= child["ts"] + child["dur"] + 1.0


def test_dispatch_spans_join_to_their_runs_on_one_clock(tmp_path):
    """One join serves both stacks: `train_step` spans (kind None, no
    `sync` phase) find their program's run on a capture's device plane by
    the same annotation and `launch_ns`. With no read to bound the run's
    end, the runtime's Done does."""
    import _xplane_synth as synth

    from paddle_tpu.profiler import xplane

    tr = TrainTracer(capacity=256)
    for _ in range(5):
        with tracing.train_dispatch_span(tr):
            pass
    records = xplane.step_span_records(tr.chrome_trace())
    assert [r["kind"] for r in records] == [None] * 5
    assert all(set(r["phases"]) == {"dispatch"} for r in records)
    for i, r in enumerate(records):     # empty bodies: space them 5 ms apart
        r["args"]["launch_ns"] += i * 5_000_000
    # a capture whose host clock reads 7 s more than this tracer's, each
    # run 300 us after its launch, 2 ms long
    steps = [{"id": r["id"], "call": 100.0,
              "launch": r["args"]["launch_ns"] / 1e3 + 7e6,
              "dev": (r["args"]["launch_ns"] / 1e3 + 7e6 + 300.0,
                      r["args"]["launch_ns"] / 1e3 + 7e6 + 2300.0)}
             for r in records]
    path = synth.write(synth.step_capture(steps, skew_us=-900.0),
                       tmp_path / "cap.xplane.pb")
    join = xplane.join_engine_steps(records, path)
    clock = join.clocks[0]
    assert clock["why"] is None
    assert clock["offset_ns"] == pytest.approx(7e9, abs=1e3)
    assert clock["violations"] == 5
    assert clock["shift_us"] == pytest.approx(900.0 + 7.5, abs=0.01)
    for r, rec in zip(join.steps, records):
        assert r["kind"] is None and r["read_us"] is None
        assert r["device_us"] == pytest.approx(2000.0)
        assert r["device_start_us"] - r["launch_us"] == \
            pytest.approx(307.5, abs=0.01)


def test_instrumented_step_delegates_and_traces():
    """The pipeline-step wrapper: records a span per call while tracing,
    stays fully transparent otherwise — jit's AOT surface (.lower) must
    reach the wrapped function (test_pipeline_schedules' memory analysis
    broke on an opaque wrapper once; never again)."""
    import jax

    jfn = jax.jit(lambda x: x * 2)
    step = tracing.InstrumentedStep(jfn, {"source": "unit"})
    assert step.lower(1.0) is not None          # delegation to jit
    tracing.disable_train_tracing()
    assert float(step(2.0)) == 4.0              # transparent when off
    tr = tracing.enable_train_tracing()
    assert float(step(3.0)) == 6.0
    spans = [e for e in tr.chrome_trace()["traceEvents"]
             if e["name"] == "train_step"]
    assert len(spans) == 1 and spans[0]["args"]["source"] == "unit"


# -- xplane join works for training captures --------------------------------

def test_training_capture_joins_by_step_id(tmp_path):
    import jax

    from paddle_tpu.profiler import xplane

    tr = tracing.enable_train_tracing()
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    ds = _Toy()
    # compile outside the capture so it records steady-state steps
    model.fit(ds, epochs=1, batch_size=8, verbose=0, shuffle=False)
    with jax.profiler.trace(str(tmp_path)):
        model.fit(ds, epochs=1, batch_size=8, verbose=0, shuffle=False)
    spans = xplane.engine_step_spans(str(tmp_path))
    assert spans, "no step annotations reached the capture"
    rows = xplane.join_engine_steps(tr.chrome_trace(), str(tmp_path)).steps
    assert rows and all(r["kind"] is None for r in rows)  # training spans
    joined = [r for r in rows if r["launch_call_us"] is not None]
    assert joined, "no train_step span joined to the capture"
    for r in joined:
        assert r["step"] in spans
        assert r["launch_call_us"] > 0 and r["host_dur_us"] > 0


# -- TrainMonitor ------------------------------------------------------------

def test_monitor_grad_norm_in_logs():
    _, rec_plain = _fit(epochs=1)
    assert all("grad_norm" not in l for l in rec_plain.logs)  # opt-in only
    mon = TrainMonitor()
    model, rec = _fit(epochs=1, callbacks=[mon])
    assert rec.logs and all("grad_norm" in l for l in rec.logs)
    assert all(np.isfinite(l["grad_norm"]) and l["grad_norm"] > 0
               for l in rec.logs)
    assert not model._monitor_grad_norm        # restored at train end
    assert mon.nan_events == 0 and mon.retrace_warnings == 0
    # steady state: exactly one program, zero retraces
    assert model.jit_retraces == 0


def test_monitor_nonfinite_loss_raises_actionably():
    paddle.seed(0)
    net = nn.Linear(8, 4)
    net.weight.set_value(np.full((8, 4), np.nan, np.float32))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    with pytest.raises(RuntimeError, match="non-finite loss.*check_nan_inf"):
        model.fit(_Toy(), epochs=1, batch_size=8, verbose=0,
                  callbacks=[TrainMonitor()])


def test_monitor_nan_stop_sets_stop_training():
    paddle.seed(0)
    net = nn.Linear(8, 4)
    net.weight.set_value(np.full((8, 4), np.nan, np.float32))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    mon = TrainMonitor(nan_action="stop")
    with pytest.warns(RuntimeWarning, match="non-finite loss"):
        model.fit(_Toy(), epochs=3, batch_size=8, verbose=0,
                  callbacks=[mon])
    assert model.stop_training
    # "stop" stops the EPOCH too — no further batches ran on condemned
    # state (the first NaN batch is the only one)
    assert mon.nan_events == 1


def test_monitor_raise_restores_flags():
    """A raising monitor must not leak its debug switches: the exception
    unwinds past fit, so the restore cannot wait for on_train_end."""
    from paddle_tpu.flags import get_flags

    paddle.seed(0)
    net = nn.Linear(8, 4)
    net.weight.set_value(np.full((8, 4), np.nan, np.float32))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss())
    with pytest.raises(RuntimeError, match="non-finite"):
        model.fit(_Toy(), epochs=1, batch_size=8, verbose=0,
                  callbacks=[TrainMonitor(check_nan_inf=True)])
    assert get_flags("FLAGS_check_nan_inf")["FLAGS_check_nan_inf"] is False
    assert model._monitor_grad_norm is False


def test_monitor_loss_spike_warns_unit():
    mon = TrainMonitor(spike_window=16, spike_factor=4.0)
    for i in range(10):
        mon.on_train_batch_end(i, {"loss": 1.0 + 0.01 * i})
    with pytest.warns(RuntimeWarning, match="loss spike"):
        mon.on_train_batch_end(10, {"loss": 50.0})
    assert mon.spike_warnings == 1
    # warnings are bounded — a pathological run cannot spam thousands
    for i in range(20):
        mon.on_train_batch_end(11 + i, {"loss": 50.0 + i})
    assert mon.spike_warnings <= mon.max_warnings
    # ... and the caps are PER KIND: exhausted spike budget must not
    # silence the recompile sentinel

    class _Stub:
        jit_traces = 1
        jit_retraces = 0
        stop_training = False

    stub = _Stub()
    mon.set_model(stub)
    mon.on_epoch_begin(0)
    mon.on_train_batch_end(0, {})        # warmup baseline
    stub.jit_traces = 2
    with pytest.warns(RuntimeWarning, match="recompile sentinel"):
        mon.on_train_batch_end(1, {})
    assert mon.retrace_warnings == 1


def test_stop_training_does_not_truncate_eval():
    """stop_training stops TRAIN epochs only: a stopped fit's eval pass
    (and any later standalone evaluate) must still see every sample."""
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                parameters=net.parameters())
    from paddle_tpu.metric import Accuracy

    model.prepare(opt, paddle.nn.CrossEntropyLoss(), Accuracy())
    model.stop_training = True           # as a stopped fit leaves it
    seen = []

    class _EvalRec(Callback):
        def on_eval_batch_end(self, step, logs=None):
            seen.append(step)

    model.evaluate(_Toy(32), batch_size=8, verbose=0,
                   callbacks=[_EvalRec()])
    assert seen == [0, 1, 2, 3]          # all 4 batches, not 1


def test_monitor_recompile_sentinel_unit():
    class _Stub:
        jit_traces = 1
        jit_retraces = 0
        stop_training = False

    stub = _Stub()
    mon = TrainMonitor(warmup_steps=1)
    mon.set_model(stub)
    mon.on_epoch_begin(0)
    mon.on_train_batch_end(0, {"loss": 1.0})   # warmup: baseline = 1
    mon.on_train_batch_end(1, {"loss": 1.0})   # steady, no new trace: quiet
    stub.jit_traces = 2
    with pytest.warns(RuntimeWarning, match="recompile sentinel"):
        mon.on_train_batch_end(2, {"loss": 1.0})
    assert mon.retrace_warnings == 1
    # epoch boundary re-baselines (first eval program is not a retrace)
    stub.jit_traces = 3
    mon.on_epoch_begin(1)
    mon.on_train_batch_end(0, {"loss": 1.0})
    assert mon.retrace_warnings == 1


def test_monitor_recompile_sentinel_fires_on_ragged_batches():
    """The real thing: a dataset whose last batch is ragged compiles a
    second program mid-epoch — exactly the per-step compile churn the
    sentinel exists to surface."""
    mon = TrainMonitor(warmup_steps=1)
    with pytest.warns(RuntimeWarning, match="recompile sentinel"):
        _fit(epochs=1, n=20, batch_size=8, callbacks=[mon])  # 8, 8, 4
    assert mon.retrace_warnings == 1
