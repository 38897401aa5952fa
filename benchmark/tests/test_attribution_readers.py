"""The six readers of step-span args, the `queue_wait` observation and the
kernels' own names, each on a hand-made `Run`: the numbers asserted were
worked out by hand from the made-up spans and intervals. A program that
records none of it (the parent of the PR that added them) must read as None
and never raise; the capture recorded before the kernels had names
(`data/small_trace_1chip.xplane.pb`, kernel `%step.1`) stands in for it."""
import sys
from pathlib import Path
from types import SimpleNamespace

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.record import Run  # noqa: E402
from benchmark.reduce.xplane import Capture  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000          # ns


def _read(name, run):
    return spec.load_reader("per_layer", name).read(run)


def _run(**kw):
    """A `record.Run` with nothing measured, then `kw` laid over it."""
    run = Run({}, {"name": "cell"}, {}, {}, None, {"platform": "tpu"}, None,
              0.0)
    for k, v in kw.items():
        setattr(run, k, v)
    return run


def _step(i, kind, start, end, **args):
    return {"id": i, "kind": kind, "start": start, "end": end,
            "phases": {}, "args": {"step": i, "kind": kind, **args}}


class _Capture:
    """As much of `reduce.xplane.Capture` as the readers look at: one chip,
    the window given, operations as `(start_ns, end_ns, name)`."""

    def __init__(self, ops, w0, w1):
        self.devices = {"/device:TPU:0": sorted(ops)}
        self._window = (w0, w1)

    def window(self):
        return self._window

    def windowed(self):
        return self.devices


def test_span_readers_take_the_windows_steps():
    steps = [
        # before the window: not read
        _step(1, "mixed", 0.0, 9.0, grid_live=999, grid_total=1000,
              h2d_arrays=13, h2d_ms=50.0),
        _step(2, "decode", 10.0, 11.0, grid_live=100, grid_total=1000,
              h2d_arrays=13, h2d_ms=2.0),
        _step(3, "decode", 11.0, 12.0, grid_live=110, grid_total=1000,
              h2d_arrays=13, h2d_ms=4.0),
        _step(4, "mixed", 12.0, 15.0, grid_live=700, grid_total=16000,
              h2d_arrays=15, h2d_ms=3.0),
    ]
    run = _run(t0=10.0, t1=20.0, engine_steps=steps)
    assert _read("kernel.ragged_live_grid_share", run) == \
        (100.0 * 910 / 18000, 3)
    assert _read("step.h2d_ms_p50", run) == (3.0, 3)
    assert _read("step.h2d_arrays", run) == (41 / 3, 3)


def test_span_readers_read_nothing_from_spans_without_the_args():
    old = [_step(2, "decode", 10.0, 11.0, rows=16, width=1, host_syncs=1)]
    for steps in (old, [], None):
        run = _run(t0=10.0, t1=20.0, engine_steps=steps)
        for name in ("kernel.ragged_live_grid_share", "step.h2d_ms_p50",
                     "step.h2d_arrays"):
            assert _read(name, run) is None, (name, steps)


def test_queue_wait_is_the_programs_own_summary():
    summary = {"ttft": {"count": 16, "mean_ms": 5300.0},
               "queue_wait": {"count": 16, "mean_ms": 1.25}}
    assert _read("sched.queue_wait_ms_mean",
                 _run(engine_latency=summary)) == (1.25, 16)
    for latency in (None, {}, {"ttft": summary["ttft"]}):
        assert _read("sched.queue_wait_ms_mean",
                     _run(engine_latency=latency)) is None


def _named(name, n):
    return f'%{name}.{n} = bf16[16,16,1,128]{{3,2,1,0}} custom-call(' \
           f'%fusion.{n}), custom_call_target="tpu_custom_call"'


def test_calls_per_step_joins_named_calls_to_whole_decode_steps():
    # the capture's clock is the host's, in ns, moved by 5 s
    clock = 5_000_000_000.0
    ops = []
    for step_start_ms in (100, 200, 300, 400):
        for layer in range(3):
            s = clock + (step_start_ms + 10 + 20 * layer) * MS
            ops.append((s, s + 5 * MS, _named("ragged_paged_attention",
                                              layer)))
            # a consumer names the kernel among its operands: not a call
            ops.append((s + 6 * MS, s + 7 * MS,
                        f"%fusion.{layer} = bf16[16] fusion("
                        f"%ragged_paged_attention.{layer}), kind=kLoop"))
        ops.append((clock + (step_start_ms + 1) * MS,
                    clock + (step_start_ms + 9) * MS,
                    "%copy.31.remat2 = bf16[8] copy(%p.1)"))
    steps = [_step(1, "decode", 0.100, 0.190),    # begins before the trace
             _step(2, "decode", 0.200, 0.290),
             _step(3, "mixed", 0.300, 0.390),     # not a decode step
             _step(4, "decode", 0.400, 0.490)]
    run = _run(capture=_Capture(ops, clock + 150 * MS, clock + 500 * MS),
               traced=(0.150, 0.500), trace_clock_ns=clock,
               engine_steps=steps)
    assert _read("kernel.ragged_calls_per_step", run) == (3.0, 2)
    # a second kernel under its own name leaves the count alone
    ops.append((clock + 205 * MS, clock + 206 * MS, _named("kv_write", 0)))
    run.capture = _Capture(ops, clock + 150 * MS, clock + 500 * MS)
    assert _read("kernel.ragged_calls_per_step", run) == (3.0, 2)


def test_flash_bwd_share_is_of_device_busy_time():
    ops = [(0, 20 * MS, _named("flash_fwd", 1)),
           (20 * MS, 50 * MS, _named("flash_bwd_dkv", 1)),
           (50 * MS, 60 * MS, _named("flash_bwd_dq", 1)),
           # 40 ms of idle, then a fusion that consumes a kernel's output
           (100 * MS, 140 * MS,
            "%fusion.7 = bf16[8] fusion(%flash_bwd_dq.1), kind=kLoop")]
    run = _run(capture=_Capture(ops, 0, 200 * MS))
    share, calls = _read("kernel.flash_bwd_time_share", run)
    assert calls == 2 and abs(share - 100.0 * 40 / 100) < 1e-9


def test_name_readers_read_nothing_from_a_program_that_names_no_kernel():
    recorded = Capture(str(DATA / "small_trace_1chip.xplane.pb"))
    w0, w1 = recorded.window()
    step = _step(1, "decode", 0.0, (w1 - w0) * 1e-9)
    run = _run(capture=recorded, traced=(0.0, (w1 - w0) * 1e-9),
               trace_clock_ns=float(w0), engine_steps=[step])
    assert _read("kernel.ragged_calls_per_step", run) is None
    assert _read("kernel.flash_bwd_time_share", run) is None
    # and nothing without a capture, or without a device plane in it
    for capture in (None, SimpleNamespace(devices={})):
        run = _run(capture=capture, engine_steps=[step])
        assert _read("kernel.ragged_calls_per_step", run) is None
        assert _read("kernel.flash_bwd_time_share", run) is None
