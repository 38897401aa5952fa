"""`reduce/step_timeline.py` and its five readers on a capture recorded on the
chip from a tiny traced engine, with that run's step spans
(`benchmark/tools/record_step_timeline.py`; TPU v5 lite, my chip run, PR 38:
`data/step_timeline_1chip.xplane.pb`, `.steps.json`). The capture was looked
at by hand once (`python -m paddle_tpu.profiler.xplane`); what is asserted
about it here was read off the listed events, not computed by the code under
test, and is said where."""
import copy
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.reduce import breakdown, step_timeline  # noqa: E402
from benchmark.reduce import intervals as iv  # noqa: E402
from benchmark.reduce.xplane import Capture  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
CAPTURE = DATA / "step_timeline_1chip.xplane.pb"
NEW = ("step.device_ms_p50", "idle.host_share", "idle.launch_share",
       "idle.readback_share", "trace.clock_fit_us")


class _Run:
    """As much of `record.Run` as the readers look at."""

    def __init__(self, capture, steps):
        self.capture = capture
        self.engine_steps = steps
        self.notes = []
        self.config = {"kind": "serve"}


def _recorded():
    with open(DATA / "step_timeline_1chip.steps.json") as f:
        return json.load(f)["steps"]


def _read(run, name):
    got = spec.load_reader("per_layer", name).read(run)
    return got[0] if isinstance(got, tuple) else got


@pytest.fixture(scope="module")
def capture():
    return Capture(str(CAPTURE))


def test_every_step_of_the_capture_gets_its_run(capture):
    run = _Run(capture, _recorded())
    join = step_timeline.joined(run)
    clock = join.clocks[0]
    assert clock["why"] is None
    steps = [s for s in join.steps if s["launch_call_us"] is not None]
    assert len(steps) == clock["steps"] == clock["pairs"] == N_STEPS
    # two step programs ran: the width-1 one and the chunk-carrying one
    assert len({s["program"] for s in steps}) == 2
    assert all(s["program"].startswith("jit_step(") for s in steps)
    for s in steps:          # the whole chain, in order, on one clock
        assert s["launch_us"] <= s["issue_us"] <= s["enqueue_us"] \
            <= s["device_start_us"] < s["device_end_us"] <= s["done_us"] \
            <= s["read_us"]
    assert f"{N_STEPS} steps matched, " in run.notes[0]
    assert "points of a" in run.notes[0]


def test_the_clock_is_fitted_and_the_device_plane_shifted(capture):
    """Read off the capture by hand (PR 38): the profiler's device plane
    lies about SKEW_US early against its host plane on every step, so every
    step violates causality as aligned; the fit of the tracer's clock to the
    capture's host clock is a few microseconds."""
    clock = step_timeline.clock(_Run(capture, _recorded()))
    assert clock["fit_us"] < 10.0
    assert clock["violations"] == N_STEPS
    assert abs(clock["shift_us"] - SKEW_US) < 150.0
    assert 0.0 < clock["causal_width_us"] < 600.0
    assert clock["error_us"] == pytest.approx(
        clock["fit_us"] + clock["causal_width_us"])
    assert _read(_Run(capture, _recorded()), "trace.clock_fit_us") == \
        pytest.approx(clock["error_us"])


def test_the_three_shares_sum_to_the_idle_share_of_the_stretch(capture):
    run = _Run(capture, _recorded())
    shares = {n: _read(run, n) for n in NEW}
    assert all(v is not None for v in shares.values()), shares
    parts, window = step_timeline.idle_parts(run)
    # between the first placed run's start and the last one's, the three
    # parts and the runs are the whole of the time
    clock = step_timeline.clock(run)
    placed = [s for s in step_timeline.joined(run).steps
              if s["device_start_us"] is not None]
    first, last = (s["device_start_us"] for s in (placed[0], placed[-1]))
    assert sum(parts.values()) == pytest.approx((last - first) * 1e-6,
                                                rel=1e-9)
    # the same stretch on the device's own plane, by its operations (what
    # `device.idle_share` reads over the window): idle within half a point
    d0, d1 = ((t - clock["shift_us"]) * 1e3 + clock["offset_ns"]
              for t in (first, last))
    ops = iv.clip(capture.devices["/device:TPU:0"], d0, d1)
    idle = 100.0 * (1.0 - iv.busy(ops) / (d1 - d0))
    mine = 100.0 * sum(parts[p] for p in step_timeline.PARTS) \
        / sum(parts.values())
    assert abs(mine - idle) < 0.5
    assert idle > 95.0      # a tiny model: the chip waits nearly all the time
    # over the window the shares are `device.idle_share` less the two ends
    ends = 100.0 * (1.0 - (last - first) * 1e-6 / window)
    total = sum(shares[f"idle.{p}_share"] for p in step_timeline.PARTS)
    assert breakdown.idle_share(run) - ends - 0.5 <= total \
        <= breakdown.idle_share(run) + 0.5
    # a run on the `XLA Modules` line is a step program's operations back
    # to back: the device's own length of a decode step, by hand DEVICE_MS
    assert shares["step.device_ms_p50"] == pytest.approx(DEVICE_MS, rel=0.02)


def _tampered(tmp_path, edit):
    """The recorded capture with `edit(device plane's XLA Modules line)`
    applied, read back as the harness reads one."""
    from paddle_tpu.profiler._xplane import xplane_pb2

    xs = xplane_pb2.XSpace()
    xs.ParseFromString(CAPTURE.read_bytes())
    dev = next(p for p in xs.planes if p.name.startswith("/device:"))
    edit(next(ln for ln in dev.lines if ln.name == "XLA Modules"))
    path = tmp_path / "tampered.xplane.pb"
    path.write_bytes(xs.SerializeToString())
    return Capture(str(path))


def test_nothing_is_read_without_a_device_capture():
    """The CPU rehearsal: a capture with no device plane, or none at all."""
    class NoDevices:
        devices = {}

    for capture in (None, NoDevices()):
        run = _Run(capture, _recorded())
        assert [_read(run, n) for n in NEW] == [None] * 5
        assert run.notes == ["step timeline: nothing read: no device capture"]


def test_nothing_is_read_on_a_mismatch(tmp_path):
    """A step between two joined ones whose run is not on the line."""
    def drop_one(line):
        del line.events[len(line.events) // 2]

    run = _Run(_tampered(tmp_path, drop_one), _recorded())
    assert [_read(run, n) for n in NEW] == [None] * 5
    assert len(run.notes) == 1 and "lies between joined steps" in run.notes[0]


def test_a_violation_no_shift_mends_leaves_the_device_time_alone(capture):
    """One step whose read returned, by its span, a millisecond before the
    step's own launch: no shift of the device's plane makes that causal. The
    runs are still matched, so the device's own length reads; the shares and
    the clock's error do not."""
    steps = copy.deepcopy(_recorded())
    victim = steps[len(steps) // 2]
    start = victim["phases"]["dispatch"][0] - 1e-3
    victim["phases"]["sync"] = [start - 1e-4, start]
    run = _Run(capture, steps)
    got = {n: _read(run, n) for n in NEW}
    assert got.pop("step.device_ms_p50") == pytest.approx(DEVICE_MS,
                                                          rel=0.02)
    assert list(got.values()) == [None] * 4
    assert "no one shift" in run.notes[0]


def test_nothing_is_read_from_a_program_without_the_join(capture,
                                                         monkeypatch):
    """The parent commit's side of a traced run: its `join_engine_steps`
    has no `StepJoin` to return and its spans no `launch_ns`."""
    from paddle_tpu.profiler import xplane

    monkeypatch.delattr(xplane, "StepJoin")
    run = _Run(capture, _recorded())
    assert [_read(run, n) for n in NEW] == [None] * 5
    assert "does not find a step's run" in run.notes[0]


# Read off the recorded capture by hand (PR 38; recorded anew after the traced
# step went back to the one `np.asarray`). Twenty `paddle_tpu.step`
# annotations, ids 7-26, four of them chunk-carrying (7, 8, 9, 19). Step 18:
# its annotation begins 1,769 us after step 17's and its `DoEnqueueProgram`
# (run_id 104) 522 us later, yet run 104 is stamped 1,027 us after step 17's
# annotation on the device's plane: 1,264 us BEFORE the host enqueued it
# (1,316 on the worst step); the run's end lies 1,545 us or more before its
# `CompleteCallbacks` on every step. So the plane has to move by 1.32-1.55 ms.
# The sixteen decode runs last 39.5-41.0 us (eight) and 43.6-49.3 us (eight);
# `reduce/stats.py median` takes the lower middle of an even count: 41.0 us.
N_STEPS = 20
SKEW_US = 1430.0
DEVICE_MS = 0.0410
