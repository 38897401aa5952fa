"""benchmark/tests/test_hybrid_readers.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_hybrid_readers import *  # noqa: F401,F403


# -- PR 39: the two readers of the routed experts' grouped matmul, on a
# hand-made capture. They are tier-1's own (this file), not the harness's:
# the readers were added to `benchmark/layer_metrics/`, nothing there edited.

import pytest  # noqa: E402

from benchmark.record import Run  # noqa: E402
from benchmark.reduce import hybrid  # noqa: E402
from paddle_tpu.profiler.xplane import StepJoin  # noqa: E402
from test_hybrid_readers import (  # noqa: E402
    CONFIG, V5E, _decode, _read, _step)

# the device plane's clock is 5,000 ns ahead of the tracer's, and the join
# moved the plane by 7 us to make the steps causal
OFFSET_NS, SHIFT_US = 5_000.0, 7.0
MATMUL = "%grouped_matmul.{} = f32[64,3]{{1,0}} custom-call(...)"


class _Capture:
    """As much of `reduce.xplane.Capture` as the two readers look at: one
    device plane's operations `(start_ns, end_ns, name)` on the device's
    clock, and the traced window."""

    path = "synthetic"

    def __init__(self, ops, window):
        self.devices = {"/device:TPU:0": sorted(ops)}
        self._window = window

    def window(self):
        return self._window

    def windowed(self):
        w0, w1 = self._window
        return {p: [(max(s, w0), min(e, w1), n) for s, e, n in ops
                    if e > w0 and s < w1] for p, ops in self.devices.items()}


def _traced(ops, runs, steps, window=(0, 100_000_000)):
    """A `Run` whose capture holds `ops`, whose step spans are `steps` and
    whose join placed step id -> `(device start ns, device end ns)` of
    `runs`, both on the device plane's own clock."""
    run = Run({}, {"name": "cell"}, CONFIG, {}, None, V5E, None, 0.0)
    run.engine_steps = steps
    run.capture = _Capture(ops, window)

    def placed(ns):
        return (ns - OFFSET_NS) / 1e3 + SHIFT_US

    rows = []
    for s in steps:
        at = runs.get(s["id"])
        rows.append({"step": s["id"], "kind": s["kind"],
                     "device_us": None if at is None
                     else (at[1] - at[0]) / 1e3,
                     "device_start_us": None if at is None else placed(at[0]),
                     "device_end_us": None if at is None else placed(at[1])})
    run._step_join = StepJoin(rows, [{
        "why": None, "offset_ns": OFFSET_NS, "shift_us": SHIFT_US,
        "steps": len(runs)}])
    return run


def _calls(start, n, each=1_000_000, gap=250_000, first=0):
    """`n` calls of the kernel from `start` on, `each` ns long."""
    return [(start + i * (each + gap), start + i * (each + gap) + each,
             MATMUL.format(first + i)) for i in range(n)]


def test_grouped_readers_on_whole_steps():
    """CONFIG has two expert layers: four calls a step. Two decode steps the
    trace holds whole, 4 x 1 ms and 4 x 2 ms of kernel; they hit 3 and 4
    experts of 12 elements of bf16. Other operations fill the runs."""
    runs = {1: (10_000_000, 20_000_000), 2: (30_000_000, 45_000_000)}
    ops = (_calls(11_000_000, 4) + _calls(31_000_000, 4, each=2_000_000)
           + [(16_000_000, 19_000_000, "%fusion.7 = ..."),
              (40_000_000, 44_000_000, "%fusion.7 = ...")])
    steps = [_decode(1, 0.010, 0.021), _decode(2, 0.030, 0.046,
                                               moe_experts_hit=4)]
    run = _traced(ops, runs, steps)
    share, calls = _read("kernel.grouped_time_share", run)
    assert calls == 8
    assert share == pytest.approx(100 * 12e-3 / 19e-3)   # busy: 12 + 3 + 4
    value, n = _read("kernel.grouped_decode_roofline", run)
    assert n == 2
    assert hybrid.expert_elements(CONFIG) == 12
    assert value == pytest.approx(100 * (3 + 4) * 12 * 2 / 819e9 / 12e-3)


def test_a_step_that_misses_a_call_is_not_counted():
    """Step 2's run holds three of its four calls (the fourth lies behind
    its end, as a neighbour's would under a clock a millisecond off), step
    3 is a chunk-carrying step, step 4 was not placed and step 5's run is
    cut by the window's end: the roofline is read from step 1 alone, the
    time share from every call in the window."""
    runs = {1: (10_000_000, 20_000_000), 2: (30_000_000, 34_500_000),
            3: (50_000_000, 60_000_000), 5: (90_000_000, 104_000_000)}
    ops = (_calls(11_000_000, 4) + _calls(31_000_000, 4)
           + _calls(51_000_000, 4) + _calls(71_000_000, 4)
           + _calls(96_000_000, 4))
    steps = [_decode(1, 0.010, 0.021), _decode(2, 0.030, 0.036),
             _step(3, "mixed", 0.050, 0.061, fed_tokens=9, emitted_tokens=1,
                   moe_pairs=36, moe_pairs_local=9, moe_experts_hit=4,
                   moe_max_load=5, state_rows=2, kv_tokens=9),
             _decode(4, 0.070, 0.081), _decode(5, 0.090, 0.105)]
    run = _traced(ops, runs, steps)
    value, n = _read("kernel.grouped_decode_roofline", run)
    assert n == 1
    assert value == pytest.approx(100 * 3 * 12 * 2 / 819e9 / 4e-3)
    _, calls = _read("kernel.grouped_time_share", run)
    assert calls == 20
    # no decode step whole: nothing to read, nothing raised
    run = _traced(ops, {2: runs[2]}, steps)
    assert _read("kernel.grouped_decode_roofline", run) is None


def test_a_capture_without_the_operation_reads_as_none():
    """The parent of PR 39: the grouped product is the compiler's
    `ragged-dot`. Also a run with no capture, and spans without the count."""
    ops = [(11_000_000, 12_000_000, "%ragged-dot.3 = f32[704,2688] ..."),
           (13_000_000, 14_000_000, "%fusion.7 = ...")]
    steps = [_decode(1, 0.010, 0.021)]
    run = _traced(ops, {1: (10_000_000, 20_000_000)}, steps)
    for name in ("kernel.grouped_time_share",
                 "kernel.grouped_decode_roofline"):
        assert _read(name, run) is None
        bare = Run({}, {"name": "cell"}, CONFIG, {}, None, V5E, None, 0.0)
        bare.engine_steps = steps
        assert _read(name, bare) is None
    plain = [_step(1, "decode", 0.010, 0.021, fed_tokens=4, emitted_tokens=4,
                   kv_tokens=40)]
    run = _traced(_calls(11_000_000, 4), {1: (10_000_000, 20_000_000)}, plain)
    assert _read("kernel.grouped_decode_roofline", run) is None


# -- PR 41: the reader of the full layers' selection kernel, beside the
# grouped matmul's, on the same hand-made captures.

SELECT = "%topk_select.{} = s32[64,16,128]{{2,1,0}} custom-call(...)"


def test_select_time_share_reads_the_kernel_by_its_name():
    """Two steps, 3 + 2 calls of 1 ms among 9 ms of other operations; the
    kernel's name folded at its first dot, as the grouped matmul's is."""
    runs = {1: (10_000_000, 20_000_000), 2: (30_000_000, 45_000_000)}
    calls = [(s, e, SELECT.format(i)) for i, (s, e, _) in enumerate(
        _calls(11_000_000, 3) + _calls(31_000_000, 2))]
    ops = calls + [(16_000_000, 19_000_000, "%fusion.7 = ..."),
                   (35_000_000, 41_000_000, "%sort.2 = ...")]
    run = _traced(ops, runs, [_decode(1, 0.010, 0.021),
                              _decode(2, 0.030, 0.046)])
    share, n = _read("kernel.select_time_share", run)
    assert n == 5
    assert share == pytest.approx(100 * 5e-3 / 14e-3)


def test_select_time_share_is_none_where_the_selection_sorts():
    """The parent of PR 41: `lax.top_k`'s sort, no operation of the name;
    and a run with no capture."""
    steps = [_decode(1, 0.010, 0.021)]
    ops = [(11_000_000, 12_000_000, "%sort.2 = f32[64,17408] ..."),
           (13_000_000, 14_000_000, "%fusion.7 = ...")]
    run = _traced(ops, {1: (10_000_000, 20_000_000)}, steps)
    assert _read("kernel.select_time_share", run) is None
    bare = Run({}, {"name": "cell"}, CONFIG, {}, None, V5E, None, 0.0)
    bare.engine_steps = steps
    assert _read("kernel.select_time_share", bare) is None


# -- the two readers of the decode group's Mamba state update, on the
# same hand-made captures. CONFIG has two Mamba layers of 2 heads x 3 x 5:
# a row's state is 120 bytes a layer, read and written: 480 bytes a step.

UPDATE = "%ssm_state_update.{} = (f32[2,4,2,3,5]{{4,3,2,1,0}}, ...)"


def _updates(start, n, each=1_000_000, first=0):
    return [(s, e, UPDATE.format(first + i)) for i, (s, e, _) in enumerate(
        _calls(start, n, each=each))]


def test_state_update_readers_on_whole_steps():
    """Two decode steps the trace holds whole, 2 x 1 ms and 2 x 2 ms of
    kernel over 4 and 3 rows, beside the grouped products and a fusion;
    a chunk-carrying step whose calls count for the time share alone."""
    runs = {1: (10_000_000, 20_000_000), 2: (30_000_000, 45_000_000),
            3: (50_000_000, 60_000_000)}
    ops = (_updates(11_000_000, 2) + _updates(31_000_000, 2, each=2_000_000)
           + _updates(51_000_000, 2, first=4)
           + _calls(14_000_000, 4, each=500_000)
           + [(40_000_000, 44_000_000, "%fusion.7 = ...")])
    steps = [_decode(1, 0.010, 0.021), _decode(2, 0.030, 0.046,
                                               state_rows=3),
             _step(3, "mixed", 0.050, 0.061, fed_tokens=9, emitted_tokens=1,
                   moe_pairs=36, moe_pairs_local=9, moe_experts_hit=4,
                   moe_max_load=5, state_rows=2, kv_tokens=9)]
    run = _traced(ops, runs, steps)
    share, calls = _read("kernel.state_update_time_share", run)
    assert calls == 6
    # busy: 2 + 4 + 2 of updates, 4 x 0.5 of products, 4 of fusion
    assert share == pytest.approx(100 * 8e-3 / 14e-3)
    value, n = _read("kernel.state_update_decode_roofline", run)
    assert n == 2
    assert value == pytest.approx(100 * (4 + 3) * 480 / 819e9 / 6e-3)
    # a step whose run holds one of its two calls is not counted
    runs[2] = (30_000_000, 32_500_000)
    value, n = _read("kernel.state_update_decode_roofline",
                     _traced(ops, runs, steps))
    assert n == 1 and value == pytest.approx(100 * 4 * 480 / 819e9 / 2e-3)


def test_state_update_readers_are_none_without_the_kernel():
    """A program whose update is the compiler's fusion and
    `bitcast_dynamic-update-slice_fusion`, no operation of the name; a run
    with no capture; spans without `state_rows`."""
    ops = [(11_000_000, 12_000_000,
            "%bitcast_dynamic-update-slice_fusion.3 = f32[5,32,128,64,128]"),
           (13_000_000, 14_000_000, "%fusion.7 = ...")]
    steps = [_decode(1, 0.010, 0.021)]
    names = ("kernel.state_update_time_share",
             "kernel.state_update_decode_roofline")
    run = _traced(ops, {1: (10_000_000, 20_000_000)}, steps)
    for name in names:
        assert _read(name, run) is None
        bare = Run({}, {"name": "cell"}, CONFIG, {}, None, V5E, None, 0.0)
        bare.engine_steps = steps
        assert _read(name, bare) is None
    plain = [_step(1, "decode", 0.010, 0.021, fed_tokens=4, emitted_tokens=4,
                   kv_tokens=40)]
    run = _traced(_updates(11_000_000, 2), {1: (10_000_000, 20_000_000)},
                  plain)
    assert _read("kernel.state_update_decode_roofline", run) is None
