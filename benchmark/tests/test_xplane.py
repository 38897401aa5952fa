"""The reading of a capture and its reduction, on small captures recorded on
the chip by `benchmark/tools/record_small_trace.py` (TPU v5 lite; my chip
runs, PR 22) and looked at by hand once: the numbers asserted here were read
off the listed events, not computed by the code under test.

One chip: the capture holds three steps of the tiny program, 24 operations
each. The device's clock reads about 0.7 ms behind the host's in this capture
(step 0's annotation begins 0.007 ms into the window and its first operation
is stamped 0.576 ms before it), so two steps lie inside the window's
annotation and the first just before it: the two clocks agree to about a
millisecond, no better. Each step is a product `fusion` of 46.6 us, three
`convolution_tanh_fusion` of 45.1 us, a `convert_reduce_fusion` of 46.6 us and
the ragged kernel's call (`%step.1 = ... custom_call_target="tpu_custom_call"`)
of 41.18 us, back to back: 270 us a step, nothing else but idle."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark.reduce import intervals as iv, kernels  # noqa: E402
from benchmark.reduce.xplane import Capture, is_collective  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
KERNEL = 'custom_call_target="tpu_custom_call"'


def test_one_chip_capture_busy_idle_and_kernel_time():
    c = Capture(str(DATA / "small_trace_1chip.xplane.pb"))
    assert list(c.devices) == ["/device:TPU:0"]
    assert c.layout["/device:TPU:0"]["XLA Ops"] == 72
    names = [a[2] for a in c.annotations]
    assert names == ["bench.window", "bench.step 0", "bench.step 1",
                     "bench.step 2"]
    w0, w1 = c.window()
    assert abs((w1 - w0) * 1e-6 - 11.863) < 1e-3            # ms
    ops = iv.clip(c.devices["/device:TPU:0"], w0, w1)
    assert len(ops) == 48
    assert abs(iv.busy(ops) * 1e-3 - 540.4) < 0.5           # us
    long_gaps = [(s, e) for s, e in iv.gaps(ops, w0, w1) if e - s > 1e5]
    assert [round((e - s) * 1e-6, 2) for s, e in long_gaps] == \
        [3.50, 3.20, 4.62]                                  # ms
    mine = kernels.matching(ops, KERNEL)
    assert len(mine) == 2 and all(n.startswith("%step.1 = ")
                                  for _, _, n in mine)
    assert abs(kernels.seconds(mine) * 1e6 - 82.35) < 0.05  # us
    by = iv.seconds_by(ops)
    assert list(by)[:4] == ["convolution_tanh_fusion", "fusion",
                            "convert_reduce_fusion", "step"]
    assert abs(by["convolution_tanh_fusion"] * 1e6 - 270.8) < 0.5
    assert not any(is_collective(n) for _, _, n in ops)


class _Run:
    """As much of `record.Run` as the capture's readers look at."""

    def __init__(self, capture):
        self.capture = capture
        self.config = {"kernels": {"ragged": KERNEL}, "kind": "train"}
        self.engine_steps = None


def test_readers_over_the_recorded_capture():
    from benchmark import spec
    from benchmark.reduce import breakdown

    run = _Run(Capture(str(DATA / "small_trace_1chip.xplane.pb")))
    read = lambda name: spec.load_reader("per_layer", name).read(run)  # noqa: E731
    assert abs(read("device.idle_share") - 100 * (1 - 0.5404 / 11.863)) < 0.01
    share, calls = read("kernel.ragged_time_share")
    assert calls == 2 and abs(share - 100 * 82.35 / 540.4) < 0.02
    assert read("step.copy_time_share") < 0.2       # a few hundred ns
    assert read("train.collective_exposed_share") == 0.0
    busy, window = breakdown.busy_and_window_s(run)
    assert abs(busy * 1e6 - 540.4) < 0.5 and abs(window * 1e3 - 11.863) < 1e-3
    assert [n for n, _ in breakdown.device_ops(run)][:2] == \
        ["convolution_tanh_fusion", "fusion"]
    # steps 1 and 2 lie in the window: idle inside a step's annotation goes
    # under `step`, the rest (the sleeps between steps) under the other name
    gaps = dict(breakdown.idle_gaps(run, "between steps"))
    assert set(gaps) == {"step", "between steps"}
    assert abs(sum(gaps.values()) * 1e3 - (11.863 - 0.5404)) < 1e-3
    assert gaps["between steps"] > gaps["step"]
    # no capture, nothing to read
    assert spec.load_reader("per_layer", "device.idle_share").read(
        _Run(None)) is None
