"""`step.live_slot_share` on hand-made step spans: a program whose span says
how many token slots its step computed (`slots`), one that does not (its
step is the rectangle `width x max_batch` of the configuration), and spans
with nothing to read. The numbers asserted were worked out by hand."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.record import Run  # noqa: E402

CONFIG = {"engine": {"max_batch": 16}}


def _read(steps, config=CONFIG, t0=10.0, t1=20.0):
    run = Run({}, {"name": "cell"}, config, {}, None, {"platform": "tpu"},
              None, 0.0)
    run.t0, run.t1, run.engine_steps = t0, t1, steps
    return spec.load_reader("per_layer", "step.live_slot_share").read(run)


def _step(i, kind, start, end, **args):
    return {"id": i, "kind": kind, "start": start, "end": end,
            "phases": {}, "args": {"step": i, "kind": kind, **args}}


def test_spans_that_carry_slots_are_read_by_them():
    steps = [
        # before the window, and a decode step: neither is read
        _step(1, "mixed", 0.0, 9.0, width=128, fed_tokens=2048, slots=2048),
        _step(2, "decode", 10.0, 11.0, width=1, fed_tokens=16, slots=16),
        _step(3, "mixed", 11.0, 12.0, width=128, fed_tokens=115, slots=272),
        _step(4, "mixed", 12.0, 13.0, width=128, fed_tokens=143, slots=272),
    ]
    assert _read(steps) == (100.0 * 258 / 544, 2)


def test_spans_without_slots_are_the_configurations_rectangle():
    steps = [
        _step(2, "decode", 10.0, 11.0, width=1, fed_tokens=16),
        _step(3, "mixed", 11.0, 12.0, width=128, fed_tokens=115),
        _step(4, "mixed", 12.0, 13.0, width=128, fed_tokens=143),
    ]
    assert _read(steps) == (100.0 * 258 / 4096, 2)
    # one span of each kind in a window: each by what it carries
    steps[1]["args"]["slots"] = 272
    assert _read(steps) == (100.0 * 258 / (272 + 2048), 2)


def test_nothing_to_read_is_none_and_never_raises():
    mixed = _step(3, "mixed", 11.0, 12.0, width=128, fed_tokens=115)
    bare = _step(3, "mixed", 11.0, 12.0, rows=16)
    decode = _step(2, "decode", 10.0, 11.0, width=1, fed_tokens=16)
    assert _read([decode]) is None
    assert _read([bare]) is None
    assert _read([]) is None
    assert _read(None) is None
    # no `slots` and no `max_batch` to make the rectangle from
    assert _read([mixed], config={}) is None
    assert _read([mixed], config={"engine": {}}) is None
