"""From what a run measured to the one line the driver reads. Every metric is
a reader of its own, found by the name `BENCHMARK.json` gives it:
`benchmark/end_to_end/<name>.py` and `benchmark/layer_metrics/<name>.py`, each
with `read(run)` that returns the value, or `(value, samples)`, or None when
the run holds nothing to read it from."""
from __future__ import annotations

import json

from . import spec
from .reduce import breakdown

COUNTED = "program_counter"   # the one source a CPU rehearsal may report


def read_all(run, group, log):
    out = {}
    for m in spec.cell_metrics(run.bench, run.cell["name"], group):
        got = spec.load_reader(group, m["name"]).read(run)
        value, n = got if isinstance(got, tuple) else (got, None)
        if value is None:
            continue
        samples = "" if n is None else f" ({n} samples)"
        if not run.on_chip and m["source"] != COUNTED:
            log(f"{m['name']}: {value} {m['unit']}{samples} [a CPU timing of "
                "the rehearsal, not a device number]")
            continue
        log(f"{m['name']}: {value} {m['unit']}{samples}")
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result(run, log):
    traced = bool(run.args.trace)
    shown = read_all(run, "per_layer" if traced else "end_to_end", log)
    if not traced:
        # counters cost nothing: on earlier lines for whoever reads the log
        read_all(run, "per_layer", log)
    device = dict(run.device, memory_peak_bytes=run.memory_peak_bytes)
    line = {"correct": bool(run.correct), "attempted": run.attempted,
            "failed": run.failed, "metrics": shown, "device": device}
    if traced and run.on_chip:
        device["busy_s"], device["window_s"] = \
            breakdown.busy_and_window_s(run)
        outside = "no step" if run.config["kind"] == "serve" \
            else "between steps"
        line["breakdown"] = {
            "device_ops": [list(x) for x in breakdown.device_ops(run)],
            "idle_gaps": [list(x) for x in breakdown.idle_gaps(run, outside)],
        }
        log("capture layout: " + json.dumps(run.capture.layout))
        for name, s, n in breakdown.raw_names(run):
            log(f"  device op {s:9.4f}s x{n:<6d} {name[:140]}")
    for note in run.notes:
        log(note)
    # what `correct` compared, each number beside its limit: the last key of
    # the line and (`run.py`) the last lines on standard error
    line["compared"] = run.compared
    return line
