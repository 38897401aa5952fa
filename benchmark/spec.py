"""Where the harness finds things: every cell, configuration, traffic mix,
generator, runner and per-layer reader is looked up by the name written in
`BENCHMARK.json` or in a data file it names. Adding one is new files plus one
entry; no file that is there needs an edit."""
from __future__ import annotations

import copy
import importlib
import importlib.util
import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark():
    return load_json(ROOT / "BENCHMARK.json")


def by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"no {what} named {name!r}; there are "
                   f"{[e['name'] for e in entries]}")


def find_cell(name):
    """(cell, configuration, traffic mix) for the workload `name`. The
    configuration is the file its `configs` entry names; the mix is
    `benchmark/traffic/<traffic>.json`."""
    bench = load_benchmark()
    cell = by_name(bench["workloads"], name, "workload")
    entry = by_name(bench["configs"], cell["config"], "configuration")
    config = load_json(ROOT / entry["file"])
    mix = load_json(HERE / "traffic" / f"{cell['traffic']}.json")
    return bench, cell, config, mix


def merged(base, overrides):
    """`base` with `overrides` laid over it, dict by dict. The rehearsal
    sizes of a configuration or a mix are written this way."""
    out = copy.deepcopy(base)
    for k, v in (overrides or {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def resolve(dotted):
    """`pkg.module.attr` -> the attribute."""
    module, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def model_kwargs(config):
    """The builder's keyword sizes: each is read from the published key the
    configuration file names for it, so the file states every size once."""
    m = config["model"]
    kw = {k: config[src] for k, src in m["kwargs_from"].items()}
    kw.update(m.get("kwargs", {}))
    return kw


def cell_metrics(bench, cell_name, group):
    """The entries of `end_to_end` or `per_layer` that this cell reports: an
    entry without `workloads` belongs to every cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]


READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_reader(group, name):
    """The reader of one metric: `benchmark/end_to_end/<name>.py` or
    `benchmark/layer_metrics/<name>.py`. Names hold dots, so the file is
    loaded by path and not imported by name."""
    path = HERE / READER_DIRS[group] / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_reader_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
