"""One run of one cell:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout, on a machine that holds the chips the cell asks
for. The last line of standard output is one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` in a
traced run) and, last, `compared`: the cell's end-to-end metrics with
`--trace 0`, its per-layer metrics with `--trace 1`, and each number that
decided `correct` beside its limit (also the last lines on standard error).
Everything else goes to earlier lines. No chip, no
result: the exit code is then not 0 and there is no such line.

`--platform cpu` is the rehearsal: the same code at the tiny sizes the data
files give under `rehearsal`, kernels interpreted. Its last line keeps only
what a CPU can say (counts); its timings are printed on earlier lines, marked
as CPU timings, and never under a metric's name.
"""
from __future__ import annotations

import time

PROCESS_START = time.monotonic()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the program and the `benchmark` package are found from the checkout's root;
# this file's own directory must not shadow anything
sys.path[0] = str(ROOT)

NO_RESULT = 3


def log(msg):
    print(f"[bench +{time.monotonic() - PROCESS_START:6.1f}s] {msg}",
          flush=True)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    p.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                   help="cpu: the rehearsal, at tiny sizes")
    p.add_argument("--control", type=int, choices=(0, 1), default=0,
                   help="1: the control, which has to come out as not "
                   "correct: after the window the reference is also put in "
                   "the program's place in the precisions the configuration "
                   "lists under `control` (correct.py). By hand and in "
                   "tests; no run of a check")
    return p.parse_args(argv)


def main(argv=None):
    args = parse(sys.argv[1:] if argv is None else argv)
    from benchmark import device, report, spec
    from benchmark.compile_clock import CompileClock
    from benchmark.record import Run

    bench, cell, config, mix = spec.find_cell(args.workload)
    if args.platform == "cpu":
        device.rehearse_on_cpu(cell["chips"])
        config = spec.merged(config, config["rehearsal"])
        mix = spec.merged(mix, mix["rehearsal"])

    found = device.describe()
    why = device.refusal(found, args.platform, cell["chips"])
    if why is not None:
        print(f"benchmark: {why}; not run", file=sys.stderr)
        return NO_RESULT
    # the system under test; where it is missing the run ends here, before a
    # line is printed
    import paddle_tpu  # noqa: F401

    log(f"cell {cell['name']} on {json.dumps(found)}")
    log(f"compile cache at {device.place_compile_cache()}")
    run = Run(bench, cell, config, mix, args, found, CompileClock(),
              PROCESS_START)
    runner = spec.resolve(f"benchmark.runners.{config['kind']}.run")
    runner(run, log)
    line = report.result(run, log)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
