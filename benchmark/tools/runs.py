"""Several runs of cells, one after another, each a process of its own (this
parent never touches jax, so each child gets the chip), with what each
printed kept under `chiprun_out/<tag>/` and one line a run on standard
output; then, for each cell and metric, the median and the spread (quartile
distance over median, `statistics.quantiles(n=4)`) of every set:

    python3 benchmark/tools/runs.py --tag sets --seconds 48 \
        --cell c1p3b-serve.rollout --seeds 11,12,13,14,15,16 --sets 2 \
        [--trace 0] [--root <checkout>] [--grep "window closes|turn-round"]
        [--control]

`--sets 2` runs the seeds twice, as the bound's rule asks. `--cell` and
`--seeds` may be given several times, pairwise. `--root` runs another
checkout's `benchmark/run.py` (a copy unpacked from `git archive`).
"""
from __future__ import annotations

import argparse
import json
import re
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def spread(values):
    if len(values) < 2 or not statistics.median(values):
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one(root, out, name, cell, seed, seconds, trace, grep, extra=()):
    cmd = [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace),
           *extra]
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
    took = time.monotonic() - t0
    (out / f"{name}.out").write_text(proc.stdout)
    (out / f"{name}.err").write_text(proc.stderr)
    lines = proc.stdout.strip().splitlines()
    try:
        line = json.loads(lines[-1])
    except (IndexError, ValueError):
        line = None
    row = {"cell": cell, "seed": seed, "trace": trace, "rc": proc.returncode,
           "took_s": round(took, 1)}
    if line is not None:
        row.update(correct=line["correct"], attempted=line["attempted"],
                   failed=line["failed"], compared=line.get("compared"),
                   peak=line["device"]["memory_peak_bytes"],
                   busy_s=line["device"].get("busy_s"),
                   window_s=line["device"].get("window_s"),
                   metrics={k: v["value"] for k, v in
                            line["metrics"].items()})
    else:
        row["stderr_tail"] = proc.stderr[-1500:]
    if grep:
        rx = re.compile(grep)
        row["log"] = [ln for ln in lines[:-1] if rx.search(ln)]
    return row


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--tag", required=True)
    p.add_argument("--cell", action="append", required=True)
    p.add_argument("--seeds", action="append", required=True,
                   type=lambda s: [int(x) for x in s.split(",")])
    p.add_argument("--sets", type=int, default=1)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--root", default=str(ROOT))
    p.add_argument("--grep", default=None)
    p.add_argument("--control", action="store_true",
                   help="the control: every run has to come out not correct")
    args = p.parse_args()
    out = ROOT / "chiprun_out" / args.tag
    out.mkdir(parents=True, exist_ok=True)
    rows = []
    for cell, seeds in zip(args.cell, args.seeds):
        for k in range(args.sets):
            for seed in seeds:
                row = one(args.root, out,
                          f"{cell}.seed{seed}.trace{args.trace}.set{k}",
                          cell, seed, args.seconds, args.trace, args.grep,
                          ("--control", "1") if args.control else ())
                row["set"] = k
                rows.append(row)
                print(json.dumps(row), flush=True)
    summary = {}
    for cell in args.cell:
        for k in range(args.sets):
            mine = [r for r in rows if r["cell"] == cell and r["set"] == k
                    and "metrics" in r]
            # a side's first run compiles: its set-up is recorded apart
            for name in sorted({m for r in mine for m in r["metrics"]}):
                vals = [r["metrics"][name] for r in mine
                        if name in r["metrics"]]
                summary[f"{cell} set{k} {name}"] = {
                    "n": len(vals), "median": statistics.median(vals),
                    "min": min(vals), "max": max(vals),
                    "spread": spread(vals)}
    (out / "rows.jsonl").write_text(
        "".join(json.dumps(r) + "\n" for r in rows))
    (out / "summary.json").write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary, indent=1))
    return 0 if all(r["rc"] == 0 and r.get("correct") != args.control
                    for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
