"""A closed-loop mix as the engine's steps, counted and not timed: which
steps are decode steps and which carry a prompt chunk, who completes when,
and where a window that closes on a token count ends. No engine and no
device: the scheduler's rules as `PERF.md` section 4 states them.

    python3 benchmark/tools/loop_steps.py --traffic rollout --seed 1 \
        [--lengths-seed 7] [--tokens 1600] [--decode-s 0.155 --chunk-s 2.2]

    every step     admit what waits into free lanes (all `clients` at the
                   first); a lane with more than one prompt token pending
                   reads its next `chunk` of them, and emits its first token
                   with the last; every other lane emits one token
    a completion   the client's next request reaches the engine after the
                   next step was planned (the engine plans before the
                   clients have read the burst), so it is admitted by the
                   step after next
    the ramp       ends with the step after which `first_tokens` requests
                   have had a token; the step then in flight is the window's
                   first burst

The seconds it prints are the two step lengths it is given times the counts:
an estimate for sizing a window, never a measurement.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)

from benchmark import spec  # noqa: E402


def steps(requests, clients, chunk=128):
    """Yield one record a step without end: `{"kind": "decode" | "mixed",
    "tokens": emitted, "firsts": requests that got their first token,
    "done": requests that completed}`. `requests` yields the generator's
    dicts; only the lengths are read."""
    lanes = []                  # [pending prompt tokens, output tokens left]
    arrived, on_the_way = clients, 0
    while True:
        while arrived and len(lanes) < clients:
            r = next(requests)
            lanes.append([len(r["prompt"]), r["max_tokens"]])
            arrived -= 1
        wide = any(lane[0] > 1 for lane in lanes)
        tokens = firsts = 0
        for lane in lanes:
            if lane[0] > 0:
                lane[0] -= min(lane[0], chunk)
                if lane[0]:
                    continue
                firsts += 1
            tokens += 1
            lane[1] -= 1
        done = sum(lane == [0, 0] for lane in lanes)
        lanes = [lane for lane in lanes if lane != [0, 0]]
        # what completed in the step before has arrived; what completed in
        # this one misses the next plan
        arrived, on_the_way = arrived + on_the_way, done
        yield {"kind": "mixed" if wide else "decode", "tokens": tokens,
               "firsts": firsts, "done": done}


def window(requests, clients, first_tokens, tokens, chunk=128):
    """`(ramp, window)`: the steps of the ramp, and the steps of a window
    that opens after it and closes with the burst that holds its
    `tokens`-th token."""
    run = steps(requests, clients, chunk)
    ramp, firsts = [], 0
    while firsts < first_tokens:
        ramp.append(next(run))
        firsts += ramp[-1]["firsts"]
    held, read = [], 0
    while read < tokens:
        held.append(next(run))
        read += held[-1]["tokens"]
    return ramp, held


def count(held):
    out = {"decode": 0, "mixed": 0, "tokens": 0, "done": 0, "firsts": 0}
    for s in held:
        out[s["kind"]] += 1
        for k in ("tokens", "done", "firsts"):
            out[k] += s[k]
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--lengths-seed", type=int, default=None)
    p.add_argument("--tokens", type=int, default=None)
    p.add_argument("--chunk", type=int, default=128)
    p.add_argument("--decode-s", type=float, default=None)
    p.add_argument("--chunk-s", type=float, default=None)
    args = p.parse_args()
    mix = spec.load_json(spec.HERE / "traffic" / f"{args.traffic}.json")
    if args.lengths_seed is not None:
        mix["lengths_seed"] = args.lengths_seed
    tokens = args.tokens or mix["window"]["tokens"]
    requests = spec.resolve(mix["generator"])(mix, args.seed, 50257)
    ramp, held = window(requests, int(mix["loop"]["clients"]),
                        int(mix["ramp"]["first_tokens"]), tokens,
                        chunk=args.chunk)
    out = {"ramp": count(ramp), "window": count(held),
           "order": "".join(s["kind"][0] for s in held)}
    if args.decode_s and args.chunk_s:
        for part in ("ramp", "window"):
            c = out[part]
            c["estimated_s"] = (c["decode"] * args.decode_s
                                + c["mixed"] * args.chunk_s)
        w = out["window"]
        w["estimated_tok_s"] = w["tokens"] / w["estimated_s"]
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
