"""The ragged kernel's share of its roofline in pure decode steps: the least
time the chip needs to read every live row's keys and values once
(`reduce/kernels.py ragged_decode_call`; the load generator knows each row's
context from what it sent and received) over the kernel's device time, summed
over the decode steps the trace holds whole."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce.peaks import peaks


def _context_tokens(samples, t):
    """Tokens in the arena of the rows decoding at `t`: prompt plus what each
    has been sent so far, for requests past their first token and not done."""
    total = 0
    for s in samples:
        ts = s.token_times
        done = len(ts) >= s.max_tokens and ts[-1] <= t
        if ts and ts[0] <= t and not done:
            total += len(s.prompt) + sum(x <= t for x in ts)
    return total


def read(run):
    if not breakdown.has_device_trace(run) or not run.engine_steps:
        return None
    ops = next(iter(breakdown.traced_ops(run).values()))
    mine = kernels.matching(ops, run.config["kernels"]["ragged"])
    peak = peaks(run.device["kind"])
    least = took = 0.0
    steps = 0
    for st in run.engine_steps:
        if st["kind"] != "decode" or st["start"] < run.traced[0] \
                or st["end"] > run.traced[1]:
            continue
        calls = kernels.inside(mine, run.to_capture_ns(st["start"]),
                               run.to_capture_ns(st["end"]))
        if not calls:
            continue
        flops, nbytes = kernels.ragged_decode_call(
            _context_tokens(run.samples, st["start"]), run.config["n_embd"],
            2)
        bound, which = kernels.least_seconds(flops, nbytes, peak)
        least += bound * len(calls)
        took += kernels.seconds(calls)
        steps += 1
    if not steps:
        return None
    run.notes.append(f"kernel.ragged_decode_roofline: {which}-bound")
    return 100.0 * least / took, steps
