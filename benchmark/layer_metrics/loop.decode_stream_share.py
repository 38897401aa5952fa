"""How near a pure decode step is to the time its reads alone take: the least
time to read every layer's weights once a pass, the head, and every live
row's keys and values in all slabs once (`reduce/looped.py
decode_stream_bytes`, the context from the step span's `kv_tokens`) at the
chip's HBM bandwidth, over the step's length (plan to emit); median over the
window's decode steps. None where the spans carry no `kv_tokens`."""
from benchmark.reduce import looped
from benchmark.reduce.peaks import peaks
from benchmark.reduce.stats import median


def read(run):
    steps = [s for s in run.window_steps()
             if s["kind"] == "decode" and "kv_tokens" in s["args"]]
    if not steps or not run.on_chip:
        return None
    rate = peaks(run.device["kind"])["hbm_bytes_per_s"]
    shares = [
        100.0 * looped.decode_stream_bytes(run.config,
                                           s["args"]["kv_tokens"])
        / rate / (s["end"] - s["start"]) for s in steps]
    return median(shares), len(shares)
