"""How near a pure decode step of the hybrid decoder is to the time its reads
and writes alone take: the least time to read the dense weights and the head,
each held expert the step's tokens picked (the span's `moe_experts_hit`), the
rows' recurrent state twice (read and written; `state_rows`) and their keys
and values once (`kv_tokens`) at the chip's HBM bandwidth
(`reduce/hybrid.py decode_stream_bytes`), over the step's length (plan to
emit); median over the window's decode steps. None where the spans carry no
`moe_experts_hit`."""
from benchmark.reduce import hybrid
from benchmark.reduce.peaks import peaks
from benchmark.reduce.stats import median


def read(run):
    steps = [s for s in run.window_steps()
             if s["kind"] == "decode" and "moe_experts_hit" in s["args"]]
    if not steps or not run.on_chip:
        return None
    rate = peaks(run.device["kind"])["hbm_bytes_per_s"]
    shares = [
        100.0 * hybrid.decode_stream_bytes(
            run.config, s["args"]["state_rows"],
            s["args"]["moe_experts_hit"], s["args"]["kv_tokens"])
        / rate / (s["end"] - s["start"]) for s in steps]
    return median(shares), len(shares)
