"""Bytes of recurrent state the pool holds beside the K/V arena (the gauge
`state_bytes_per_lane` x the engine's lanes) over the chip's memory. None
where the program has no such gauge or it reads 0 (a model that keeps no
state a lane)."""
from benchmark.reduce.peaks import peaks


def read(run):
    per_lane = (run.gauges or {}).get("state_bytes_per_lane")
    if not per_lane or not run.on_chip:
        return None
    return (100.0 * per_lane * run.config["engine"]["max_batch"]
            / peaks(run.device["kind"])["hbm_bytes"])
