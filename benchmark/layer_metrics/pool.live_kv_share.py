"""Blocks of the KV arena that running requests hold, over the blocks that
can be handed out, at the window's end (the program's `block_utilization`
gauge, set every step; cached blocks nobody holds count as free). The arena
is reserved whole, so `device.peak_hbm_share` says how much memory the
deployment takes and this says how much of it the traffic uses."""


def read(run):
    held = (run.gauges or {}).get("block_utilization")
    return None if held is None else 100.0 * held
