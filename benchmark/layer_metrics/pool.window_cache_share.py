"""Bytes the window layers' cache holds for the sequences in flight (the
gauge `window_cache_bytes_per_lane` x `num_running`) over what it would hold
with every position kept (`tokens_in_flight` x a token's window rows,
`reduce/latent.py window_bytes_per_token`), at the window's end. None where
the program has no such gauge (a model without window layers) or nothing is
in flight."""
from benchmark.reduce import latent


def read(run):
    gauges = run.gauges or {}
    per_lane = gauges.get("window_cache_bytes_per_lane")
    tokens = gauges.get("tokens_in_flight")
    if not per_lane or not tokens or not gauges.get("num_running"):
        return None
    return (100.0 * per_lane * gauges["num_running"]
            / (tokens * latent.window_bytes_per_token(run.config)))
