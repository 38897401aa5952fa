"""Published peaks of one chip, keyed by what `device.device_kind` contains.
Source: Google Cloud documentation, "TPU v5e" system architecture page: 197
TFLOP/s in bf16, 16 GB of HBM2e at 819 GB/s per chip. (The same figures as the
program's `profiler/flops.py` tables, copied so that no later PR can move the
yardstick.) A device that is not listed is an error, never a default."""
from __future__ import annotations

PEAKS = {
    "v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
    "v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
            "hbm_bytes": 16e9},
}


def hbm_share(run):
    """Peak bytes in use on the fullest chip over the chip's memory, in
    percent; None where the backend keeps no such statistic."""
    if not run.memory_peak_bytes:
        return None
    return 100.0 * run.memory_peak_bytes \
        / peaks(run.device["kind"])["hbm_bytes"]


def peaks(device_kind):
    for key, val in sorted(PEAKS.items(), key=lambda kv: -len(kv[0])):
        if key in device_kind.lower():
            return val
    raise ValueError(f"no peaks listed for device_kind {device_kind!r} "
                     f"(listed: {sorted(PEAKS)}); add it with its source")
