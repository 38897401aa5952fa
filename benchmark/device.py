"""The device as jax reports it. A run is refused, with no result and no
fallback, unless it finds the platform and the number of chips its cell asks
for."""
from __future__ import annotations

import os

from . import spec


def describe():
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def refusal(found, want_platform, want_chips):
    """Why this machine cannot run the cell; None when it can."""
    if found["platform"] != want_platform:
        return (f"needs platform {want_platform!r}, jax found "
                f"{found['platform']!r} ({found['kind']})")
    if found["count"] != want_chips:
        return (f"the cell needs {want_chips} chips, jax found "
                f"{found['count']}")
    return None


def memory_peak_bytes():
    """Peak bytes in use on the fullest chip; 0 where the backend keeps no
    such statistic (the CPU)."""
    import jax

    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def rehearse_on_cpu(chips):
    """The rehearsal's platform: the CPU with `chips` virtual devices and the
    kernels interpreted. Before anything touches jax."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["PADDLE_TPU_FORCE_PALLAS_INTERPRET"] = "1"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={chips}")


def place_compile_cache():
    """Where JAX_COMPILATION_CACHE_DIR is set, jax has read it and nothing is
    set here. Otherwise the cache sits at a fixed path inside the checkout
    (the path is part of the cache's key). Every program is kept, however
    quick its compile, so that a second run of a cell compiles nothing."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(spec.ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
