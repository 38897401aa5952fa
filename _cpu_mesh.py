"""Force an n-device CPU host platform for multi-chip testing without TPUs.

Shared by __graft_entry__.py and tests/conftest.py. Must run before any jax
backend initialization: the device count is an XLA flag read when the CPU
client is built. JAX_PLATFORMS=cpu is exported as well, so every process
started from here stays off the chip too (SURVEY.md §4 fake-backend
strategy; XLA's host platform is the equivalent of reference
phi/backends/custom/fake_cpu_device.h).
"""
import os
import re


def force_host_cpu_devices(n_devices: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    want = f"--xla_force_host_platform_device_count={n_devices}"
    if "--xla_force_host_platform_device_count" in flags:
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", want, flags)
        os.environ["XLA_FLAGS"] = flags
    else:
        os.environ["XLA_FLAGS"] = (flags + " " + want).strip()

    os.environ["JAX_PLATFORMS"] = "cpu"

    import jax

    # jax read the variable when it was first imported, which may be before
    # this call
    jax.config.update("jax_platforms", "cpu")
    if jax.default_backend() != "cpu" or len(jax.devices()) < n_devices:
        raise RuntimeError(
            f"needed {n_devices} CPU devices but the backend is already up "
            f"({jax.default_backend()}, {len(jax.devices())} devices); "
            "call force_host_cpu_devices before any jax use"
        )
