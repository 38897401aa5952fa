"""Device management.

Reference parity: paddle.device.set_device/get_device
(/root/reference/python/paddle/device/__init__.py:355,382) parse strings like
"gpu:0" and flip a global Place. Here, devices are JAX devices; 'tpu' is the
first-class accelerator. The current device is a process-global used by tensor
creation ops (jax.device_put target); compute follows its inputs, which is the
XLA model rather than a DeviceContextPool.
"""
from __future__ import annotations

import functools

import jax

_current_device = None  # lazily resolved jax.Device


@functools.lru_cache(maxsize=None)
def _platform_devices(platform: str):
    """Process-local devices only: under multi-controller JAX, jax.devices()
    lists every process's devices, but tensors can only be created on
    addressable ones."""
    try:
        return tuple(jax.local_devices(backend=platform))
    except RuntimeError:
        return ()


def _default_device():
    return jax.local_devices()[0]


def set_device(device: str):
    """Accepts 'tpu', 'tpu:0', 'cpu', 'gpu:0' (alias of accelerator), 'custom_dev'."""
    global _current_device
    if device is None:
        _current_device = None
        return None
    name, _, idx = device.partition(":")
    idx = int(idx) if idx else 0
    name = name.lower()
    if name == "tpu":
        # the chip or an error: "tpu" never quietly means the CPU
        devs = _platform_devices("tpu")
    elif name in ("gpu", "xpu", "npu", "mlu", "ipu", "custom_dev"):
        # the reference's other accelerator names are aliases of the
        # default backend, whatever it is
        devs = jax.local_devices()
    elif name == "cpu":
        devs = _platform_devices("cpu")
    else:
        raise ValueError(f"Unknown device string: {device!r}")
    if not devs:
        raise RuntimeError(f"No devices for platform {name!r}")
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"{device!r}: platform {name!r} has {len(devs)} device(s), "
            f"index {idx} does not exist")
    _current_device = devs[idx]
    return _current_device


def current_device():
    return _current_device if _current_device is not None else _default_device()


def get_device() -> str:
    d = current_device()
    return f"{d.platform}:{d.id}"


def device_count(platform=None) -> int:
    if platform is None:
        return len(jax.devices())
    return len(_platform_devices(platform))


def is_compiled_with_tpu() -> bool:
    return True


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_mkldnn() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    return True


def synchronize():
    """Block until all dispatched work on the current device finishes."""
    (jax.device_put(0, current_device()) + 0).block_until_ready()
