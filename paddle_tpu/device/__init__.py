"""paddle.device parity (reference python/paddle/device/__init__.py:60-382)."""
from ..core.device import (  # noqa: F401
    current_device,
    device_count,
    get_device,
    is_compiled_with_cinn,
    is_compiled_with_cuda,
    is_compiled_with_mkldnn,
    is_compiled_with_npu,
    is_compiled_with_rocm,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
    set_device,
    synchronize,
)
from . import tpu  # noqa: F401

cuda = tpu  # paddle.device.cuda.* API parity aliases onto the accelerator


def get_available_device():
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_available_custom_device():
    return []


def get_all_device_type():
    return get_available_device()


def get_all_custom_device_type():
    return []


def is_compiled_with_custom_device(name):
    return False
