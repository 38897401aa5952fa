"""paddle_tpu — a TPU-native deep learning framework with the capabilities of
PaddlePaddle (reference: /root/reference, see SURVEY.md).

Architecture: JAX/XLA is the compiler+kernel library; eager mode is a dynamic
tape over jax.vjp; the performance path compiles whole train steps to one XLA
executable (SURVEY.md §7). Public API mirrors `paddle.*`.
"""
from __future__ import annotations


def _place_compile_cache():
    """Turn on JAX's persistent compilation cache (off by default).

    The serving step builds one attention kernel per layer and per width
    program, so a cold start compiles for minutes and a warm one loads.
    Where JAX_COMPILATION_CACHE_DIR is set, jax has already read it and
    nothing is set here. Otherwise the cache sits at a fixed path beside
    the package, `<checkout>/.jax_cache` (git-ignored): every process
    started from one checkout shares it, whatever its home directory."""
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


_place_compile_cache()

# --- core ------------------------------------------------------------------
from .core.dtypes import (  # noqa: F401
    bfloat16,
    bool_,
    complex64,
    complex128,
    float16,
    float32,
    float64,
    get_default_dtype,
    int8,
    int16,
    int32,
    int64,
    set_default_dtype,
    uint8,
)
from .core.tensor import Parameter, Tensor, is_tensor, to_tensor  # noqa: F401
from .core.autograd import enable_grad, no_grad, set_grad_enabled, is_grad_enabled  # noqa: F401
from .core.rng import seed, get_rng_state, set_rng_state  # noqa: F401
from .core import device as _device_mod
from .core.device import (  # noqa: F401
    is_compiled_with_cuda,
    is_compiled_with_rocm,
    is_compiled_with_tpu,
    is_compiled_with_xpu,
)


class _Place:
    """Reference Place parity (CPUPlace/CUDAPlace/...): on a compiler-managed
    runtime placement is a device string; these classes keep API shape."""

    _kind = "cpu"

    def __init__(self, device_id=0):
        self._id = int(device_id)

    def __repr__(self):
        return f"Place({self._kind}:{self._id})"

    def __eq__(self, other):
        if isinstance(other, str):  # Tensor.place returns the string form
            return other == repr(self)
        return isinstance(other, _Place) and (self._kind, self._id) == (
            other._kind, other._id
        )

    def __hash__(self):
        return hash((self._kind, self._id))


class CPUPlace(_Place):
    _kind = "cpu"


class CUDAPlace(_Place):
    _kind = "gpu"


class TPUPlace(_Place):
    _kind = "tpu"


class CUDAPinnedPlace(_Place):
    _kind = "cpu_pinned"

# bind Tensor methods before anything imports them
from .ops import _bind as _bind_mod

_bind_mod.bind()

# --- functional op surface (paddle.* level) --------------------------------
from .ops.creation import (  # noqa: F401
    arange, as_complex, as_real, assign, bernoulli, clone, complex, diag,
    diag_embed, diagflat, empty, empty_like, eye, full, full_like, linspace,
    logspace, meshgrid, multinomial, normal, numel, ones, ones_like, poisson,
    rand, randint, randint_like, randn, randperm, standard_normal, tril, triu,
    uniform, zeros, zeros_like,
)
from .ops.math import *  # noqa: F401,F403
from .ops.linalg import (  # noqa: F401
    bmm, cholesky, cholesky_solve, cond, corrcoef, cov, cross, det, dist, dot,
    eig, eigh, eigvals, eigvalsh, einsum, householder_product,
    inverse, lstsq, lu, matmul, matrix_power, matrix_rank, mm, multi_dot, mv,
    norm, pinv, qr, slogdet, solve, svd, triangular_solve,
)
from .ops.search import histogram  # noqa: F401
from .ops.manipulation import *  # noqa: F401,F403
from .ops.logic import *  # noqa: F401,F403
from .ops.search import (  # noqa: F401
    argmax, argmin, argsort, bincount, bucketize, kthvalue, mode, searchsorted,
    sort, topk,
)
from .ops.common_nn import one_hot  # noqa: F401

# --- subsystems ------------------------------------------------------------
from . import amp  # noqa: F401
from . import audio  # noqa: F401
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distributed  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import framework  # noqa: F401
from . import geometric  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import onnx  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import quantization  # noqa: F401
from . import serving  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import vision  # noqa: F401
from .flags import get_flags, set_flags  # noqa: F401

from .device import get_device, set_device  # noqa: F401
from .framework.io import load, save  # noqa: F401
from .io import batch  # noqa: F401  (legacy reader decorator, paddle.batch)
from .hapi.model import Model  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from .jit.api import to_static  # noqa: F401

# paddle.grad
from .autograd.functional import grad  # noqa: F401

# paddle.flops / summary
from .hapi.summary import flops, summary  # noqa: F401

from .static.program import disable_static, enable_static  # noqa: F401


def in_dynamic_mode():
    from .static.program import in_static_mode

    return not in_static_mode()


__version__ = "0.3.0"

# paddle.linalg / paddle.tensor / paddle.version namespace parity
import sys as _sys  # noqa: E402

from .ops import linalg  # noqa: F401,E402
from . import ops as tensor  # noqa: F401,E402  (paddle.tensor.* functions)

# make `import paddle_tpu.tensor` importable too, not just attribute access
_sys.modules[__name__ + ".tensor"] = tensor
_sys.modules[__name__ + ".linalg"] = linalg


class version:  # noqa: N801 — reference paddle.version module shape
    full_version = __version__
    major, minor, patch = (__version__.split(".") + ["0", "0"])[:3]
    commit = "tpu-native"

    @staticmethod
    def show():
        print(f"paddle-tpu {version.full_version} ({version.commit})")
