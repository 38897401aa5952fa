"""User custom C++ operators with autograd.

Reference parity: RegisterOperatorWithMetaInfo
(/root/reference/paddle/fluid/framework/custom_operator.cc:746) + the
cpp_extension `load` flow — a user ships C++ forward/backward kernels and
gets a differentiable paddle op.

TPU-native design: user C++ cannot run ON the TPU (device kernels are
Pallas's job — see ops/pallas/), so a custom C++ op is a HOST op: the C
function executes through jax.pure_callback (XLA host callback), wrapped in
jax.custom_vjp so the user's backward kernel supplies the gradient. The op
then enters the normal funnel (autograd.apply) — tape, static capture, jit
all work; each call pays a device<->host round trip, which is the honest
cost of host-side C++ anywhere.

C ABI contract (same-shape float32 op):

    extern "C" void <name>_forward(const float* x, float* y, int64_t n);
    extern "C" void <name>_backward(const float* x, const float* grad_y,
                                    float* grad_x, int64_t n);  // optional

Missing backward => the op is forward-only (stop_gradient outputs).
"""
from __future__ import annotations

import ctypes
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from ..core import autograd
from ..core.tensor import Tensor
from ..ops._helpers import T
from . import cpp_extension

REGISTRY = {}

# ops that already warned about being traced into a compiled program —
# one warning per op name, not one per trace (a bucketed predictor can
# legitimately trace the same program several times)
_TRACE_WARNED = set()


def _in_abstract_trace(x):
    """True when `x` is being traced into a COMPILED program (jit /
    static-graph replay) — a DynamicJaxprTracer, possibly wrapped in
    autodiff tracers (jit-of-grad). Eager autodiff also passes tracers
    through (jax.vjp linearization), but their `.primal` chain bottoms
    out at a concrete array, not a jaxpr tracer — no warning there."""
    from jax.interpreters import partial_eval as pe

    dyn = pe.DynamicJaxprTracer
    for _ in range(8):  # unwrap nested autodiff/batching tracers
        if isinstance(x, dyn):
            return True
        # JVPTracer carries `.primal`, vmap's BatchTracer carries `.val`
        nxt = getattr(x, "primal", None)
        if nxt is None:
            nxt = getattr(x, "val", None)
        if nxt is None:
            return False
        x = nxt
    return False


def _warn_if_traced(name, x):
    """Warn (once per op) when a host-callback custom op is being TRACED
    into a jit/static program: the callback does not fuse — every
    execution of the compiled program pays a device->host round trip
    (device flush, host ctypes call on a copied buffer, result upload)
    per call site, serialized against the surrounding program. That cost
    is invisible at trace time, which is exactly when users assume jit
    made everything fast."""
    if name in _TRACE_WARNED or not _in_abstract_trace(x):
        return
    _TRACE_WARNED.add(name)
    warnings.warn(
        f"custom op '{name}' is a HOST-callback op being traced into a "
        "jit/static program: every execution pays a device->host round "
        "trip (sync + host copy + C call) at this call site — it will "
        "not fuse with surrounding device ops. Keep it outside hot "
        "compiled loops, or port the kernel to Pallas (ops/pallas/) to "
        "run it on-device. This is the JL003 host-callback-in-jit class: "
        "the static analyzer flags the same pattern at build time (see "
        "README 'Static analysis' or `python -m paddle_tpu.analysis "
        "--list-rules`).",
        stacklevel=4,
    )


def _c_fn(lib, sym, n_bufs):
    try:
        fn = getattr(lib, sym)
    except AttributeError:
        return None
    fn.argtypes = [ctypes.POINTER(ctypes.c_float)] * n_bufs + [ctypes.c_int64]
    fn.restype = None
    return fn


def load_custom_op(name, sources, extra_cxx_flags=None, verbose=False):
    """Compile + register a differentiable custom op; returns the callable
    (also available via paddle_tpu.utils.custom_op.REGISTRY[name])."""
    lib = cpp_extension.load(
        f"customop_{name}", sources, extra_cxx_flags=extra_cxx_flags,
        verbose=verbose,
    )
    fwd_c = _c_fn(lib, f"{name}_forward", 2)
    if fwd_c is None:
        raise ValueError(
            f"custom op {name}: symbol {name}_forward not found in the "
            "built library (C ABI: extern \"C\" void "
            f"{name}_forward(const float* x, float* y, int64_t n))"
        )
    bwd_c = _c_fn(lib, f"{name}_backward", 3)

    def host_fwd(x):
        x = np.ascontiguousarray(x, np.float32)
        y = np.empty_like(x)
        fwd_c(
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            y.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(x.size),
        )
        return y

    def host_bwd(x, gy):
        x = np.ascontiguousarray(x, np.float32)
        gy = np.ascontiguousarray(gy, np.float32)
        gx = np.empty_like(x)
        bwd_c(
            x.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            gy.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            gx.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            ctypes.c_int64(x.size),
        )
        return gx

    @jax.custom_vjp
    def f(a):
        _warn_if_traced(name, a)
        out = jax.pure_callback(
            host_fwd, jax.ShapeDtypeStruct(a.shape, jnp.float32),
            a.astype(jnp.float32),
        )
        return out.astype(a.dtype)

    def f_fwd(a):
        return f(a), a

    def f_bwd(a, g):
        if bwd_c is None:
            raise NotImplementedError(
                f"custom op {name} has no {name}_backward kernel — the op is "
                "forward-only"
            )
        gx = jax.pure_callback(
            host_bwd, jax.ShapeDtypeStruct(a.shape, jnp.float32),
            a.astype(jnp.float32), g.astype(jnp.float32),
        )
        return (gx.astype(g.dtype),)

    f.defvjp(f_fwd, f_bwd)
    f.__name__ = name

    def op_fn(x):
        xt = T(x)
        if bwd_c is None:
            # forward-only: never record a tape node
            with autograd.no_grad():
                out, node = autograd.apply(f, xt, name=name)
        else:
            out, node = autograd.apply(f, xt, name=name)
        return Tensor._from_op(out, node)

    op_fn.__name__ = name
    REGISTRY[name] = op_fn
    return op_fn
