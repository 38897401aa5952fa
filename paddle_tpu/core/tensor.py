"""Tensor: the user-facing eager tensor wrapping a jax.Array.

Reference parity: DenseTensor (/root/reference/paddle/phi/core/dense_tensor.h:38)
plus the eager-tensor Python surface (/root/reference/paddle/fluid/pybind/eager_method.cc).
The jax.Array carries storage/placement/sharding (the AllocatorFacade and
Place roles); this class adds paddle semantics: stop_gradient, .grad,
.backward(), name, and the imperative method surface. Methods are bound from
the functional op library at import time (the role of eager codegen —
eager_gen.py / python_c_gen.py — without codegen: the op set is small because
everything lowers to XLA).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import autograd, device, dtypes

_tensor_counter = [0]


def _new_name():
    _tensor_counter[0] += 1
    return f"generated_tensor_{_tensor_counter[0]}"


class Tensor:
    __slots__ = (
        "_array",
        "stop_gradient",
        "_grad",
        "_node",
        "_out_index",
        "_retain_grads",
        "name",
        "is_leaf",
        "persistable",
        "__weakref__",
    )

    __array_priority__ = 100  # numpy defers binary ops to Tensor

    def __init__(self, data, dtype=None, place=None, stop_gradient=True, name=None):
        if isinstance(data, Tensor):
            arr = data._array
        elif isinstance(data, jax.Array):
            arr = data
        else:
            npdata = np.asarray(data)
            if dtype is None and npdata.dtype == np.float64:
                npdata = npdata.astype(np.float32)  # paddle default dtype
            # jnp.array copies: asarray can alias the caller's numpy buffer
            # (zero-copy CPU path), which breaks jax's immutability contract
            # if the caller mutates it and corrupts the heap if the array is
            # ever donated (see set_value)
            arr = jnp.array(npdata, dtype=dtypes.convert_dtype(dtype))
            arr = jax.device_put(arr, place or device.current_device())
        if dtype is not None:
            want = dtypes.convert_dtype(dtype)
            if np.dtype(arr.dtype) != np.dtype(want):
                arr = arr.astype(want)
        self._array = arr
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node = None
        self._out_index = 0
        self._retain_grads = False
        self.name = name or _new_name()
        self.is_leaf = True
        self.persistable = False

    # ---- construction from op outputs -------------------------------------
    @staticmethod
    def _from_op(array, node=None, out_index=0):
        t = Tensor.__new__(Tensor)
        t._array = array
        t.stop_gradient = node is None
        t._grad = None
        t._node = node
        t._out_index = out_index
        t._retain_grads = False
        t.name = _new_name()
        t.is_leaf = node is None
        t.persistable = False
        return t

    # ---- metadata ---------------------------------------------------------
    @property
    def shape(self):
        return list(self._array.shape)

    @property
    def dtype(self):
        return np.dtype(self._array.dtype).type

    @property
    def ndim(self):
        return self._array.ndim

    dim = ndim

    @property
    def size(self):
        return int(self._array.size)

    @property
    def place(self):
        d = self._array.devices() if hasattr(self._array, "devices") else {self._array.device}
        dev = next(iter(d)) if isinstance(d, (set, frozenset)) else d
        return f"Place({dev.platform}:{dev.id})"

    def numel(self):
        return Tensor(jnp.asarray(self._array.size, jnp.int64 if False else jnp.int32))

    def element_size(self):
        return np.dtype(self._array.dtype).itemsize

    # ---- conversion -------------------------------------------------------
    def numpy(self):
        return np.asarray(self._array)

    def item(self, *args):
        return self._array.item(*args)

    def tolist(self):
        return np.asarray(self._array).tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._array)
        return a.astype(dtype) if dtype is not None else a

    def astype(self, dtype):
        want = dtypes.convert_dtype(dtype)
        out, node = autograd.apply(
            lambda x: x.astype(want), self, name="cast"
        )
        return Tensor._from_op(out, node)

    cast = astype

    def to(self, *args, **kwargs):
        # .to('cpu') / .to(dtype) / .to(device, dtype)
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a.split(":")[0] in ("cpu", "tpu", "gpu"):
                plat = "cpu" if a.startswith("cpu") else None
                devs = (
                    jax.local_devices(backend="cpu")
                    if plat == "cpu"
                    else jax.local_devices()
                )
                t = Tensor._from_op(jax.device_put(t._array, devs[0]), t._node, t._out_index)
                t.stop_gradient = self.stop_gradient
            else:
                t = t.astype(a)
        return t

    def cpu(self):
        return self.to("cpu")

    def tpu(self):
        return self.to("tpu")

    cuda = tpu

    def pin_memory(self):
        return self

    # ---- autograd surface -------------------------------------------------
    @property
    def grad(self):
        if self._grad is None:
            return None
        g = Tensor(self._grad)
        g.stop_gradient = True
        return g

    @grad.setter
    def grad(self, value):
        if value is None:
            self._grad = None
        else:
            # jnp.array (not asarray): same ownership boundary as
            # set_value — a zero-copied numpy buffer stored as grad state
            # would be freed by a donating optimizer step (JL001)
            self._grad = value._array if isinstance(value, Tensor) else jnp.array(value)

    def _accumulate_grad(self, ct):
        ct = ct.astype(self._array.dtype) if ct.dtype != self._array.dtype else ct
        if ct.shape != self._array.shape:
            ct = jnp.reshape(ct, self._array.shape)
        self._grad = ct if self._grad is None else self._grad + ct

    def backward(self, grad_tensor=None, retain_graph=False):
        autograd.backward(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            self._grad = jnp.zeros_like(self._grad)
        else:
            self._grad = None

    def retain_grads(self):
        self._retain_grads = True

    def detach(self):
        t = Tensor._from_op(self._array)
        t.stop_gradient = True
        return t

    def detach_(self):
        self._node = None
        self.stop_gradient = True
        return self

    def clone(self):
        out, node = autograd.apply(lambda x: x + 0, self, name="clone")
        return Tensor._from_op(out, node)

    # ---- mutation (eager only) --------------------------------------------
    def set_value(self, value):
        # jnp.array (not asarray): asarray of an aligned numpy array is
        # ZERO-COPY on the CPU backend, so a donating jitted step (hapi
        # train: donate_argnums over params/opt state) would free a buffer
        # numpy owns — heap corruption after Model.load + train_batch
        arr = value._array if isinstance(value, Tensor) else jnp.array(np.asarray(value))
        if tuple(arr.shape) != tuple(self._array.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._array.shape}"
            )
        self._array = arr.astype(self._array.dtype)
        return self

    def copy_(self, other):
        return self.set_value(other)

    def fill_(self, value):
        self._array = jnp.full_like(self._array, value)
        return self

    def zero_(self):
        self._array = jnp.zeros_like(self._array)
        return self

    # ---- python protocol ---------------------------------------------------
    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._array.shape[0]

    def __repr__(self):
        prefix = f"Tensor(shape={self.shape}, dtype={dtypes.dtype_name(self.dtype)}, stop_gradient={self.stop_gradient},\n       "
        return prefix + np.array2string(np.asarray(self._array), prefix="       ") + ")"

    def __bool__(self):
        import jax as _jax

        if isinstance(self._array, _jax.core.Tracer):
            # a named, actionable error instead of jax's deep trace error —
            # jit.to_static catches it and retries with AST-converted
            # control flow (jit/dy2static.py; reference
            # jit/dy2static/ifelse_transformer.py:56)
            from ..jit.dy2static import _HINT, Dy2StaticControlFlowError

            raise Dy2StaticControlFlowError(_HINT)
        return bool(self._array)

    def __int__(self):
        return int(self._array)

    def __float__(self):
        return float(self._array)

    def __index__(self):
        return int(self._array)

    def __hash__(self):
        return id(self)

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __getitem__(self, idx):
        idx = _convert_index(idx)
        out, node = autograd.apply(lambda x: x[idx], self, name="getitem")
        return Tensor._from_op(out, node)

    def __setitem__(self, idx, value):
        idx = _convert_index(idx)
        varr = value._array if isinstance(value, Tensor) else value
        if self._node is not None or (not self.stop_gradient and autograd.is_grad_enabled()):
            # Differentiable scatter: build a new tensor through the tape.
            if not isinstance(value, Tensor):
                value = Tensor(varr)
            out, node = autograd.apply(
                lambda x, v: x.at[idx].set(v.astype(x.dtype)), self, value, name="setitem"
            )
            self._array = out
            self._node = node
            self._out_index = 0
            self.stop_gradient = node is None
        else:
            self._array = self._array.at[idx].set(
                jnp.asarray(varr).astype(self._array.dtype)
            )

    # dunder arithmetic bound in ops/_bind.py


def _convert_index(idx):
    def conv(i):
        if isinstance(i, Tensor):
            return i._array
        return i

    if isinstance(idx, tuple):
        return tuple(conv(i) for i in idx)
    return conv(idx)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor parity (reference python/paddle/tensor/creation.py)."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)


def is_tensor(x):
    return isinstance(x, Tensor)


def as_array(x, dtype=None):
    """Internal: coerce Tensor | array | python scalar to a jax array."""
    if isinstance(x, Tensor):
        a = x._array
    elif isinstance(x, jax.Array):
        a = x
    else:
        a = jnp.asarray(x)
        if a.dtype == jnp.float64:
            a = a.astype(jnp.float32)
    if dtype is not None:
        a = a.astype(dtypes.convert_dtype(dtype))
    return a


class Parameter(Tensor):
    """Trainable tensor: stop_gradient=False, persistable, optionally carries a
    sharding spec consumed by the distributed layer (GSPMD annotation — the
    TPU-native replacement for per-parameter placement in the reference)."""

    __slots__ = ("trainable", "optimize_attr", "regularizer", "need_clip",
                 "sharding_axes", "process_mesh")

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable, name=name)
        self.trainable = trainable
        self.persistable = True
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.need_clip = True
        self.sharding_axes = None  # tuple of mesh-axis names or None per dim
        self.process_mesh = None  # auto_parallel.ProcessMesh annotation

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()
