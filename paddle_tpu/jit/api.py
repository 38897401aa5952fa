"""jit.to_static: trace + compile a Layer/function to one XLA executable.

Reference parity: python/paddle/jit/api.py:222 (@to_static),
dy2static/program_translator.py:299 (StaticFunction, per-input-spec concrete
program cache), partial_program.py:148 (execute captured program).

TPU-native design (SURVEY.md §7 step 4): *tracing*, not AST rewriting — the
function runs once under jax tracing via functional_call; XLA compiles and
caches one executable per (input shapes, dtypes, training flag). Data-
dependent Python control flow must use lax-style ops (paddle's 20 AST
transformers are replaced by the compiler contract).
"""
from __future__ import annotations

import functools
import os
import pickle

import jax
import numpy as np

from ..core import rng
from ..core.functional import functional_call, state_dict_arrays
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..static import InputSpec


class TracedProgram:
    """The 'ConcreteProgram' equivalent: a jitted callable + its state."""

    def __init__(self, fn, layer=None):
        self.layer = layer
        self.fn = fn


class StaticFunction:
    def __init__(self, function, input_spec=None, layer=None):
        self._function = function
        self._input_spec = input_spec
        self._layer = layer
        self._cache = {}
        self._tried_convert = False
        functools.update_wrapper(self, function)

    def _convert_control_flow(self, cause):
        """Tracing hit data-dependent Python control flow: retry once with
        the AST-converted function (dy2static fallback). Raises the
        actionable error when conversion is not possible."""
        from .dy2static import Dy2StaticControlFlowError, convert_control_flow

        if self._tried_convert:
            raise cause
        self._tried_convert = True
        fn = self._function
        target = getattr(fn, "__func__", fn)
        converted = convert_control_flow(target)
        if converted is None:
            raise Dy2StaticControlFlowError(
                f"to_static({getattr(fn, '__name__', fn)}): could not "
                "auto-convert the data-dependent control flow (only "
                "assignment-style if/while bodies are convertible — "
                "return/break/continue inside the branch are not)"
            ) from cause
        if self._layer is not None and hasattr(fn, "__self__"):
            converted = converted.__get__(fn.__self__, type(fn.__self__))
        self._function = converted
        self._cache.clear()

    def __get__(self, instance, owner):
        if instance is None:
            return self
        # ONE bound wrapper per instance: repeated attribute access must
        # return the same object, or per-instance state (the compiled-entry
        # cache, a dy2static-converted body) would be rebuilt/lost on every
        # call through the class descriptor
        cache = self.__dict__.get("_bound_cache")
        if cache is None:
            import weakref

            cache = self.__dict__["_bound_cache"] = weakref.WeakKeyDictionary()
        bound = cache.get(instance)
        if bound is None:
            bound = StaticFunction(
                self._function.__get__(instance, owner), self._input_spec,
                layer=instance,
            )
            cache[instance] = bound
        return bound

    @staticmethod
    def _contains_tensor(v):
        if isinstance(v, (list, tuple, set)):
            return any(StaticFunction._contains_tensor(x) for x in v)
        if isinstance(v, dict):
            return any(StaticFunction._contains_tensor(x) for x in v.values())
        return isinstance(v, (Tensor, np.ndarray, jax.Array))

    def _key(self, args, kwargs=None):
        key = []
        for a in args:
            if isinstance(a, Tensor):
                key.append((tuple(a.shape), str(np.dtype(a.dtype))))
            else:
                # jaxlint: disable=JL002 -- non-Tensor positional args are hashable Python scalars/tuples by contract; Tensor/ndarray args take the (shape, dtype) branch above
                key.append(repr(a))
        # kwargs are baked into the compiled entry at trace time, so they
        # MUST be part of the cache key — a changed kwarg is a new program.
        # Direct Tensor kwargs are keyed by (shape, dtype) and enter the
        # program as runtime arrays; a Tensor buried in a container would be
        # baked as a constant AND repr-truncation would collide the cache
        # key for large arrays, so it is rejected loudly.
        for k in sorted(kwargs or {}):
            v = kwargs[k]
            if isinstance(v, Tensor):
                key.append((k, tuple(v.shape), str(np.dtype(v.dtype))))
            elif isinstance(v, (np.ndarray, jax.Array)):
                # keyed like a Tensor: repr() truncates large arrays, so two
                # different arrays could collide on one cache key (raw
                # jax.Array kwargs would additionally be baked into the
                # traced closure as constants if left on the repr path)
                key.append((k, tuple(v.shape), str(np.dtype(v.dtype))))
            else:
                if self._contains_tensor(v):
                    raise TypeError(
                        f"to_static: kwarg '{k}' holds Tensors inside a "
                        "container; container values are baked into the "
                        "compiled program as constants. Pass each Tensor as "
                        "its own keyword or positional argument."
                    )
                # jaxlint: disable=JL002 -- only plain Python values reach here: Tensor/ndarray kwargs took the (shape, dtype) branch, Tensor-in-container kwargs raised above
                key.append((k, repr(v)))
        layer = self._layer
        if isinstance(layer, Layer):
            key.append(layer.training)
        return tuple(key)

    def __call__(self, *args, **kwargs):
        from ..core import autograd as _autograd

        if _autograd.in_trace_mode():
            # already inside a trace (functional_call) — run the original
            # forward body; the outer jit owns compilation
            return self._function(*args, **kwargs)
        layer = self._layer
        if not isinstance(layer, Layer):
            # plain function: jit over arrays directly
            return self._call_function(*args, **kwargs)
        key = self._key(args, kwargs)
        entry = self._cache.get(key)
        # Tensor/ndarray kwargs are keyed by (shape, dtype) like positional
        # args, so they MUST enter the compiled entry as runtime arrays —
        # baking them into the traced closure would silently replay the
        # first call's values for every later same-shape kwarg
        kw_names = tuple(sorted(
            k for k, v in (kwargs or {}).items()
            if isinstance(v, (Tensor, np.ndarray, jax.Array))
        ))
        if entry is None:
            training = layer.training
            static_kwargs = {
                k: v for k, v in kwargs.items() if k not in kw_names
            }

            @jax.jit
            def compiled(params, buffers, key_, kw_arrays, *arrays):
                kw = dict(static_kwargs)
                kw.update(zip(kw_names, kw_arrays))
                out, new_buf = functional_call(
                    layer, params, buffers,
                    args=tuple(arrays), kwargs=kw,
                    rng_key=key_, training=training,
                )
                return out, new_buf

            entry = compiled
            self._cache[key] = entry
        params, buffers = state_dict_arrays(layer)
        arrays = tuple(a._array if isinstance(a, Tensor) else a for a in args)
        kw_arrays = tuple(
            kwargs[k]._array if isinstance(kwargs[k], Tensor) else kwargs[k]
            for k in kw_names
        )
        from .dy2static import Dy2StaticControlFlowError

        try:
            out, new_buf = entry(params, buffers, rng.next_key(), kw_arrays,
                                 *arrays)
        except Dy2StaticControlFlowError as e:
            self._convert_control_flow(e)  # swaps self._function, clears cache
            return self.__call__(*args, **kwargs)
        from ..core.functional import load_state_arrays, tree_to_tensors

        load_state_arrays(layer, buffers=new_buf)
        return tree_to_tensors(out)

    def _call_function(self, *args, **kwargs):
        key = self._key(args, kwargs)
        entry = self._cache.get(key)
        # Tensor/ndarray kwargs become runtime arrays (see __call__):
        # shape/dtype keyed, value passed per call
        kw_names = tuple(sorted(
            k for k, v in kwargs.items()
            if isinstance(v, (Tensor, np.ndarray, jax.Array))
        ))
        if entry is None:
            from ..core import autograd

            static_kwargs = {
                k: v for k, v in kwargs.items() if k not in kw_names
            }

            @jax.jit
            def compiled(key_, kw_arrays, *arrays):
                tensors = tuple(
                    Tensor._from_op(a) if isinstance(a, jax.Array) else a for a in arrays
                )
                kw = dict(static_kwargs)
                kw.update(zip(kw_names, (Tensor._from_op(a) for a in kw_arrays)))
                with autograd.trace_mode(), rng.key_scope(key_):
                    # read self._function at trace time: the dy2static
                    # fallback may have swapped in a converted body
                    out = self._function(*tensors, **kw)
                return jax.tree_util.tree_map(
                    lambda x: x._array if isinstance(x, Tensor) else x,
                    out,
                    is_leaf=lambda x: isinstance(x, Tensor),
                )

            entry = compiled
            self._cache[key] = entry
        arrays = tuple(a._array if isinstance(a, Tensor) else a for a in args)
        kw_arrays = tuple(
            kwargs[k]._array if isinstance(kwargs[k], Tensor) else kwargs[k]
            for k in kw_names
        )
        from .dy2static import Dy2StaticControlFlowError

        try:
            out = entry(rng.next_key(), kw_arrays, *arrays)
        except Dy2StaticControlFlowError as e:
            self._convert_control_flow(e)
            return self._call_function(*args, **kwargs)
        from ..core.functional import tree_to_tensors

        return tree_to_tensors(out)

    @property
    def code(self):
        import inspect

        return inspect.getsource(self._function)

    def concrete_program_specify_input_spec(self, input_spec=None):
        return None


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    def decorate(fn):
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec, layer=fn)
            return fn
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def _input_avals(input_spec, scope):
    """InputSpec list -> jax ShapeDtypeStructs; None/-1 dims become shared
    symbolic dims (jax.export shape polymorphism), so one artifact serves
    any batch size."""
    avals = []
    for i, s in enumerate(input_spec):
        if isinstance(s, Tensor):
            s = InputSpec(s.shape, s.dtype)
        dims = []
        for j, d in enumerate(s.shape or []):
            if d is None or int(d) < 0:
                # every unknown dim is independent (reference InputSpec
                # semantics) — inputs whose batches must agree still work,
                # they just don't enforce equality at call time
                (dim,) = jax.export.symbolic_shape(f"_d{i}_{j}", scope=scope)
                dims.append(dim)
            else:
                dims.append(int(d))
        avals.append(jax.ShapeDtypeStruct(tuple(dims), np.dtype(s.dtype)))
    return avals


# custom-calls every exported artifact must allow (shared by jit.save and
# static.save_inference_model — extend HERE when a new kernel needs one)
_EXPORT_DISABLED_CHECKS = (
    jax.export.DisabledSafetyCheck.custom_call("tpu_custom_call"),
    jax.export.DisabledSafetyCheck.custom_call("Sharding"),
)


def save(layer, path, input_spec=None, **configs):
    """jit.save: persist an EXECUTABLE program artifact + weights.

    Reference parity: jit/translated_layer.py + static/io.py:442
    (save/load_inference_model) serialize a ProgramDesc; the TPU-native
    artifact is serialized StableHLO from jax.export — `jit.load` in a fresh
    process (no model class available) deserializes and runs it bit-equal.
    Weights ship alongside as arguments (not baked), so the artifact is
    update-able and the program re-usable across checkpoints."""
    if not isinstance(layer, Layer):
        raise TypeError("jit.save expects an nn.Layer")
    if not input_spec:
        raise ValueError(
            "jit.save requires input_spec=[InputSpec(shape, dtype), ...] "
            "(or example Tensors) to trace the program artifact"
        )
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    from ..framework.io import save as fsave

    was_training = layer.training
    layer.eval()
    try:
        params, buffers = state_dict_arrays(layer)

        def fwd(params, buffers, *inputs):
            out, _ = functional_call(
                layer, params, buffers, args=inputs, training=False
            )
            return out

        scope = jax.export.SymbolicScope()
        avals = _input_avals(list(input_spec), scope)
        exp = jax.export.export(
            jax.jit(fwd), disabled_checks=list(_EXPORT_DISABLED_CHECKS)
        )(params, buffers, *avals)
        artifact = {
            "format": "paddle_tpu.stablehlo.v1",
            "stablehlo": exp.serialize(),
            "class_module": type(layer).__module__,
            "class_name": type(layer).__name__,
        }
        with open(path + ".pdmodel", "wb") as f:
            pickle.dump(artifact, f)
        fsave(
            {"params": dict(params), "buffers": dict(buffers)},
            path + ".pdiparams",
        )
        # plain state_dict too (framework save/load interop)
        fsave(layer.state_dict(), path + ".pdparams")
    finally:
        layer.train() if was_training else layer.eval()


class TranslatedLayer:
    """A loaded program artifact, callable like the original layer with no
    access to its Python class (reference jit/translated_layer.py)."""

    def __init__(self, exported, params, buffers):
        self._exported = exported
        self._params = params
        self._buffers = buffers
        self.training = False

    def __call__(self, *inputs):
        arrays = tuple(
            i._array if isinstance(i, Tensor) else jax.numpy.asarray(np.asarray(i))
            for i in inputs
        )
        out = self._exported.call(self._params, self._buffers, *arrays)
        from ..core.functional import tree_to_tensors

        return tree_to_tensors(out)

    forward = __call__

    def eval(self):
        self.training = False
        return self

    def train(self):
        raise RuntimeError("a loaded inference artifact cannot be trained")

    def state_dict(self):
        out = {k: Tensor._from_op(v) for k, v in self._params.items()}
        out.update({k: Tensor._from_op(v) for k, v in self._buffers.items()})
        return out

    def set_state_dict(self, state_dict):
        """Swap weights AND buffers (e.g. BatchNorm running stats) without
        re-exporting (same shapes/dtypes)."""
        for k, v in state_dict.items():
            arr = v._array if isinstance(v, Tensor) else jax.numpy.asarray(v)
            if k in self._params:
                self._params[k] = arr.astype(self._params[k].dtype)
            elif k in self._buffers:
                self._buffers[k] = arr.astype(self._buffers[k].dtype)


def load(path, **configs):
    """jit.load: deserialize and run the saved program — no model class
    needed (the reference's TranslatedLayer contract)."""
    from ..framework.io import load as fload

    with open(path + ".pdmodel", "rb") as f:
        artifact = pickle.load(f)
    if artifact.get("format") != "paddle_tpu.stablehlo.v1":
        raise ValueError(f"unrecognized jit artifact: {artifact.get('format')}")
    exported = jax.export.deserialize(artifact["stablehlo"])
    blob = fload(path + ".pdiparams")
    to_arr = lambda v: v._array if isinstance(v, Tensor) else jax.numpy.asarray(v)
    params = {k: to_arr(v) for k, v in blob["params"].items()}
    buffers = {k: to_arr(v) for k, v in blob["buffers"].items()}
    return TranslatedLayer(exported, params, buffers)
