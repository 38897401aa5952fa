"""dy2static fallback (VERDICT r4 item 6 / Missing #2).

Reference: /root/reference/python/paddle/jit/dy2static/ifelse_transformer.py:56
and loop_transformer.py. The trace-based to_static now (1) raises a NAMED,
actionable error when Python control flow branches on a traced tensor, and
(2) auto-converts assignment-style if/while bodies to
static.nn.cond/while_loop and retries.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import jit, nn
from paddle_tpu.jit.dy2static import Dy2StaticControlFlowError


def test_named_actionable_error_for_unconvertible():
    """return-inside-branch is not convertible: the user gets ONE clear
    error naming static.nn.cond, not a jax tracer stack."""

    @jit.to_static
    def f(x):
        if x.sum() > 0:  # data-dependent, returns from the branch
            return x * 2
        return x - 1

    with pytest.raises(Dy2StaticControlFlowError) as ei:
        f(paddle.to_tensor(np.ones(4, np.float32)))
    assert "static.nn.cond" in str(ei.value) or "could not auto-convert" in str(
        ei.value
    )


def test_eager_bool_still_works():
    t = paddle.to_tensor(np.array(1.0, np.float32))
    assert bool(t > 0)


def test_converted_if_end_to_end():
    """Assignment-style data-dependent `if` converts and matches eager."""

    def f(x):
        if x.sum() > 0:
            y = x * 2.0
        else:
            y = x - 1.0
        return y + 1.0

    sf = jit.to_static(f)
    pos = paddle.to_tensor(np.ones(4, np.float32))
    neg = paddle.to_tensor(-np.ones(4, np.float32))
    np.testing.assert_allclose(np.asarray(sf(pos)._array), np.ones(4) * 3)
    np.testing.assert_allclose(np.asarray(sf(neg)._array), -np.ones(4))


def test_converted_if_reads_prior_value():
    """Branch bodies that READ the pre-branch value of a reassigned var."""

    def f(x):
        y = x + 1.0
        if x.mean() > 0:
            y = y * 10.0
        return y

    sf = jit.to_static(f)
    pos = paddle.to_tensor(np.ones(3, np.float32))
    neg = paddle.to_tensor(-np.ones(3, np.float32))
    np.testing.assert_allclose(np.asarray(sf(pos)._array), np.ones(3) * 20)
    np.testing.assert_allclose(np.asarray(sf(neg)._array), np.zeros(3))


def test_converted_while_end_to_end():
    """Data-dependent `while` converts to ONE lax.while_loop."""

    def f(x):
        s = x
        while s.sum() < 100.0:
            s = s * 2.0
        return s

    sf = jit.to_static(f)
    out = np.asarray(sf(paddle.to_tensor(np.ones(4, np.float32)))._array)
    # 4 -> 8 -> ... -> 128 >= 100
    np.testing.assert_allclose(out, np.ones(4) * 32)


def test_converted_while_with_body_local_temporary():
    """A traced-cond `while` whose body uses a temporary assigned before
    read must still convert: the temp's _UNDEF init is unobservable, so it
    can't be rejected by the XLA carry check (dy2static review fix)."""

    def f(x):
        s = x
        while s.sum() < 100.0:
            doubled = s * 2.0  # body-local: assigned before read
            s = doubled
        return s

    sf = jit.to_static(f)
    out = np.asarray(sf(paddle.to_tensor(np.ones(4, np.float32)))._array)
    np.testing.assert_allclose(out, np.ones(4) * 32)


def test_converted_while_temporary_read_after_loop_stays_loud():
    """A body 'temporary' that is read AFTER the loop is not a temporary:
    a zero-trip loop would leak the zero-seeded carry where plain Python
    raises NameError, so the traced path must keep the loud conversion
    error instead of silently returning zeros."""

    def f(x):
        s = x
        while s.sum() < 1.0:  # False on entry for ones(4): zero trips
            d = s * 2.0
            s = d
        return d  # noqa: F821 — undefined when the loop never ran

    sf = jit.to_static(f)
    with pytest.raises(TypeError, match="read before assignment|undefined"):
        sf(paddle.to_tensor(np.ones(4, np.float32)))


def test_converted_while_still_rejects_read_before_assignment():
    """A loop variable genuinely read before assignment keeps the
    actionable error on the traced path."""

    def f(x):
        s = x
        while s.sum() < 100.0:
            s = s + acc  # noqa: F821 — read before ANY assignment
            acc = s * 0.0
        return s

    sf = jit.to_static(f)
    with pytest.raises(TypeError, match="read before assignment|undefined"):
        sf(paddle.to_tensor(np.ones(4, np.float32)))


def test_concrete_condition_keeps_python_semantics():
    """The converted dispatch runs plain Python when the condition is
    concrete (outside tracing)."""

    def f(x, flag):
        if flag:
            y = x + 1.0
        else:
            y = x - 1.0
        return y

    sf = jit.to_static(f)
    # flag is a plain bool (non-tensor arg -> part of the jit cache key)
    a = np.asarray(sf(paddle.to_tensor(np.zeros(2, np.float32)), True)._array)
    b = np.asarray(sf(paddle.to_tensor(np.zeros(2, np.float32)), False)._array)
    np.testing.assert_allclose(a, np.ones(2))
    np.testing.assert_allclose(b, -np.ones(2))


class GatedNet(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 4)

    def forward(self, x):
        h = self.fc(x)
        if h.mean() > 0:  # data-dependent gate on a Layer forward
            out = h * 2.0
        else:
            out = h * 0.5
        return out


def test_layer_forward_with_data_dependent_if():
    paddle.seed(0)
    net = GatedNet()
    sfnet = jit.to_static(net)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    out = sfnet(x)
    # eager reference (same params, plain python branch)
    h = net.fc(x)
    expected = (h * 2.0 if float(h.mean()._array) > 0 else h * 0.5)._array
    np.testing.assert_allclose(
        np.asarray(out._array), np.asarray(expected), rtol=1e-6
    )


if __name__ == "__main__":
    import sys

    sys.exit(pytest.main([__file__, "-x", "-q"]))


class DecoratedGatedNet(nn.Layer):
    """forward decorated @jit.to_static at class level (the reference's
    idiom) — the descriptor must hand back ONE bound wrapper per instance
    so the dy2static conversion survives re-access."""

    def __init__(self):
        super().__init__()
        self.fc = nn.Linear(4, 4)

    @jit.to_static
    def forward(self, x):
        h = self.fc(x)
        if h.mean() > 0:
            out = h * 2.0
        else:
            out = h * 0.5
        return out


def test_decorated_layer_method_converts():
    paddle.seed(0)
    net = DecoratedGatedNet()
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    out = net(x)
    h = net.fc(x)
    expected = (h * 2.0 if float(h.mean()._array) > 0 else h * 0.5)._array
    np.testing.assert_allclose(
        np.asarray(out._array), np.asarray(expected), rtol=1e-6
    )


def test_to_static_kwargs_in_cache_key():
    """Changed kwargs must recompile, not replay the first call's baked
    kwargs (review finding: the cache key ignored kwargs)."""

    def f(x, scale=1.0):
        return x * scale

    sf = jit.to_static(f)
    x = paddle.to_tensor(np.ones(3, np.float32))
    a = np.asarray(sf(x, scale=2.0)._array)
    b = np.asarray(sf(x, scale=5.0)._array)
    np.testing.assert_allclose(a, 2.0 * np.ones(3))
    np.testing.assert_allclose(b, 5.0 * np.ones(3))


def test_to_static_tensor_kwargs_are_runtime_values():
    """Two same-shape Tensor kwargs hit the same compiled entry but must
    use their OWN values (advisor finding, fixed in PR 1: the kwarg's concrete array was
    baked into the traced closure, silently replaying the first mask)."""

    def f(x, mask=None):
        return x * mask

    sf = jit.to_static(f)
    x = paddle.to_tensor(np.ones(4, np.float32))
    m1 = paddle.to_tensor(np.array([1, 0, 1, 0], np.float32))
    m2 = paddle.to_tensor(np.array([0, 1, 0, 1], np.float32))  # same shape
    np.testing.assert_allclose(np.asarray(sf(x, mask=m1)._array), m1.numpy())
    np.testing.assert_allclose(np.asarray(sf(x, mask=m2)._array), m2.numpy())
    assert len(sf._cache) == 1  # same program, different runtime kwarg


def test_to_static_layer_tensor_kwargs_are_runtime_values():
    """Same regression through the Layer path (functional_call kwargs)."""

    class Masked(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x, mask=None):
            return self.fc(x) * mask

    paddle.seed(0)
    net = Masked()
    sfnet = jit.to_static(net)
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    m1 = paddle.to_tensor(np.ones((2, 4), np.float32))
    m2 = paddle.to_tensor(np.zeros((2, 4), np.float32))
    out1 = np.asarray(sfnet(x, mask=m1)._array)
    out2 = np.asarray(sfnet(x, mask=m2)._array)
    ref = np.asarray(net.fc(x)._array)
    np.testing.assert_allclose(out1, ref, rtol=1e-6)
    np.testing.assert_allclose(out2, np.zeros((2, 4)), rtol=1e-6)


def test_to_static_ndarray_kwargs_are_runtime_values():
    """Raw np.ndarray kwargs take the Tensor-kwarg path: keyed by
    (shape, dtype), value passed at runtime — repr() truncates large arrays,
    so keying by repr collided different arrays onto one baked constant."""

    def f(x, mask=None):
        return x * mask

    sf = jit.to_static(f)
    x = paddle.to_tensor(np.ones(2000, np.float32))
    m1 = np.ones(2000, np.float32)
    m2 = np.ones(2000, np.float32)
    m2[1000] = 5.0  # identical truncated repr, different value
    np.testing.assert_allclose(np.asarray(sf(x, mask=m1)._array), m1)
    np.testing.assert_allclose(np.asarray(sf(x, mask=m2)._array), m2)
    assert len(sf._cache) == 1


def test_to_static_jax_array_kwargs_are_runtime_values():
    """Raw jax.Array kwargs (flagged in the serving-frontend issue): they
    fell through to the repr() cache key and were baked into the traced
    closure as constants, silently replaying the first call's values for
    every later same-shape kwarg. Now keyed by (shape, dtype) and passed
    as runtime arrays, through both the plain-function and Layer paths."""
    import jax.numpy as jnp

    def f(x, mask=None):
        return x * mask

    sf = jit.to_static(f)
    x = paddle.to_tensor(np.ones(4, np.float32))
    m1 = jnp.asarray(np.array([1, 0, 1, 0], np.float32))
    m2 = jnp.asarray(np.array([0, 1, 0, 1], np.float32))  # same shape/dtype
    np.testing.assert_allclose(np.asarray(sf(x, mask=m1)._array),
                               np.asarray(m1))
    np.testing.assert_allclose(np.asarray(sf(x, mask=m2)._array),
                               np.asarray(m2))
    assert len(sf._cache) == 1  # same program, different runtime kwarg

    class Masked(nn.Layer):
        def __init__(self):
            super().__init__()
            self.fc = nn.Linear(4, 4)

        def forward(self, x, mask=None):
            return self.fc(x) * mask

    paddle.seed(0)
    net = Masked()
    sfnet = jit.to_static(net)
    xb = paddle.to_tensor(np.ones((2, 4), np.float32))
    ones = jnp.asarray(np.ones((2, 4), np.float32))
    zeros = jnp.asarray(np.zeros((2, 4), np.float32))
    ref = np.asarray(net.fc(xb)._array)
    np.testing.assert_allclose(np.asarray(sfnet(xb, mask=ones)._array), ref,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sfnet(xb, mask=zeros)._array),
                               np.zeros((2, 4)), rtol=1e-6)


def test_to_static_rejects_tensor_in_container_kwarg():
    """A Tensor inside a container kwarg would be baked as a constant (and
    numpy's truncated repr would collide cache keys for large arrays) —
    rejected loudly instead."""

    def f(x, masks=None):
        return x * masks[0]

    sf = jit.to_static(f)
    x = paddle.to_tensor(np.ones(4, np.float32))
    m = paddle.to_tensor(np.ones(4, np.float32))
    with pytest.raises(TypeError, match="container"):
        sf(x, masks=[m])


def test_converted_function_with_concrete_inner_while():
    """A traced `if` triggers whole-function conversion; an unrelated
    concrete while with a body-local temporary must still run (review
    finding: the _UNDEF guard fired before the Python fallback)."""

    def f(x):
        if x.sum() > 0:
            y = x * 2.0
        else:
            y = x - 1.0
        n = 3
        while n > 0:
            t = y + 1.0
            y = t
            n = n - 1
        return y

    sf = jit.to_static(f)
    out = np.asarray(sf(paddle.to_tensor(np.ones(3, np.float32)))._array)
    np.testing.assert_allclose(out, np.ones(3) * 5.0)  # 2 + 3
