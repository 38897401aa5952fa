"""Random state management.

Reference parity: paddle.seed + per-generator state
(/root/reference/python/paddle/framework/random.py) and the tensor-parallel
RNGStatesTracker (/root/reference/python/paddle/distributed/fleet/layers/mpu/random.py:35).

Design (TPU-first): a process-global PRNG key + monotone counter. Eager ops
fold the counter into the key (cheap, traceable). Under `jax.jit` tracing the
framework swaps in an explicit traced key via `key_scope`, so compiled train
steps are deterministic functions of (params, batch, seed) — the functional
JAX discipline — while user code keeps the stateful paddle API.
"""
from __future__ import annotations

import contextlib
import threading

import jax
import numpy as np


class _KeyState(threading.local):
    def __init__(self):
        self._key = None
        self.counter = 0
        self.override = None  # (key, counter_box) inside key_scope

    @property
    def key(self):
        # built on first use, not at import: making a key initialises the
        # JAX backend, and a process that has done that holds the chip —
        # launcher / lint / bench parents import this package and must
        # leave the chip to the child they start
        if self._key is None:
            self._key = jax.random.PRNGKey(0)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_state = _KeyState()

# host-side numpy Generator: PROCESS-global (not thread-local) because the
# DataLoader's prefetch thread is where samplers actually iterate — a
# thread-local would silently hand that thread a fresh OS-entropy stream and
# paddle.seed would never reach the shuffle order
_host_lock = threading.Lock()
_host_gen = None


def seed(s: int):
    """paddle.seed parity: seeds the device RNG stream AND paddle's own
    host-side generator (DataLoader shuffle, RandomSampler) — the
    reference's global seed reaches its CPU generators the same way
    (framework/random.py). numpy's GLOBAL state is deliberately left alone:
    a library call must not clobber user np.random streams."""
    global _host_gen

    _state.key = jax.random.PRNGKey(int(s))
    _state.counter = 0
    with _host_lock:
        _host_gen = np.random.default_rng(int(s) % (2**31))
    return s


def host_generator():
    """paddle's host-side numpy Generator (shuffles, samplers). Seeded by
    paddle.seed; lazily random otherwise. Process-global so the DataLoader
    prefetch thread draws from the seeded stream."""
    global _host_gen

    with _host_lock:
        if _host_gen is None:
            _host_gen = np.random.default_rng()
        return _host_gen


def get_rng_state():
    """Full RNG snapshot: device (key, counter) + the host generator's
    bit-generator state, so a round-trip also restores sampler/shuffle
    streams (the reference's get_rng_state covers its CPU generators too)."""
    host = host_generator().bit_generator.state
    return (_state.key, _state.counter, host)


def set_rng_state(st):
    global _host_gen

    if len(st) == 2:  # pre-r4 snapshots: device state only
        _state.key, _state.counter = st
        return
    _state.key, _state.counter, host = st
    with _host_lock:
        if _host_gen is None:
            _host_gen = np.random.default_rng()
        _host_gen.bit_generator.state = host


def next_key():
    """Return a fresh PRNG key; works both eagerly and under tracing."""
    if _state.override is not None:
        base, box = _state.override
        box[0] += 1
        return jax.random.fold_in(base, box[0])
    _state.counter += 1
    return jax.random.fold_in(_state.key, _state.counter)


def capture_key():
    """Key for an RNG op that may be captured into a static Program.

    Under static-graph capture (paddle.static.program_guard /
    enable_static), the key is registered as an *RNG slot* of the program:
    a placeholder input that Executor.run (and the hapi StaticGraphAdapter)
    substitutes with a fresh per-step key, so dropout masks vary per step
    instead of being frozen at their capture-time value (reference: random
    ops re-run per Executor.run). The placeholder itself does not advance
    the global stream — capture is a dry run, not a training step.
    Everywhere else this is exactly next_key()."""
    from . import autograd

    cap = getattr(autograd._tls, "capture", None)
    if (
        cap is not None
        and _state.override is None
        and not autograd._tls.trace_mode
        and autograd._tls.apply_depth == 0
    ):
        slot = len(cap._rng_aids) + 1
        # distinct placeholder per slot, high offset so it cannot collide
        # with the 1-based per-step stream
        key = jax.random.fold_in(_state.key, 0x7FFF0000 + slot)
        cap._register_rng_key(key)
        return key
    return next_key()


@contextlib.contextmanager
def key_scope(key):
    """Route next_key() through `key` (possibly a tracer) for the duration.

    Used by functional_call / compiled train steps so randomness is an
    explicit input of the XLA program.
    """
    prev = _state.override
    _state.override = (key, [0])
    try:
        yield
    finally:
        _state.override = prev


class RNGStatesTracker:
    """Named RNG states: tensor-parallel dropout needs same-seed inside an mp
    group for some ops and different-seed for others (reference
    mpu/random.py:35). Tracks independent key states by name."""

    def __init__(self):
        self.states_ = {}

    def add(self, name, seed_):
        if name in self.states_:
            raise ValueError(f"rng state {name} already exists")
        self.states_[name] = [jax.random.PRNGKey(int(seed_)), 0]

    def reset(self):
        self.states_.clear()

    @contextlib.contextmanager
    def rng_state(self, name="model_parallel_rng"):
        if name not in self.states_:
            raise ValueError(f"rng state {name} not added")
        st = self.states_[name]
        prev = _state.override
        box = [st[1]]
        _state.override = (st[0], box)
        try:
            yield
        finally:
            st[1] = box[0]
            _state.override = prev


_GLOBAL_TRACKER = RNGStatesTracker()


def get_rng_state_tracker():
    return _GLOBAL_TRACKER


def model_parallel_random_seed(seed_: int, mp_rank: int = 0):
    """Reference mpu/random.py:89 — global seed shared, mp seed offset by rank."""
    global_seed = 100 + seed_
    local_seed = seed_ + 1024 + mp_rank
    _GLOBAL_TRACKER.reset()
    seed(global_seed)
    _GLOBAL_TRACKER.add("model_parallel_rng", local_seed)


def normal_np(shape, mean=0.0, std=1.0, dtype=np.float32, rs=None):
    rs = rs or np.random
    return rs.normal(mean, std, size=shape).astype(dtype)
