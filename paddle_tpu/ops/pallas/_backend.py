"""Shared backend gate for the Pallas kernels (flash, ragged paged attn, the
paged arena's append and the routed experts' grouped matmul).

One policy, two env knobs, checked in this order:

- ``PADDLE_TPU_DISABLE_PALLAS``          — always use the XLA fallbacks.
- ``PADDLE_TPU_FORCE_PALLAS_INTERPRET``  — run the Pallas kernels through the
  interpreter on ANY backend (CI's way to exercise the kernel code paths on
  CPU runners, including inside jitted serving steps).

On a ``tpu`` backend the kernels are on and compiled; elsewhere they are off
unless the interpret knob opts in, and the XLA path is simply the default.
On a ``tpu`` backend the XLA path is never taken quietly: every dispatch
that takes it is counted in `XLA_FALLBACKS` and logged with its reason
(`note_xla_fallback`), so a run can assert that it stayed on the kernels.
"""
from __future__ import annotations

import collections
import logging
import os

_log = logging.getLogger(__name__)

# "kernel: reason" -> dispatches (counted while tracing) that took the XLA
# path on a tpu backend. Process-wide, like the warnings registry: the
# dispatch functions are free functions called from inside traces.
XLA_FALLBACKS = collections.Counter()


def use_pallas():
    """Whether attention dispatch should take the Pallas kernel path. A
    backend that fails to initialise raises here; it is not read as "no
    kernels"."""
    if os.environ.get("PADDLE_TPU_DISABLE_PALLAS"):
        return False
    if os.environ.get("PADDLE_TPU_FORCE_PALLAS_INTERPRET"):
        return True
    import jax

    return jax.default_backend() == "tpu"


def interpret_mode():
    """Whether Pallas kernels must run interpreted (non-TPU backends)."""
    return bool(os.environ.get("PADDLE_TPU_FORCE_PALLAS_INTERPRET"))


def note_xla_fallback(kernel, reason):
    """Record that `kernel`'s dispatch is taking its XLA path. On a tpu
    backend that is an event to count and log; anywhere else it is the
    only path there is and nothing is recorded."""
    import jax

    if jax.default_backend() != "tpu":
        return
    XLA_FALLBACKS[f"{kernel}: {reason}"] += 1
    _log.warning("%s runs on the XLA path on a tpu backend: %s",
                 kernel, reason)
