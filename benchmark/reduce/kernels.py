"""What each kernel's call must do at the least, from shapes: operations and
bytes of the algorithm, not of the implementation. A roofline share is the
least time the chip could take (the larger of operations over peak FLOP/s and
bytes over peak bytes/s) over the time the kernel took on the device."""
from __future__ import annotations

import re

from . import intervals as iv
from .breakdown import has_device_trace, traced_ops


def matching(ops, pattern):
    """The operations whose name matches the kernel's `pattern` (the
    configuration file gives it, under `kernels`)."""
    rx = re.compile(pattern)
    return [o for o in ops if rx.search(o[2])]


def inside(ops, start, end):
    return [o for o in ops if o[0] >= start and o[1] <= end]


def seconds(ops):
    return sum(e - s for s, e, _ in ops) * 1e-9


def time_share(run, kernel):
    """`(percent, calls)`: device time in the kernel the configuration names
    `kernel` over the time in which the device ran anything, traced window,
    averaged over the chips; None without a device capture."""
    if not has_device_trace(run):
        return None
    shares, calls = [], 0
    for ops in traced_ops(run).values():
        mine = matching(ops, run.config["kernels"][kernel])
        calls += len(mine)
        shares.append(seconds(mine) / (iv.busy(ops) * 1e-9))
    return 100.0 * sum(shares) / len(shares), calls


def least_seconds(flops, nbytes, peak):
    """`(seconds, which)`: the roofline bound and which resource sets it."""
    by_flops = flops / peak["flops_bf16"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes \
        else (by_bytes, "bandwidth")


def ragged_decode_call(context_tokens, hidden, itemsize):
    """One decode call of the ragged paged-attention kernel (one layer, every
    row one query token): each row's keys and values are read once,
    `context_tokens` summed over rows, and multiplied twice."""
    nbytes = 2 * context_tokens * hidden * itemsize
    flops = 2 * 2 * context_tokens * hidden
    return flops, nbytes


def flash_train_layer(rows, seq, hidden, itemsize):
    """The flash kernels of one layer in one training step (forward, dK/dV,
    dQ) on `rows` causal sequences: two products forward and four backward
    over half the square; q, k, v, o and their gradients cross HBM once each
    way. A recomputed forward is not counted."""
    flops = 6 * rows * seq * seq * hidden
    nbytes = (4 + 4 + 3) * rows * seq * hidden * itemsize
    return flops, nbytes
