"""Device time in XLA's own copy operations (`copy`, `copy-start/done`,
`copy_bitcast_fusion`: data moved or relaid, nothing computed) over the time
in which the device ran anything, traced window, averaged over the chips. In
the serving step programs this is the KV arena being copied between the layout
it is kept in and the layout the kernel's call is given."""
from benchmark.reduce import breakdown
from benchmark.reduce import intervals as iv


def read(run):
    if not breakdown.has_device_trace(run):
        return None
    shares = []
    for ops in breakdown.traced_ops(run).values():
        copies = [o for o in ops if iv.category(o[2]).startswith("copy")]
        shares.append(iv.busy(copies) / iv.busy(ops))
    return 100.0 * sum(shares) / len(shares)
