"""Time in collective operations while no other operation runs on that
device, over the traced window, averaged over the chips."""
from benchmark.reduce import breakdown
from benchmark.reduce import intervals as iv
from benchmark.reduce.xplane import is_collective


def read(run):
    if not breakdown.has_device_trace(run):
        return None
    w0, w1 = run.capture.window()
    shares = []
    for ops in breakdown.traced_ops(run).values():
        coll = [o for o in ops if is_collective(o[2])]
        rest = [o for o in ops if not is_collective(o[2])]
        shares.append(iv.exposed(coll, rest) / (w1 - w0))
    return 100.0 * sum(shares) / len(shares)
