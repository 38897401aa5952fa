"""Device time in the routed experts' grouped matmul, by the operation's own
name in the device plane (`grouped_matmul`: the `name=` of its `pallas_call`,
`paddle_tpu/ops/pallas/grouped_matmul.py`), over the time in which the device
ran anything, traced window, averaged over the chips; samples are the
kernel's calls. None where no operation of the capture has the name: a
program whose grouped product is the compiler's `ragged-dot` (a commit before
PR 39), or a model without routed experts."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce import intervals as iv

NAME = "grouped_matmul"


def read(run):
    if not breakdown.has_device_trace(run):
        return None
    shares, calls = [], 0
    for ops in breakdown.traced_ops(run).values():
        mine = [o for o in ops if iv.category(o[2]) == NAME]
        calls += len(mine)
        shares.append(kernels.seconds(mine) / (iv.busy(ops) * 1e-9))
    if not calls:
        return None
    return 100.0 * sum(shares) / len(shares), calls
