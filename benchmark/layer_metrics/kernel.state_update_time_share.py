"""Device time in the decode group's Mamba state update, by the operation's
own name in the device plane (`ssm_state_update`: the `name=` of its
`pallas_call`, `paddle_tpu/ops/pallas/ssm_state_update.py`), over the time
in which the device ran anything, traced window, averaged over the chips;
samples are the kernel's calls. None where no operation of the capture has
the name: a program whose state update is the compiler's fusion and
update-slice, or a model without Mamba layers."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce import intervals as iv

NAME = "ssm_state_update"


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
