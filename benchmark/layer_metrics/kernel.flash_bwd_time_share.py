"""Device time in the two backward flash-attention kernels, by their own
names in the device plane (`flash_bwd_dkv`, `flash_bwd_dq`: the `name=` of
their `pallas_call`s), over the time in which the device ran anything,
traced window, averaged over the chips; samples are the kernel calls. None
where no operation of the capture has either name (a program that does not
name its kernels)."""
from benchmark.reduce import breakdown, kernels
from benchmark.reduce import intervals as iv

NAMES = ("flash_bwd_dkv", "flash_bwd_dq")


def read(run):
    if not breakdown.has_device_trace(run):
        return None
    shares, calls = [], 0
    for ops in breakdown.traced_ops(run).values():
        mine = [o for o in ops if iv.category(o[2]) in NAMES]
        calls += len(mine)
        shares.append(kernels.seconds(mine) / (iv.busy(ops) * 1e-9))
    if not calls:
        return None
    return 100.0 * sum(shares) / len(shares), calls
