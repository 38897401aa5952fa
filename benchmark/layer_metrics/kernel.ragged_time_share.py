"""Device time in the ragged paged-attention kernel over the time in which
the device ran anything, traced window, averaged over the chips."""
from benchmark.reduce import kernels


def read(run):
    return kernels.time_share(run, "ragged")
