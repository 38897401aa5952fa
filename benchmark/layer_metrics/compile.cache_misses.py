"""Programs the persistent cache did not hold during set-up. 0 from the
second run of a cell in a checkout on."""


def read(run):
    return run.compile_setup["misses"]
