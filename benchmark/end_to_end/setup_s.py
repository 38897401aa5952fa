"""Process start to the first measured request or step: imports, weights,
compiling or loading programs, warm-up, and the ramp."""


def read(run):
    return run.t0 - run.process_start
