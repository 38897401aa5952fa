"""Requests the scheduler threw out of the arena to make room, whole load."""


def read(run):
    return run.load_counters.get("preemptions", 0)
