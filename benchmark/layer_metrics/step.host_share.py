"""Share of step time in the phases in which the device has nothing from this
step to do (plan, build and emit), steps of the window."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    host = sum(e - s for st in steps for name, (s, e) in st["phases"].items()
               if name in ("plan", "build", "emit"))
    return 100.0 * host / sum(st["end"] - st["start"] for st in steps), \
        len(steps)
