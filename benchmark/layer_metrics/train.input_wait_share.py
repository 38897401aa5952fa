"""Share of the window in which the loop waited for the input iterator."""


def read(run):
    if not run.steps:
        return None
    span = run.steps[-1]["end"] - run.steps[0]["start"] \
        + run.steps[0]["input_wait"]
    return 100.0 * sum(s["input_wait"] for s in run.steps) / span
