"""Loss of the last step that ended inside the window."""


def read(run):
    return run.steps[-1]["loss"] if run.steps else None
