"""Tokens of the steps that ended inside the window, over the time from the
first such step's start to the last one's end (each step ends when its loss
has been read back), summed over the chips."""


def read(run):
    return (run.train_tok_s(), len(run.steps)) if run.steps else None
