"""The part of set-up that is served traffic: from the load's start to the
window's start, in which the clients' first requests are admitted and their
prompts read. A gain or loss in prefill shows here, apart from the process's
start-up, the weights and the programs' loading."""


def read(run):
    if run.load_start is None:
        return None
    return run.t0 - run.load_start
