"""Tokens that a window closing on a token count (`window.tokens` in the
mix) still lacked when `--seconds`, its cap, closed it instead: 0 in a run
that did the whole of its work, and then `out_tok_s` is that work over the
time it took. Above 0 the program was too slow for the cap and `out_tok_s`
is the rate over what was read. None for a window of time."""


def read(run):
    return run.tokens_short
