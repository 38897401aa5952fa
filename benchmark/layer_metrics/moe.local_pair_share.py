"""Of the (token, expert) picks the window's live tokens made, the share that
named an expert this chip holds and was computed here: the step spans'
`moe_pairs_local` over `moe_pairs`, summed over the window's steps. With
even routing it is experts held over the router's width (a quarter where a
chip holds 128 of 512). None where the spans carry neither."""


def read(run):
    steps = [s for s in run.window_steps() if "moe_pairs" in s["args"]]
    pairs = sum(s["args"]["moe_pairs"] for s in steps)
    if not pairs:
        return None
    return (100.0 * sum(s["args"]["moe_pairs_local"] for s in steps) / pairs,
            len(steps))
