"""Mean number of host arrays a step hands to the device (the step span's
`h2d_arrays`, counted from the tuple `engine.py _run_step` builds), steps of
the window. None where the spans carry no `h2d_arrays`."""


def read(run):
    n = [s["args"]["h2d_arrays"] for s in run.window_steps()
         if "h2d_arrays" in s["args"]]
    return (sum(n) / len(n), len(n)) if n else None
