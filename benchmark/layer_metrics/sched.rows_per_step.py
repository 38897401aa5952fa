"""Mean live rows a step (decode rows and prompt chunks) in the window, from
the step spans' `rows`."""


def read(run):
    rows = [s["args"]["rows"] for s in run.window_steps()]
    return (sum(rows) / len(rows), len(rows)) if rows else None
