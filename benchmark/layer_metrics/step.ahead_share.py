"""Share of the window's steps that were launched while the step before them
was unread (the step span's `ahead`: the engine's loop keeps one step in
flight ahead of the host, `serving/engine.py LLMEngine.step`). None where the
spans carry no `ahead`, as before the loop kept a step in flight."""


def read(run):
    ahead = [s["args"]["ahead"] for s in run.window_steps()
             if "ahead" in s["args"]]
    return (100.0 * sum(ahead) / len(ahead), len(ahead)) if ahead else None
