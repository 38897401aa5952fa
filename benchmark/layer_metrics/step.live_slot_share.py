"""Share of the token slots a chunk-carrying step computed that carried a
token: the `fed_tokens` of the window's `mixed` steps over their slots. A
step's slots are its span's `slots` (the token axis of the program that ran,
`engine.py step_groups`) where it has one and, where it has none (a program
whose step is one rectangle), the span's `width` times the configuration's
`max_batch`. None where the window holds no such step or the spans carry no
`fed_tokens`."""


def read(run):
    steps = [s["args"] for s in run.window_steps()
             if s["kind"] == "mixed" and "fed_tokens" in s["args"]]
    rows = (run.config.get("engine") or {}).get("max_batch")
    slots = [a["slots"] if "slots" in a
             else a["width"] * rows if rows and "width" in a else None
             for a in steps]
    if not steps or None in slots or not sum(slots):
        return None
    return 100.0 * sum(a["fed_tokens"] for a in steps) / sum(slots), len(steps)
