"""Held experts that got at least one token in a pure decode step, over the
experts held (the gauges `experts_held` x the expert layers of the
pattern): the step spans' `moe_experts_hit`, mean over the window's decode
steps. It is the share of the held experts' weights a decode step has to
read. None where the spans carry no `moe_experts_hit`."""
from benchmark.reduce import hybrid


def read(run):
    steps = [s for s in run.window_steps()
             if s["kind"] == "decode" and "moe_experts_hit" in s["args"]]
    held = (run.gauges or {}).get("experts_held")
    if not steps or not held:
        return None
    slots = hybrid.expert_slots(run.config, held)
    return (100.0 * sum(s["args"]["moe_experts_hit"] for s in steps)
            / (len(steps) * slots), len(steps))
