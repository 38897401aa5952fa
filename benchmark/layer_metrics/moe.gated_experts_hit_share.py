"""Held experts that got at least one token of a step's DECODE GROUP (the
rows one token wide, whether or not a chunk rides beside them), over the
experts held (the gauge `experts_held` x the expert layers of `layer_types`):
the step spans' `moe_experts_hit_decode`, mean over the window's steps whose
decode group hit any. It is the share of the held experts' weights the
decoding rows alone make a step read (a chunk beside them hits every one): a
reading of the router's picks, as `moe.experts_hit_share` is for the hybrid
(that reader counts the hybrid's pattern, over pure decode steps). None where
the spans carry no `moe_experts_hit_decode` or the file has no
`layer_types`."""
from benchmark.reduce import latent


def read(run):
    steps = [s for s in run.window_steps()
             if s["args"].get("moe_experts_hit_decode")]
    held = (run.gauges or {}).get("experts_held")
    if not steps or not held or "layer_types" not in run.config:
        return None
    slots = latent.expert_slots(run.config, held)
    return (100.0 * sum(s["args"]["moe_experts_hit_decode"] for s in steps)
            / (len(steps) * slots), len(steps))
