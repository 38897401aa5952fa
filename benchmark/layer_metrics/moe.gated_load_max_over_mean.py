"""The largest load of a held expert in a step (tokens sent to one expert of
one layer: the span's `moe_max_load`) over the mean load of that step (the
pairs computed here, `moe_pairs_local`, over the experts held x the expert
layers of `layer_types`); median over the window's steps that computed any
pair. 1 is even. `moe.load_max_over_mean`'s counterpart for the gated
three-matrix expert (that reader counts the hybrid's pattern): a reading of
the router's picks, not of the program's speed. None where the spans carry no
`moe_max_load` or the file has no `layer_types`."""
from benchmark.reduce import latent
from benchmark.reduce.stats import median


def read(run):
    held = (run.gauges or {}).get("experts_held")
    steps = [s for s in run.window_steps()
             if s["args"].get("moe_pairs_local")]
    if not steps or not held or "layer_types" not in run.config:
        return None
    slots = latent.expert_slots(run.config, held)
    ratios = [s["args"]["moe_max_load"]
              / (s["args"]["moe_pairs_local"] / slots) for s in steps]
    return median(ratios), len(ratios)
