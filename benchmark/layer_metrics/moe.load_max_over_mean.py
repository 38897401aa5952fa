"""The largest load of a held expert in a step (tokens sent to one expert of
one layer: the span's `moe_max_load`) over the mean load of that step (the
pairs computed here, `moe_pairs_local`, over the experts held x the expert
layers); median over the window's steps that computed any pair. 1 is even;
a decode step of 32 tokens puts 1.4 tokens on an expert on average, so the
largest of 640 such counts lies several times above it by chance alone. None
where the spans carry no `moe_max_load`."""
from benchmark.reduce import hybrid
from benchmark.reduce.stats import median


def read(run):
    held = (run.gauges or {}).get("experts_held")
    steps = [s for s in run.window_steps()
             if s["args"].get("moe_pairs_local")]
    if not steps or not held:
        return None
    slots = hybrid.expert_slots(run.config, held)
    ratios = [s["args"]["moe_max_load"]
              / (s["args"]["moe_pairs_local"] / slots) for s in steps]
    return median(ratios), len(ratios)
