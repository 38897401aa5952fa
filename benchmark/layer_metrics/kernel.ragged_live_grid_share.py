"""Share of the ragged kernel's grid steps that did useful work: live steps
over all steps, a head a layer, summed over the window's steps. The engine's
step span carries both (`grid_live`, `grid_total`, counted by
`ops/pallas/paged_attention.py ragged_grid_steps` from the vectors the step
was built from): a step is live where a row that carries a request has a
query tile and a KV block there; the rest of `max_batch x query tiles x
max_blocks` is walked and skipped. None where the spans carry no grid."""


def read(run):
    steps = [s["args"] for s in run.window_steps()
             if "grid_total" in s["args"]]
    total = sum(a["grid_total"] for a in steps)
    if not total:
        return None
    return 100.0 * sum(a["grid_live"] for a in steps) / total, len(steps)
