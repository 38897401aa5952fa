"""Of the keys the full layers' decode queries could see, the share their
selection kept and attention read: the step spans' `attn_keys_selected_decode`
over `attn_keys_visible_decode`, summed over the window's steps. Both are
counted inside the step program over the DECODE GROUP of every step (the rows
one token wide, whether or not a chunk rides beside them), from the masks the
program applies: the index scores a query's selection ranks, and the gathered
rows its attention is handed. So it reads `index_topk` over the mean context
of the rows (about 2,048 / 9,500 in the notes mix) while the selection works,
and 100 from a program that attends every causal key
(tests/test_serving_dots3.py plants that). None where the spans carry
neither."""


def read(run):
    steps = [s for s in run.window_steps()
             if s["args"].get("attn_keys_visible_decode")]
    if not steps:
        return None
    return (100.0 * sum(s["args"]["attn_keys_selected_decode"] for s in steps)
            / sum(s["args"]["attn_keys_visible_decode"] for s in steps),
            len(steps))
