"""The whole step's share of the chip's peak for the latent-attention /
gated-expert decoder, window: the operations the forward needs for the tokens
the window's steps were fed and emitted: matrices over fed tokens, the expert
term from the (token, held expert) pairs computed, the head over emitted
tokens, and attention's and the indexer's products over the keys the steps
counted (`reduce/latent.py serve_flops`, from the step spans' `fed_tokens`,
`emitted_tokens`, `moe_pairs_local`, `attn_keys_selected`,
`attn_keys_visible`, `window_keys_read`), over the window's seconds times the
peak. The selected form's count: a program that masked a dense product would
read low, not high. None where the spans carry no `attn_keys_selected` (a
program that has no such model)."""
from benchmark.reduce import latent
from benchmark.reduce.peaks import peaks


def read(run):
    steps = run.window_steps()
    if (not steps or not run.on_chip
            or "attn_keys_selected" not in steps[0]["args"]):
        return None
    total = lambda key: sum(s["args"][key] for s in steps)  # noqa: E731
    flops = latent.serve_flops(
        run.config, total("fed_tokens"), total("emitted_tokens"),
        total("moe_pairs_local"), total("attn_keys_selected"),
        total("attn_keys_visible"), total("window_keys_read"))
    peak = run.device["count"] * peaks(run.device["kind"])["flops_bf16"]
    return 100.0 * flops / (run.seconds * peak), len(steps)
