"""What a step of the latent-attention / gated-expert decoder (`model_type`
`dots3_note`) must do at the least, from the published keys of its
configuration file. A layer is latent attention of one of two kinds
(`layer_types`: `full_attention` with an indexer and a top-`index_topk`
selection, `sliding_attention` with the `swa_*` sizes and a window) and a
feed-forward: dense below `first_k_dense_replace`, else a router, a shared
expert and, of the `n_routed_experts` held here, those a token's picks name.
Operations and bytes of the algorithm, not of the implementation."""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
FULL, WINDOW = "full_attention", "sliding_attention"


def layers(config):
    """`(full, window, dense, expert)` layers in the file."""
    kinds = config["layer_types"]
    dense = min(config["first_k_dense_replace"], len(kinds))
    return kinds.count(FULL), kinds.count(WINDOW), dense, len(kinds) - dense


def sizes(config, kind):
    """`(heads, r_q, r_kv, d_n, d_r, d_v)` of a layer kind."""
    p = "swa_" if kind == WINDOW else ""
    return tuple(config[p + k] for k in (
        "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim"))


def router_width(config):
    return config.get("router_experts", config["n_routed_experts"])


def attention_elements(config, kind):
    """An attention layer's matrices: what multiplies the normed stream
    (`W_qa`, `W_kva`, the gate and, in a full layer, the indexer's key and
    head-weight projections), `W_qb`, `W_kvb` (absorbed or expanded: the
    same elements a token), the indexer's query projection, `W_o`."""
    H = config["hidden_size"]
    heads, r_q, r_kv, d_n, d_r, d_v = sizes(config, kind)
    index = (config["index_head_dim"] + config["index_n_heads"],
             r_q * config["index_n_heads"] * config["index_head_dim"]) \
        if kind == FULL else (0, 0)
    return (H * (r_q + r_kv + d_r + heads + index[0])
            + r_q * heads * (d_n + d_r) + r_kv * heads * (d_n + d_v)
            + index[1] + heads * d_v * H)


def moe_shared_elements(config):
    """What every token runs of an expert layer outside its routed experts:
    the router over its whole width and the shared expert's three
    matrices."""
    H = config["hidden_size"]
    return H * router_width(config) + 3 * H * config["moe_intermediate_size"]


def expert_elements(config):
    """One routed expert: `W1`, `W3` hidden -> expert width and `W2` back."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def dense_elements(config):
    """Matrix elements every fed token runs, all layers: attention of both
    kinds, the dense feed-forward, the expert layers outside their routed
    experts."""
    n_full, n_win, n_dense, n_moe = layers(config)
    return (n_full * attention_elements(config, FULL)
            + n_win * attention_elements(config, WINDOW)
            + n_dense * 3 * config["hidden_size"] * config["intermediate_size"]
            + n_moe * moe_shared_elements(config))


def selected_key_flops(config):
    """A (query token, selected key) pair of a full layer, absorbed (a
    query's selected rows are its own, so nothing can be expanded once for
    many queries): scores over `[c_kv | k_r]` and the output over `c_kv`,
    every head."""
    heads, _, r_kv, _, d_r, _ = sizes(config, FULL)
    return 2 * heads * (2 * r_kv + d_r)


def index_key_flops(config):
    """A (query token, visible key) pair of a full layer's indexer."""
    return 2 * config["index_n_heads"] * config["index_head_dim"]


def window_key_flops(config):
    """A (query token, key in its window) pair of a window layer, expanded
    (the cheaper order; a decode row's absorbed products are five times
    these, so the share errs low there)."""
    heads, _, _, d_n, d_r, d_v = sizes(config, WINDOW)
    return 2 * heads * (d_n + d_r + d_v)


def serve_flops(config, fed_tokens, emitted_tokens, pairs_local,
                keys_selected, keys_visible, window_keys):
    """Forward over `fed_tokens`: 2 per matrix element a token in every
    layer's dense part, 2 per element of an expert for each (token, held
    expert) pair computed (the spans' `moe_pairs_local`), the untied head
    over the `emitted_tokens` rows that are sampled, and attention over the
    keys the step's counts name: the selected form's products
    (`attn_keys_selected`), the indexer's over every visible key
    (`attn_keys_visible`), the window layers' (`window_keys_read`); all three
    already summed over the layers of their kind. A program that masked a
    dense product would compute more and count the same: it reads low."""
    return (2.0 * (fed_tokens * dense_elements(config)
                   + pairs_local * expert_elements(config)
                   + emitted_tokens * config["vocab_size"]
                   * config["hidden_size"])
            + keys_selected * selected_key_flops(config)
            + keys_visible * index_key_flops(config)
            + window_keys * window_key_flops(config))


def window_bytes_per_token(config):
    """What the window layers would hold a token with every position kept:
    a row `[c_kv | k_r]` a layer."""
    _, _, r_kv, _, d_r, _ = sizes(config, WINDOW)
    return layers(config)[1] * (r_kv + d_r) * ITEMSIZE[config["dtype"]]


def expert_slots(config, held):
    """(layer, held expert) pairs a step can send tokens to."""
    return held * layers(config)[3]
