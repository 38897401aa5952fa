"""What a looped decoder's step must do at the least, from the published keys
of its configuration file: `num_hidden_layers` layers run `total_ut_steps`
times a token, attention of `num_attention_heads` heads of `head_dim`, a gated
feed-forward of `intermediate_size`, an untied head over `vocab_size`.
Operations and bytes of the algorithm, not of the implementation."""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def sizes(config):
    """`(H, A, F, L, T, V, itemsize)`: hidden width, attention width (heads
    x head size), feed-forward width, layers, passes, vocabulary, bytes a
    weight or a cached value."""
    return (config["hidden_size"],
            config["num_attention_heads"] * config["head_dim"],
            config["intermediate_size"], config["num_hidden_layers"],
            config["total_ut_steps"], config["vocab_size"],
            ITEMSIZE[config["dtype"]])


def layer_matrix_elements(config):
    """One layer's matrices: q, k, v, o and the gate, up and down
    projections (the four norm gains are a thousandth of a percent)."""
    H, A, F, *_ = sizes(config)
    return 4 * H * A + 3 * H * F


def serve_flops(config, fed_tokens, emitted_tokens):
    """Forward over `fed_tokens` (prompt chunks and decode tokens alike):
    2 per matrix element a token a pass, every layer `total_ut_steps` times,
    and the untied head over the `emitted_tokens` rows that are sampled.
    Attention's two products over the context are left out, as in
    `flops.dense_serve_flops`, so the share of the peak this gives errs
    low."""
    H, _, _, L, T, V, _ = sizes(config)
    return 2.0 * (fed_tokens * T * L * layer_matrix_elements(config)
                  + emitted_tokens * V * H)


def decode_stream_bytes(config, kv_tokens):
    """What a pure decode step must read from HBM once: every layer's
    matrices once a pass (the chip's fast memory holds a fraction of one
    layer, so a pass cannot keep the last one's), the head, and the keys and
    values of `kv_tokens` tokens of context (summed over the rows) in each
    of the `total_ut_steps x num_hidden_layers` slabs. The embedding rows,
    the gains and the step's own activations are left out: the share errs
    low."""
    H, A, _, L, T, V, itemsize = sizes(config)
    weights = (T * L * layer_matrix_elements(config) + V * H) * itemsize
    return weights + kv_tokens * T * L * 2 * A * itemsize
