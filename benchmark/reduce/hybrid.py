"""What a step of the hybrid Mamba-2 / attention / latent-expert decoder
(`model_type` `nemotron_h`) must do at the least, from the published keys of
its configuration file. One part a layer, by the character of
`hybrid_override_pattern`: `M` a Mamba-2 mixer (`in_proj`, `out_proj`), `*`
attention with `num_key_value_heads` K/V heads, `E` a latent mixture of
experts (router, the two latent projections, the shared expert, and of the
`n_routed_experts` experts held here those a token's picks name). Operations
and bytes of the algorithm, not of the implementation."""
from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}
ROUTER_ITEMSIZE = 4          # the router is float32 whatever the weights are
SSM_STATE_ITEMSIZE = 4       # and so is the recurrent state


def layers(config):
    """`(mamba, attention, expert)` layers in the pattern."""
    p = config["hybrid_override_pattern"]
    return p.count("M"), p.count("*"), p.count("E")


def mamba_widths(config):
    """`(inner, xBC)`: the mixer's inner width (heads x head size) and the
    convolution's channels (inner + B and C of every group)."""
    inner = config["mamba_num_heads"] * config["mamba_head_dim"]
    return inner, inner + 2 * config["n_groups"] * config["ssm_state_size"]


def router_width(config):
    """The router's outputs: `router_experts` where the file holds a share
    of the experts, else all of `n_routed_experts`."""
    return config.get("router_experts", config["n_routed_experts"])


def mamba_elements(config):
    """A Mamba-2 layer's two matrices: `in_proj` hidden -> z | xBC | dt and
    `out_proj` inner -> hidden (the convolution, `A`, `D`, `dt_bias` and the
    gains are a thousandth of them)."""
    H = config["hidden_size"]
    inner, xbc = mamba_widths(config)
    return H * (inner + xbc + config["mamba_num_heads"]) + inner * H


def attention_elements(config):
    H, d = config["hidden_size"], config["head_dim"]
    q, kv = config["num_attention_heads"], config["num_key_value_heads"]
    return H * (q + 2 * kv) * d + q * d * H


def moe_shared_elements(config):
    """What every token runs of an expert layer outside its routed experts:
    the router over its whole width (`router_experts`; the file's
    `n_routed_experts` where it holds them all), the projections into and
    out of the latent width, the shared expert's two matrices."""
    H, Z = config["hidden_size"], config["moe_latent_size"]
    return (H * router_width(config) + 2 * H * Z
            + 2 * H * config["moe_shared_expert_intermediate_size"])


def expert_elements(config):
    """One routed expert: `W1` latent -> expert width and `W2` back."""
    return 2 * config["moe_latent_size"] * config["moe_intermediate_size"]


def serve_flops(config, fed_tokens, emitted_tokens, pairs_local):
    """Forward over `fed_tokens`: 2 per matrix element a token in every
    layer's dense part, 2 per element of an expert for each (token, held
    expert) pair computed (`pairs_local`, summed over the expert layers: the
    step spans' `moe_pairs_local`), and the untied head over the
    `emitted_tokens` rows that are sampled. Attention's products over the
    context and the scan's products over the state (6 % of a Mamba layer's
    matrices) are left out, so the share of the peak this gives errs low."""
    n_m, n_a, n_e = layers(config)
    dense = (n_m * mamba_elements(config) + n_a * attention_elements(config)
             + n_e * moe_shared_elements(config))
    return 2.0 * (fed_tokens * dense + pairs_local * expert_elements(config)
                  + emitted_tokens * config["vocab_size"]
                  * config["hidden_size"])


def state_bytes_per_lane(config):
    """A lane's recurrent state, all Mamba layers: the float32 state
    `[heads, head_dim, state]` and the convolution's `conv_kernel - 1` last
    inputs in the weights' type."""
    n_m, _, _ = layers(config)
    inner, xbc = mamba_widths(config)
    return n_m * (inner * config["ssm_state_size"] * SSM_STATE_ITEMSIZE
                  + (config["conv_kernel"] - 1) * xbc
                  * ITEMSIZE[config["dtype"]])


def kv_bytes_per_token(config):
    _, n_a, _ = layers(config)
    return (n_a * 2 * config["num_key_value_heads"] * config["head_dim"]
            * ITEMSIZE[config["dtype"]])


def expert_slots(config, held):
    """(layer, held expert) pairs a step can send tokens to: `held` experts
    (the program's `experts_held` gauge) in every expert layer."""
    return held * layers(config)[2]


def decode_stream_bytes(config, rows, experts_hit, kv_tokens):
    """What a pure decode step of `rows` rows must move through HBM: every
    layer's dense matrices and the head once, each held expert that a token
    of the step picked once (`experts_hit`, summed over the expert layers:
    the span's `moe_experts_hit`), the rows' recurrent state read and
    written, and the keys and values of `kv_tokens` tokens of context once.
    Embedding rows, gains and the step's own activations are left out: the
    share errs low."""
    item = ITEMSIZE[config["dtype"]]
    n_m, n_a, n_e = layers(config)
    H = config["hidden_size"]
    dense = (n_m * mamba_elements(config) + n_a * attention_elements(config)
             + n_e * moe_shared_elements(config)
             + config["vocab_size"] * H) * item
    dense += n_e * H * router_width(config) * (ROUTER_ITEMSIZE - item)
    return (dense + experts_hit * expert_elements(config) * item
            + 2 * rows * state_bytes_per_lane(config)
            + kv_tokens * kv_bytes_per_token(config))
