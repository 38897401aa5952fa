"""Operations the mathematics needs, from shapes. Recomputed operations
(remat, the fused head's backward) are not counted."""
from __future__ import annotations


def dense_train_flops_per_token(hidden_size, num_layers, seq_len, vocab_size,
                                intermediate_size):
    """Forward and backward of a dense decoder: 6 per matrix parameter a token
    (qkv + projection, the MLP, the tied head) plus causal attention's two
    products, forward and backward, half of the square. Copied from the
    program's `profiler/flops.py dense_train_flops_per_token`."""
    H, L, S, V, F = (hidden_size, num_layers, seq_len, vocab_size,
                     intermediate_size)
    n_matmul = L * (4 * H * H + 2 * H * F) + V * H
    attn = L * 2 * S * H * 3
    return 6.0 * n_matmul + attn


def dense_serve_flops(hidden_size, num_layers, vocab_size, intermediate_size,
                      fed_tokens, emitted_tokens):
    """Forward of a dense decoder over `fed_tokens` (prompt chunks and decode
    tokens alike), the tied head over the `emitted_tokens` rows that are
    sampled: 2 per matrix parameter a token. Attention's two products over
    the context are left out (2-3 % of a token's at some hundreds of tokens
    of context), so the share of the peak this gives errs low."""
    H, L, V, F = hidden_size, num_layers, vocab_size, intermediate_size
    return 2.0 * (fed_tokens * L * (4 * H * H + 2 * H * F)
                  + emitted_tokens * V * H)
