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
