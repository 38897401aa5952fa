"""Initialisation rules for configurations whose source publishes none in its
`config.json`, in the signature `weights.build_model` calls:
`(name, shape, key, dtype, num_layers)`."""
from __future__ import annotations


def looped_decoder(name, shape, key, dtype, num_layers):
    """Matrices N(0, 0.02) and biases 0, the range the decoder families this
    block descends from publish as `initializer_range`; norm gains 1, but
    the gains of the two norms that write into the residual stream (after
    attention and after the feed-forward, `norm2` and `norm4`):
    1/sqrt(2 x num_layers).

    That is GPT-2's rule (`weights.gpt2`: the projections that write into the
    stream scaled by 1/sqrt(2L)) put where this block has it: a projection's
    own scale is normed away by the norm behind it, so the norm's gain is
    what sets how much a sub-layer adds. With gains of 1 every sub-layer
    adds a unit-RMS vector to a stream that starts each pass at RMS 1, and
    the random network is chaotic: it multiplies a relative perturbation by
    2.7 a pass at 48 layers, so bf16's operand rounding alone reads a
    `logit_deficit` of 0.35-0.51 against the float32 reference (PERF.md
    section 6, PR 34: on the chip through the engine, and here on the CPU
    in the model's plain forward with no cache and no kernel), which says
    nothing of the program. Scaled, a pass adds as much as it starts with
    and the factor is 1.1."""
    import jax
    import jax.numpy as jnp

    if len(shape) >= 2:
        return (jax.random.normal(key, shape, jnp.float32) * 0.02
                ).astype(dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    if ".norm2." in name or ".norm4." in name:
        return jnp.full(shape, (2.0 * num_layers) ** -0.5, dtype)
    return jnp.ones(shape, dtype)
