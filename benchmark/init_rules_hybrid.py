"""The initialisation rule of the hybrid Mamba-2 / attention / expert
configuration (`benchmark/configs/nemotron3s-ep4-serve.json`), in the
signature `weights.build_model` calls: `(name, shape, key, dtype,
num_layers)`. `init_rules.py` is the looped decoder's and is left as it is."""
from __future__ import annotations

import re

# the projections that write into the residual stream: Mamba's `out_proj`,
# attention's `o`, the expert layer's `latent_up` and its shared expert's
# second matrix
WRITES_INTO_STREAM = re.compile(
    r"\.(mixer\.out_proj|attn\.o|moe\.latent_up|moe\.shared\.v2)\.weight$")
# of those, W_up, which writes the routed experts' sum
WRITES_ROUTED_SUM = re.compile(r"\.moe\.latent_up\.weight$")
# W_up's further scale. No count of anything: set from two readings of
# `logit_deficit` on the chip (the rule's docstring). Written as 22 ** -0.5
# (0.2132) because PR 36's recorded runs used that float, bit for bit
ROUTED_WRITE_SCALE = 22 ** -0.5
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 1e-3, 1e-1, 1e-4


def nemotron_h(name, shape, key, dtype, num_layers):
    """Matrices N(0, 0.02); the projections that write into the stream
    scaled by 1/sqrt(layers present), which is what the published
    `rescale_prenorm_residual: true` does (one part a layer, so the count is
    the layers and not twice them).

    `latent_up` (W_up) is scaled by `ROUTED_WRITE_SCALE` (0.2132) more, and
    the reason is the comparison, not the model. bf16 rounding in front of
    the router exchanges the 22nd pick for the 23rd at 10-30 % of positions
    in every expert layer of a SOUND program, and with random weights the
    two experts are independent vectors, so `correct.py`'s `logit_deficit`
    (limit 0.12, not this PR's to change) reads those exchanges. Readings on
    the chip at the cell's size (my chip runs, PR 36; PERF.md section 2),
    the sound program / a program whose routed sum adds nothing
    (`latent_up` zeroed), largest and smallest of nine seeds (eight at
    0.60), by W_up's scale as a share of the other write-projections':

        1.00   sound 0.38      dead routed path 0.85
        0.75         0.35                        0.59
        0.60         0.119                       0.48
        0.40         0.109                       0.27
        0.30         0.100                       0.18
        0.2132       0.063 (38 runs)             0.126

    No scale lets 0.12 stand between the two with room on both sides; this
    one keeps the sound program correct with room (fresh seeds read higher)
    and still reads a dead routed path as a fault, by little. What the
    comparison cannot see at this scale: a fault of precision confined to
    the experts (fp8 on `experts.w1`/`w2` alone reads 0.005-0.009 here and
    0.011-0.048 at 0.5). The router, its scores and its picks are untouched and
    depend on the token as before; the experts' own matrices stay
    N(0, 0.02); the CPU tests hold the routed path to 1e-4 under weights
    that make it a large share.

    Mamba-2's own published rule for its
    three vectors: `A_log` = log of U(1, 16), `dt_bias` = the inverse
    softplus of a step drawn log-uniformly from [`time_step_min` 0.001,
    `time_step_max` 0.1] and floored at `time_step_floor` 1e-4, `D` = 1.
    Norm gains 1, the convolution's bias 0. The router N(0, 0.02) in
    float32 whatever `dtype` is (the published router is float32), its
    choosing bias `e_score_correction_bias` 0."""
    import jax
    import jax.numpy as jnp

    if ".router." in name:
        if name.endswith("bias"):
            return jnp.zeros(shape, jnp.float32)
        return jax.random.normal(key, shape, jnp.float32) * 0.02
    if name.endswith("A_log"):
        return jnp.log(jax.random.uniform(
            key, shape, jnp.float32, minval=1.0, maxval=16.0)).astype(dtype)
    if name.endswith("dt_bias"):
        lo, hi = jnp.log(TIME_STEP_MIN), jnp.log(TIME_STEP_MAX)
        step = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                       * (hi - lo) + lo)
        step = jnp.maximum(step, TIME_STEP_FLOOR)
        # softplus(x) = step  <=>  x = step + log(1 - exp(-step))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dtype)
    if len(shape) >= 2:
        std = 0.02
        if WRITES_INTO_STREAM.search(name):
            std /= num_layers ** 0.5
        if WRITES_ROUTED_SUM.search(name):
            std *= ROUTED_WRITE_SCALE
        return (jax.random.normal(key, shape, jnp.float32) * std
                ).astype(dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    return jnp.ones(shape, dtype)        # norm gains, Mamba's D
