"""Seeded weights, made on the device in one jitted call in the type they are
served or trained in. The model is built as a skeleton (names and shapes, no
data), so nothing is initialised leaf by leaf or on the host."""
from __future__ import annotations

import re

from . import spec


def gpt2(name, shape, key, dtype, num_layers):
    """GPT-2's published initialisation (Radford et al. 2019, as Cerebras-GPT
    keeps it: `initializer_range` 0.02): matrices N(0, 0.02), the two
    projections that write into the residual stream scaled by 1/sqrt(2L),
    LayerNorm gains 1, every bias 0."""
    import jax
    import jax.numpy as jnp

    if len(shape) >= 2:
        std = 0.02
        if re.search(r"\.(proj|fc2)\.weight$", name):
            std /= (2.0 * num_layers) ** 0.5
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)
    if name.endswith("bias"):
        return jnp.zeros(shape, dtype)
    return jnp.ones(shape, dtype)


def build_model(config, seed):
    """The configuration's model, built by the builder it names, holding
    weights drawn from `seed` by the rule it names."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.layer import skeleton_init

    m = config["model"]
    with skeleton_init():
        model = spec.resolve(m["builder"])(
            spec.resolve(m["config"])(**spec.model_kwargs(config)))
    rule = spec.resolve(m["init"])
    dtype = jnp.dtype(config["dtype"])
    named = model.named_parameters_dict()
    shapes = {k: tuple(p._array.shape) for k, p in named.items()}
    num_layers = model.cfg.num_layers

    @jax.jit
    def make(key):
        return {k: rule(k, shape, jax.random.fold_in(key, i), dtype,
                        num_layers)
                for i, (k, shape) in enumerate(sorted(shapes.items()))}

    arrays = make(jax.random.PRNGKey(seed))
    for k, p in named.items():
        p._array = arrays[k]
    return model


def initial_arrays(config, seed):
    """The same weights again, as a dict of arrays: training donates the
    model's own, and the reference is fed the initial ones after the window."""
    import jax

    fresh = build_model(config, seed)
    out = {k: p._array for k, p in fresh.named_parameters_dict().items()}
    return jax.block_until_ready(out)
