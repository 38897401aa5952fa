"""A reference a test can work out by hand: the logits at a position are the
row of `params["table"]` that its token selects (a bigram model). It stands
where a configuration's `reference` stands, for `test_control.py`."""
import jax.numpy as jnp


def sequence_logits(params, ids, config, rows=None):
    t = params["table"].astype(jnp.float32)[jnp.asarray(ids, jnp.int32)]
    return t if rows is None else t[jnp.asarray(rows)]
