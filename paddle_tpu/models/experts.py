"""What the expert layers of the served models share: the sigmoid router and
the dropless dispatch of a step's (token, pick) pairs to the experts THIS
chip holds. `models/nemotron_h.py LatentMoE` (a two-matrix expert on a
latent width) and `models/dots3.py GatedMoE` (a three-matrix gated expert on
the full width) differ in the expert's form only, which they hand in.

THE CHIP'S SHARE (expert parallelism without its exchange). A layer holds
experts `[experts_from, experts_from + held)` of the router's width. The
router keeps its whole width, its picks and the normalisation over all of
them; the sum runs over the picks that are held here; what the absent
experts would have added is left out. The dispatch is dropless: every held
pick of a live token is computed whatever the imbalance (pairs sorted by held
expert, a grouped product over the stacked expert weights:
`ops/pallas/grouped_matmul.py`); slots that carry no token are not
dispatched.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# the router's type (input, weight, product, sigmoid); a test turns it to
# bfloat16 to show that its comparison would catch that
ROUTER_DTYPE = jnp.float32


def sigmoid_route(a, weight, bias, per_tok, scaling, normed):
    """`(picks [T, per_tok], weights [T, per_tok])` for the normed stream
    `a` `[T, hidden]`, over the router's whole width: `s = sigmoid(a W_r)`,
    the `per_tok` experts with the largest `s + b` (`bias`, the published
    `e_score_correction_bias`, chooses only), weights `scaling x s_e`, over
    the sum of the chosen `s` where `normed`."""
    logits = jnp.matmul(a.astype(ROUTER_DTYPE), weight.astype(ROUTER_DTYPE),
                        precision=jax.lax.Precision.HIGHEST)
    s = jax.nn.sigmoid(logits).astype(jnp.float32)
    _, picks = jax.lax.top_k(s + bias.astype(jnp.float32), per_tok)
    chosen = jnp.take_along_axis(s, picks, axis=-1)
    if normed:
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return picks, scaling * chosen


def held_expert_sum(u, picks, weights, live, experts_from, held, expert):
    """`(sum over a token's held picks of w_e f_e(u) [T, out] float32,
    local [T, k], sizes [held])` for the experts' input `u` `[T, in]` (in
    the experts' type). `expert(x [T k, in], sizes [held]) -> [T k, out]` is
    the expert's form over rows sorted by held expert, `sizes` rows an
    expert (grouped products); `live` `[T]` says which slots hold a token.
    `local` says which (token, pick) pairs were computed here
    (`pair_counts` counts them)."""
    T, k = picks.shape
    local = ((picks >= experts_from) & (picks < experts_from + held)
             & live[:, None])
    # pairs sorted by held expert; every other pair (an absent expert's, a
    # dead slot's) behind them under the key `held`, outside every group:
    # not dispatched
    key = jnp.where(local, picks - experts_from, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sorted_key = key[order]
    bounds = jnp.searchsorted(sorted_key, jnp.arange(held + 1))
    sizes = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    y = expert(u[order // k], sizes)                      # [T k, out]
    # back to (token, pick) order; rows outside the groups hold nothing
    # that is read
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    y = y[back].reshape(T, k, -1)
    routed = jnp.sum(
        jnp.where(local[..., None], y * weights[..., None], 0.0), axis=1)
    return routed, local, sizes


def pair_counts(live, local, sizes):
    """An expert layer's counts of a step, int32 scalars `(pairs,
    pairs_local, experts_hit, max_load)`: the picks of the live tokens, those
    of them computed here, the held experts that got a token, the largest
    number one got."""
    return (jnp.sum(live).astype(jnp.int32) * local.shape[1],
            jnp.sum(local).astype(jnp.int32),
            jnp.sum(sizes > 0).astype(jnp.int32), jnp.max(sizes))


def held_experts_hit(picks, pairs, experts_from, held):
    """How many of the held experts the (token, pick) pairs `pairs` `[T, k]`
    bool name: int32."""
    named = jnp.zeros(held + 1, bool).at[
        jnp.where(pairs, picks - experts_from, held).reshape(-1)].set(True)
    return jnp.sum(named[:held], dtype=jnp.int32)


def sum_step_stats(per_layer):
    """The step's four expert counts out of one tuple a layer, as the two
    leading parts of the vector a model leaves on `caches.step_stats`:
    `[sums of the first three [3], the largest load [1]]`, int32."""
    per_layer = jnp.array(per_layer, jnp.int32).reshape(-1, 4)
    return [jnp.sum(per_layer[:, :3], axis=0),
            jnp.max(per_layer[:, 3:], axis=0, initial=0)]
