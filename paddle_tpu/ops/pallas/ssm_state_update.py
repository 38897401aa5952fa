"""The decode step of a Mamba-2 layer's scan, its state updated in place in
the pool's stacked leaf: `models/nemotron_h.py _mamba_paged`, for the decode
group (one token a row, row i lane i).

For each (lane, head), with `g` the head's group:

    S_new = exp(dt A) S_in + (dt x) B_g^T        (S_in zeros for a fresh lane)
    y     = S_new C_g

`ssm_chunk` at width 1 (the `D x` term and the gate stay with the caller).
Everything is float32. A lane whose row carries no token and is not fresh
keeps its state bit for bit (no arithmetic touches it); its `y` is `S_in
C_g`, what the XLA form gives it.

`ssm_state_update(S, layer, x, B, C, dt, A, fresh, live) -> (y, S)` reads
layer `layer` of the leaf `S` `[layers, lanes, heads, head_dim, state]` and
writes the new state back into the same leaf: the leaf is aliased input to
output and the layer is a prefetched scalar of the block index, so only that
layer's blocks are read and written, once each, and the other layers' state
is never copied. `why_xla(S)` says why a leaf cannot take it (None where it
can): the kernels off, a state that is not float32, a state size that is no
multiple of 128 (the lanes of a block row) or a head size that is no multiple
of 8 (its sublanes). The caller keeps the XLA form for those and counts it
(`_backend.note_xla_fallback`).

Kernel design (TPU): grid `(lanes, heads / hb)`; a step holds `hb` whole
`[head_dim, state]` head states (`head_block`), read and written once through
the pipeline. The decays and the rows' modes (keep, from the state, from
zeros) are prefetched scalars; `dt x` arrives as one row of `hb x head_dim`
and `y` leaves as one; B and C are the lane's `[groups, state]`, a head's
group row read by index. No value moves along the lanes (`_kernel`). On a
v5e the kernel takes what a pass that only copies the layer takes (PERF.md
section 6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._backend import interpret_mode, use_pallas

# a block of whole head states may take this much. On a v5e at the hybrid
# cell's shapes the kernel alone takes 0.410 ms a layer in blocks of 8, 32
# and 128 heads alike (256 KiB, 1 MiB, 4 MiB), but inside the step program
# blocks of 8 took 0.475 ms and blocks of 32 0.414 (PERF.md section 6)
_STATE_BLOCK_BYTES = 1 << 20
# a row's modes: its state kept as it is, updated, or started from zeros
KEEP, UPDATE, FROM_ZEROS = 0, 1, 2


def head_block(heads, head_dim, state):
    """Heads a grid step: the largest divisor `hb` of `heads` whose `[hb,
    head_dim, state]` float32 fits `_STATE_BLOCK_BYTES` and whose `hb x
    head_dim` values of `dt x` and `y` fill whole 128-lane rows, with `hb`
    a multiple of 8 (a block's sublanes); or all the heads. None where no
    block fits."""
    head_bytes = head_dim * state * 4
    fits = [hb for hb in range(8, heads + 1, 8)
            if heads % hb == 0 and hb * head_dim % 128 == 0
            and hb * head_bytes <= _STATE_BLOCK_BYTES]
    if fits:
        return max(fits)
    return heads if heads * head_bytes <= _STATE_BLOCK_BYTES else None


def why_xla(S):
    """Why the leaf `S` `[layers, lanes, heads, head_dim, state]` takes the
    XLA form and not the kernel; None where it takes the kernel."""
    *_, heads, head_dim, state = S.shape
    if not use_pallas():
        return "kernels off"
    if S.dtype != jnp.float32:
        return f"a {S.dtype} state"
    if state % 128:
        return "a state size that is no multiple of 128"
    if head_dim % 8:
        return "a head size that is no multiple of 8"
    if head_block(heads, head_dim, state) is None:
        return "no block of whole heads fits"
    return None


def _kernel(hb, per_group, layer_ref, mode_ref, decay_ref, dtx_ref, b_ref,
            c_ref, s_ref, o_ref, y_ref):
    """Heads `[j hb, (j + 1) hb)` of lane `r`: `dtx_ref` `[1, 1, hb P]` (a
    head's `dt x` after another's), `b_ref` / `c_ref` `[1, groups, N]` (the
    lane's), `s_ref` / `o_ref` `[1, 1, hb, P, N]` (layer `layer_ref[0]` of
    the leaf, aliased), `y_ref` `[1, 1, hb P]`.

    No value moves along the lanes: `dt x` is laid down the sublanes and
    turned (`[N, hb P]` -> `[hb P, N]`: row `h P + p` holds head h's `dt
    x_p` in every lane), and `y`'s sums over the state run down the
    sublanes of the products turned the other way. On a v5e this pass
    takes what its bytes take (PERF.md section 6); summing along the
    lanes and broadcasting a column along them took 0.08 ms a layer more."""
    from jax.experimental import pallas as pl

    r, j = pl.program_id(0), pl.program_id(1)
    heads = pl.num_programs(1) * hb
    mode = mode_ref[r]
    P, N = s_ref.shape[3:]
    dtx = jnp.broadcast_to(dtx_ref[0], (N, hb * P)).T     # [hb P, N]
    products = []
    for h in range(hb):
        head = j * hb + h
        g = pl.ds(head // per_group, 1)
        S = s_ref[0, 0, h]                                # [P, N]
        base = jnp.where(mode == FROM_ZEROS, 0.0, S)
        new = (decay_ref[r * heads + head] * base
               + dtx[h * P:(h + 1) * P] * b_ref[0, g])
        new = jnp.where(mode == KEEP, S, new)
        o_ref[0, 0, h] = new
        products.append(new * c_ref[0, g])
    y_ref[0] = jnp.sum(jnp.concatenate(products, axis=0).T, axis=0,
                       keepdims=True)


@functools.lru_cache(maxsize=None)
def _build(layers, lanes, heads, P, N, groups, hb, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    state = pl.BlockSpec((1, 1, hb, P, N),
                         lambda r, j, layer, *_: (layer[0], r, j, 0, 0))
    row = pl.BlockSpec((1, groups, N), lambda r, j, *_: (r, 0, 0))
    heads_of = pl.BlockSpec((1, 1, hb * P), lambda r, j, *_: (r, 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(lanes, heads // hb),
        in_specs=[heads_of, row, row, state],
        out_specs=[state, heads_of],
    )
    block = hb * P * N * 4
    return pl.pallas_call(
        functools.partial(_kernel, hb, heads // groups),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((layers, lanes, heads, P, N),
                                        jnp.float32),
                   jax.ShapeDtypeStruct((lanes, 1, heads * P), jnp.float32)],
        # operands: 3 prefetched scalars, dt x, B, C, then the leaf
        input_output_aliases={6: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            # the state block's four copies in the pipeline and a megabyte
            # for the rest: at the hybrid cell's shapes three copies and a
            # megabyte do not compile, and six or eight take the same time.
            # No more (PERF.md section 6: VMEM asked and not needed
            # is taken from the arrays of the program around it)
            vmem_limit_bytes=4 * block + (1 << 20)),
        interpret=interpret,
        name="ssm_state_update",
    )


def ssm_state_update(S, layer, x, B, C, dt, A, fresh, live):
    """The decode step of layer `layer` (a Python int) on the leaf `S`
    `[layers, lanes, heads, P, N]` float32, row i lane i: `x` `[lanes,
    heads, P]`, `B`, `C` `[lanes, groups, N]`, `dt` `[lanes, heads]` (after
    softplus; 0 where a row carries no token), `A` `[heads]`, `fresh` and
    `live` `[lanes]` (the row starts from zeros; it carries a token), all
    float32 but the two flags. Returns `(y [lanes, heads, P], S)`, `S` the
    leaf with layer `layer` replaced."""
    layers, lanes, heads, P, N = S.shape
    groups = B.shape[1]
    fn = _build(layers, lanes, heads, P, N, groups,
                head_block(heads, P, N), interpret_mode())
    mode = jnp.where(fresh, FROM_ZEROS, jnp.where(live, UPDATE, KEEP))
    decay = jnp.exp(dt * A[None, :])
    S, y = fn(np.full((1,), layer, np.int32), mode.astype(jnp.int32),
              decay.reshape(-1), (dt[..., None] * x).reshape(lanes, 1, -1),
              B, C, S)
    return y.reshape(lanes, heads, P), S
