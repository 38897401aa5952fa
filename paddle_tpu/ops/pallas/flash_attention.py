"""Flash attention v2: Pallas TPU kernels (fwd + bwd) + XLA fallback.

Layouts follow the reference flash_attention API
(/root/reference/python/paddle/nn/functional/flash_attention.py:20, CUDA
kernel paddle/phi/kernels/gpu/flash_attn_kernel.cu): q, k, v are
[batch, seq, num_heads, head_dim].

Kernel design (TPU):
- Forward: grid (batch*heads, q_blocks, k_blocks) with the k dimension
  innermost; VMEM holds one q tile and one k/v tile at a time (K/V stream
  through — sequence length is not bounded by whole-K-in-VMEM). Online
  softmax state (m, l, acc) lives in VMEM scratch that persists across the
  sequential k iterations; the output tile and the logsumexp are written on
  the last k step. fp32 accumulation on the MXU (preferred_element_type).
- Backward: two Pallas kernels recomputing p = exp(s - lse) FlashAttention-2
  style: dkv (grid bh, k_blocks, q_blocks; accumulates dk/dv in scratch) and
  dq (grid bh, q_blocks, k_blocks). delta = rowsum(dO * O) is a cheap XLA
  precompute.
- Causal uses bottom-right alignment (jnp.tril offset sk - sq), matching the
  XLA fallback and the reference semantics, and SKIPS fully-masked k tiles
  (pl.when) rather than just masking them.
- Additive float masks stream through the same grid as an extra input
  ([B|1, H|1, Sq, Sk], broadcast handled by the index map).
- Dropout draws keep-bits in-kernel (pltpu.prng_*) seeded per (bh, q, k)
  tile, so forward and backward regenerate identical masks with no stored
  dropout state.
- Why the wrapper reshapes [B,S,H,D] -> [B*H,S,D] around the kernels
  (tried and rejected in r4): reading the native layout via 4-D blocks
  (1, bq, 1, d) is not lowerable — Mosaic requires the block's minor two
  dims to be (8, 128)-divisible or equal to the array dims, and the head
  axis sits second-to-minor. The transposes XLA inserts around the
  custom-calls are the price of the paddle-native [B,S,H,D] API layout.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ._backend import interpret_mode, note_xla_fallback, use_pallas

_NEG_INF = -1e30


# ---------------------------------------------------------------------------
# XLA fallback (also the correctness reference in tests)
# ---------------------------------------------------------------------------

def _attention_xla(q, k, v, mask=None, causal=False, dropout_p=0.0, dropout_key=None):
    """Reference XLA attention, differentiable; [B,S,H,D] layout."""
    d = q.shape[-1]
    scale = 1.0 / np.sqrt(d)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        mask_c = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask_c[None, None], s, _NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            s = jnp.where(mask, s, _NEG_INF)
        else:
            s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return out


# kept as a module-level alias so older call sites keep working; the policy
# (including the PADDLE_TPU_FORCE_PALLAS_INTERPRET CI override) lives in
# _backend.py, shared with the ragged paged-attention kernel
_use_pallas = use_pallas


# ---------------------------------------------------------------------------
# shared in-kernel score/mask/dropout logic
# ---------------------------------------------------------------------------

def _tile_scores(q, kt, qi, kj, *, scale, causal, off, bq, bk, mask_tile):
    """s tile (bq, bk) in f32 with scaling + causal (bottom-right) + additive
    mask applied. Inputs stay in their storage dtype (bf16 on TPU): the MXU's
    fast path is low-precision multiply with f32 accumulation
    (preferred_element_type) — upcasting inputs first would force full-f32
    multiplies at a fraction of peak."""
    s = jax.lax.dot_general(
        q, kt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos + off >= kpos, s, _NEG_INF)
    if mask_tile is not None:
        s = s + mask_tile.astype(jnp.float32)
    return s


def _tile_keep(seed_ref, i, qi, kj, nq, nk, shape, dropout_p):
    """Deterministic per-tile keep mask from the kernel PRNG — regenerated
    identically in forward and backward."""
    from jax.experimental.pallas import tpu as pltpu

    pltpu.prng_seed(seed_ref[0] + ((i * nq + qi) * nk + kj))
    bits = pltpu.prng_random_bits(shape)  # uint32
    threshold = np.uint32(int(dropout_p * float(2**32 - 1)))
    return bits.astype(jnp.uint32) >= threshold


def _causal_live(qi, kj, *, bq, bk, off):
    """Whether this (q, k) tile intersects the bottom-right causal region."""
    return (qi * bq + bq - 1 + off) >= (kj * bk)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *,
                scale, causal, off, bq, bk, dropout_p, has_mask):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nq = pl.num_programs(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    live = _causal_live(qi, kj, bq=bq, bk=bk, off=off) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0]
        kt = k_ref[0]
        mask_tile = mask_ref[0] if has_mask else None
        s = _tile_scores(q, kt, qi, kj, scale=scale, causal=causal, off=off,
                         bq=bq, bk=bk, mask_tile=mask_tile)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if dropout_p > 0.0:
            keep = _tile_keep(seed_ref, pl.program_id(0), qi, kj, nq, nk,
                              p.shape, dropout_p)
            p_use = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
        else:
            p_use = p
        alpha = jnp.exp(m_prev - m_new)
        # l tracks the TRUE softmax normalizer (pre-dropout p)
        l_ref[:] = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        vt = v_ref[0]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p_use.astype(vt.dtype), vt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new

    @pl.when(kj == nk - 1)
    def _():
        l = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)
        # lse layout (bh, 8, sq): 8 sublanes satisfy the TPU (8,128) block
        # tiling rule; all rows carry the same value
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:] + jnp.log(l))[:, 0][None, :], lse_ref.shape[1:]
        )


@functools.lru_cache(maxsize=None)
def _build_fwd(causal, bq, bk, dropout_p, has_mask, mask_b, mask_h, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def fwd(q, k, v, mask, seed):  # q [BH,Sq,D], k/v [BH,Sk,D], mask [B*H|1,Sq,Sk]
        bh, sq, d = q.shape
        sk = k.shape[1]
        scale = 1.0 / np.sqrt(d)
        off = sk - sq
        nq, nk = sq // bq, sk // bk
        base = functools.partial(
            _fwd_kernel, scale=scale, causal=causal, off=off, bq=bq, bk=bk,
            dropout_p=dropout_p, has_mask=has_mask,
        )
        if has_mask:
            kern = base
        else:
            def kern(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, a, m, l):
                return base(seed_ref, q_ref, k_ref, v_ref, None, o_ref, lse_ref, a, m, l)
        in_specs = [
            pl.BlockSpec(memory_space=pltpu.SMEM),  # seed
            pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, t: (i, t, 0)),
        ]
        if has_mask:
            in_specs.append(
                pl.BlockSpec(
                    (1, bq, bk),
                    lambda i, j, t: (0 if mask_b == 1 and mask_h == 1 else i, j, t),
                )
            )
        o, lse = pl.pallas_call(
            kern,
            out_shape=(
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, 8, sq), jnp.float32),
            ),
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=(
                pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0)),
                pl.BlockSpec((1, 8, bq), lambda i, j, t: (i, 0, j)),
            ),
            scratch_shapes=[
                pltpu.VMEM((bq, d), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
                pltpu.VMEM((bq, 1), jnp.float32),
            ],
            interpret=interpret,
            name="flash_fwd",
        )(seed, q, k, v, *([mask] if has_mask else []))
        return o, lse

    return fwd


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                mask_ref, dk_ref, dv_ref, dka_ref, dva_ref, *,
                scale, causal, off, bq, bk, dropout_p, has_mask):
    from jax.experimental import pallas as pl

    kj = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dka_ref[:] = jnp.zeros_like(dka_ref)
        dva_ref[:] = jnp.zeros_like(dva_ref)

    live = _causal_live(qi, kj, bq=bq, bk=bk, off=off) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0]
        kt = k_ref[0]
        vt = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        mask_tile = mask_ref[0] if has_mask else None
        s = _tile_scores(q, kt, qi, kj, scale=scale, causal=causal, off=off,
                         bq=bq, bk=bk, mask_tile=mask_tile)
        p = jnp.exp(s - lse)  # true softmax probabilities
        dp = jax.lax.dot_general(  # dO @ V^T
            do, vt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_p > 0.0:
            keep = _tile_keep(seed_ref, pl.program_id(0), qi, kj, nq, nk,
                              p.shape, dropout_p)
            dscale = jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
            dv_p = p * dscale
            dp = dp * dscale
        else:
            dv_p = p
        # dV += (D o P)^T @ dO
        dva_ref[:] += jax.lax.dot_general(
            dv_p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        dka_ref[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dka_ref[:].astype(dk_ref.dtype)
        dv_ref[0] = dva_ref[:].astype(dv_ref.dtype)


def _dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               mask_ref, dq_ref, dqa_ref, *,
               scale, causal, off, bq, bk, dropout_p, has_mask):
    from jax.experimental import pallas as pl

    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nq = pl.num_programs(1)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _():
        dqa_ref[:] = jnp.zeros_like(dqa_ref)

    live = _causal_live(qi, kj, bq=bq, bk=bk, off=off) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0]
        kt = k_ref[0]
        vt = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        mask_tile = mask_ref[0] if has_mask else None
        s = _tile_scores(q, kt, qi, kj, scale=scale, causal=causal, off=off,
                         bq=bq, bk=bk, mask_tile=mask_tile)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, vt, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_p > 0.0:
            keep = _tile_keep(seed_ref, pl.program_id(0), qi, kj, nq, nk,
                              p.shape, dropout_p)
            dp = dp * jnp.where(keep, 1.0 / (1.0 - dropout_p), 0.0)
        ds = p * (dp - delta) * scale
        dqa_ref[:] += jax.lax.dot_general(
            ds.astype(kt.dtype), kt, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _():
        dq_ref[0] = dqa_ref[:].astype(dq_ref.dtype)


@functools.lru_cache(maxsize=None)
def _build_bwd(causal, bq, bk, dropout_p, has_mask, mask_b, mask_h, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def bwd(q, k, v, do, o, lse, mask, seed):
        bh, sq, d = q.shape
        sk = k.shape[1]
        scale = 1.0 / np.sqrt(d)
        off = sk - sq
        nq, nk = sq // bq, sk // bk
        delta2d = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        delta = jnp.broadcast_to(delta2d[:, None, :], (bh, 8, sq))

        common = dict(scale=scale, causal=causal, off=off, bq=bq, bk=bk,
                      dropout_p=dropout_p, has_mask=has_mask)
        mask_map_kq = (
            lambda i, t, j: (0 if mask_b == 1 and mask_h == 1 else i, j, t)
        )
        mask_map_qk = (
            lambda i, j, t: (0 if mask_b == 1 and mask_h == 1 else i, j, t)
        )

        seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
        dkv_in = [
            seed_spec,
            pl.BlockSpec((1, bq, d), lambda i, t, j: (i, j, 0)),   # q by inner j
            pl.BlockSpec((1, bk, d), lambda i, t, j: (i, t, 0)),   # k by outer t
            pl.BlockSpec((1, bk, d), lambda i, t, j: (i, t, 0)),
            pl.BlockSpec((1, bq, d), lambda i, t, j: (i, j, 0)),   # do
            pl.BlockSpec((1, 8, bq), lambda i, t, j: (i, 0, j)),   # lse
            pl.BlockSpec((1, 8, bq), lambda i, t, j: (i, 0, j)),   # delta
        ]
        if has_mask:
            dkv_in.append(pl.BlockSpec((1, bq, bk), mask_map_kq))
        dkv_base = functools.partial(_dkv_kernel, **common)
        if has_mask:
            dkv_kern = dkv_base
        else:
            def dkv_kern(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dk_ref, dv_ref, dka, dva):
                return dkv_base(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                                delta_ref, None, dk_ref, dv_ref, dka, dva)
        dk, dv = pl.pallas_call(
            dkv_kern,
            out_shape=(
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ),
            grid=(bh, nk, nq),
            in_specs=dkv_in,
            out_specs=(
                pl.BlockSpec((1, bk, d), lambda i, t, j: (i, t, 0)),
                pl.BlockSpec((1, bk, d), lambda i, t, j: (i, t, 0)),
            ),
            scratch_shapes=[
                pltpu.VMEM((bk, d), jnp.float32),
                pltpu.VMEM((bk, d), jnp.float32),
            ],
            interpret=interpret,
            name="flash_bwd_dkv",
        )(seed, q, k, v, do, lse, delta, *([mask] if has_mask else []))

        dq_in = [
            seed_spec,
            pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, t: (i, t, 0)),
            pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, 8, bq), lambda i, j, t: (i, 0, j)),
            pl.BlockSpec((1, 8, bq), lambda i, j, t: (i, 0, j)),
        ]
        if has_mask:
            dq_in.append(pl.BlockSpec((1, bq, bk), mask_map_qk))
        dq_base = functools.partial(_dq_kernel, **common)
        if has_mask:
            dq_kern = dq_base
        else:
            def dq_kern(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                        delta_ref, dq_ref, dqa):
                return dq_base(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                               delta_ref, None, dq_ref, dqa)
        dq = pl.pallas_call(
            dq_kern,
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            grid=(bh, nq, nk),
            in_specs=dq_in,
            out_specs=pl.BlockSpec((1, bq, d), lambda i, j, t: (i, j, 0)),
            scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
            interpret=interpret,
            name="flash_bwd_dq",
        )(seed, q, k, v, do, lse, delta, *([mask] if has_mask else []))
        return dq, dk, dv

    return bwd


# ---------------------------------------------------------------------------
# dispatch + custom vjp
# ---------------------------------------------------------------------------

# The device plane names a Pallas call after the last scope in front of it,
# which is the call's `name=` (`flash_fwd`, `flash_bwd_dkv`, `flash_bwd_dq`).
# A transform wraps the first scope inside it: under `jax.grad` alone the
# names would read `jvp_flash_fwd_` and `transpose_jvp_flash_bwd_dq__`. This
# scope stands in front and takes the wrapping, so a kernel has one name
# whatever differentiates, rematerialises or maps the call.
_SCOPE = "flash_attention"


def _bshd_to_bhsd(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _bhsd_to_bshd(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.lru_cache(maxsize=None)
def _flash_custom(causal, bq, bk, dropout_p, has_mask, mask_b, mask_h, interpret):
    fwd_call = _build_fwd(causal, bq, bk, dropout_p, has_mask, mask_b, mask_h, interpret)
    bwd_call = _build_bwd(causal, bq, bk, dropout_p, has_mask, mask_b, mask_h, interpret)

    @jax.custom_vjp
    def flash(q, k, v, mask, seed):  # [B,S,H,D]
        return _fwd(q, k, v, mask, seed)[0]

    def _fwd(q, k, v, mask, seed):
        b, sq, h, d = q.shape
        qf, kf, vf = _bshd_to_bhsd(q), _bshd_to_bhsd(k), _bshd_to_bhsd(v)
        mf = mask.reshape((-1,) + mask.shape[2:]) if has_mask else jnp.zeros((), jnp.float32)
        with jax.named_scope(_SCOPE):
            of, lse = fwd_call(qf, kf, vf, mf, seed)
        return _bhsd_to_bshd(of, b, h), (qf, kf, vf, of, lse, mf, seed, b, h)

    def fwd(q, k, v, mask, seed):
        o, res = _fwd(q, k, v, mask, seed)
        return o, res

    def bwd(res, g):
        qf, kf, vf, of, lse, mf, seed, b, h = res
        gf = _bshd_to_bhsd(g)
        with jax.named_scope(_SCOPE):
            dqf, dkf, dvf = bwd_call(qf, kf, vf, gf, of, lse, mf, seed)
        dq = _bhsd_to_bshd(dqf, b, h)
        dk = _bhsd_to_bshd(dkf, b, h)
        dv = _bhsd_to_bshd(dvf, b, h)
        dmask = None
        if has_mask:
            # d loss/d mask = p * (dp - delta), recomputed in plain XLA from
            # the saved lse (no extra softmax pass). XLA dead-code-eliminates
            # this whole block whenever the mask cotangent is unused, so
            # non-trainable masks pay nothing; trainable additive biases
            # (e.g. relative-position bias) get exact gradients. dropout>0
            # never reaches here (dispatch falls back to XLA for mask+dropout
            # since the in-kernel PRNG stream is not reproducible outside).
            sq, sk = qf.shape[1], kf.shape[1]
            d = qf.shape[2]
            scale = 1.0 / np.sqrt(d)
            s = jax.lax.dot_general(
                qf.astype(jnp.float32), kf.astype(jnp.float32),
                (((2,), (2,)), ((0,), (0,))),
            ) * scale
            if causal:
                mask_c = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
                s = jnp.where(mask_c[None], s, _NEG_INF)
            s = s + mf.astype(jnp.float32)
            p = jnp.exp(s - lse[:, 0, :][:, :, None])
            dp = jax.lax.dot_general(
                gf.astype(jnp.float32), vf.astype(jnp.float32),
                (((2,), (2,)), ((0,), (0,))),
            )
            delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32), -1)
            dsm = (p * (dp - delta[:, :, None])).reshape(b, h, sq, sk)
            # reduce over whichever dims the mask broadcasts (b==1 keeps
            # (1,H,...) masks possible when the batch itself is 1)
            axes = ()
            if mask_b == 1:
                axes += (0,)
            if mask_h == 1:
                axes += (1,)
            dmask = dsm.sum(axis=axes, keepdims=True) if axes else dsm
        return dq, dk, dv, dmask, None

    flash.defvjp(fwd, bwd)
    return flash


def flash_attention_array(
    q, k, v, mask=None, causal=False, dropout_p=0.0, dropout_key=None,
    block_q=None, block_k=None,
):
    """Dispatch: Pallas kernels on TPU (streamed K/V, fused mask/dropout,
    Pallas backward); XLA fallback elsewhere or for unsupported shapes.
    Tile sizes default to FLAGS_pallas_block_q/k (tunable per chip)."""
    if block_q is None or block_k is None:
        from ...flags import flag as _flag

        block_q = block_q or _flag("FLAGS_pallas_block_q")
        block_k = block_k or _flag("FLAGS_pallas_block_k")
    sq, sk = q.shape[1], k.shape[1]

    def _fit_block(b, s):
        # largest power-halving of the requested tile that divides the
        # sequence, so odd-length-but-divisible shapes keep the kernel
        # instead of silently dropping to the XLA fallback
        b = min(b, s)
        while b > 8 and s % b:
            b //= 2
        return b

    bq = _fit_block(block_q, sq)
    bk = _fit_block(block_k, sk)
    # why this call cannot take the kernels (None = it can). Off-TPU the
    # XLA path is the default; on a tpu backend note_xla_fallback counts
    # and logs the reason, so no shape drops to XLA unseen.
    why_xla = None
    mf = None
    if mask is not None:
        # additive float masks broadcastable over batch/head stream through
        # the kernel; bool masks fall back
        if mask.dtype == jnp.bool_ or mask.ndim != 4:
            why_xla = f"mask dtype {mask.dtype} ndim {mask.ndim}"
        elif mask.shape[2] != sq or mask.shape[3] != sk:
            why_xla = f"mask shape {mask.shape} for sq={sq} sk={sk}"
        elif not (
            (mask.shape[0] in (1, q.shape[0]))
            and (mask.shape[1] in (1, q.shape[2]))
        ):
            why_xla = f"mask shape {mask.shape} does not broadcast"
        elif (mask.shape[0] == 1) != (mask.shape[1] == 1):
            # mixed broadcast (e.g. [B,1,Sq,Sk]) — materialize over heads
            mf = jnp.broadcast_to(mask, (q.shape[0], q.shape[2], sq, sk))
        else:
            mf = mask
    if dropout_p > 0.0 and mask is not None:
        # mask gradients require recomputing ds outside the kernel, which is
        # impossible with the in-kernel dropout PRNG — keep semantics uniform
        # by using the XLA path for the (rare) mask+dropout combination
        why_xla = "mask with dropout"
    elif dropout_p > 0.0 and dropout_key is None:
        why_xla = "dropout without a key"
    if why_xla is None and (sq % bq or sk % bk):
        why_xla = f"sq={sq} sk={sk} do not divide into tiles {bq}x{bk}"
    if why_xla is None and not _use_pallas():
        why_xla = "kernels off"
    interpret = interpret_mode()
    if why_xla is None and dropout_p > 0.0 and interpret:
        why_xla = "TPU PRNG primitives are unavailable in interpreter mode"
    if why_xla is not None:
        note_xla_fallback("flash_attention", why_xla)
        return _attention_xla(q, k, v, mask, causal, dropout_p, dropout_key)
    has_mask = mf is not None
    mb = mf.shape[0] if has_mask else 0
    mh = mf.shape[1] if has_mask else 0
    seed = (
        jax.random.randint(dropout_key, (1,), 0, np.int32(2**31 - 1), dtype=jnp.int32)
        if dropout_p > 0.0 else jnp.zeros((1,), jnp.int32)
    )
    fn = _flash_custom(causal, bq, bk, float(dropout_p), has_mask, mb, mh, interpret)
    return fn(q, k, v, mf if has_mask else None, seed)
