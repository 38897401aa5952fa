"""A hybrid decoder-only LM: Mamba-2 mixers, grouped-query attention and a
latent mixture of experts in one stack.

The block NVIDIA publishes as Nemotron-H / Nemotron-3 (`model_type`
`nemotron_h`). The stream `h` `[tokens, hidden]`; every layer is ONE part
behind one RMSNorm, `h = h + part(RMSNorm(h; g, eps))`, the part chosen by
the layer's character in `pattern` (the published
`hybrid_override_pattern`); then a final RMSNorm and an untied head. No
bias anywhere but the convolution's; no learned positions and no rotary
embedding (the state-space layers carry position).

- `M`, Mamba-2 mixer. `in_proj` hidden -> `z` (inner) | `xBC` (inner +
  2 x groups x state) | `dt` (heads). `xBC` = SiLU(causal depthwise
  convolution over the last `conv_kernel` tokens, with bias); split into
  `x` `[heads, head_dim]`, `B` and `C` `[groups, state]`; head i reads group
  `i // (heads / groups)`. `dt = softplus(dt + dt_bias)`, `A = -exp(A_log)`
  a number a head. State a head `S` `[head_dim, state]`:
  `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`; `y_t = S_t C_t + D x_t`.
  Then the gated norm by group, `RMSNorm_group(y * SiLU(z))` with a gain of
  the inner width, and `out_proj` inner -> hidden.
- `*`, attention: `q` hidden -> heads x d, `k`, `v` hidden -> kv_heads x d
  each, causal softmax(q k^T / sqrt(d)) v, query head i reads K/V head
  `i // (heads / kv_heads)`, `o` heads x d -> hidden.
- `E`, latent mixture of experts. Router in float32 from the normed stream:
  `s = sigmoid(a W_r)`, `W_r` `[hidden, router_experts]`; the
  `experts_per_tok` experts with the largest `s + b` (`b`, the published
  `e_score_correction_bias`, chooses only); weights
  `w_e = routed_scaling x s_e / sum of the chosen s`. `u = a W_down`
  (hidden -> latent). Expert e: `f_e(u) = relu(u W1_e)^2 W2_e` (no gate
  matrix). Routed part `(sum_e w_e f_e(u)) W_up` (latent -> hidden). Shared
  expert on the full width: `relu(a V1)^2 V2`. Output routed + shared.

THE CHIP'S SHARE (expert parallelism without its exchange; router and
dispatch are models/experts.py, shared with models/dots3.py). The layer holds
experts `[experts_from, experts_from + experts_held)` of `router_experts`.
The router keeps its whole width, its picks and the normalisation over all
of them; the sum over e runs over the picks that are held here; what the
absent experts would have added is left out, and that partial result goes
on to the next layer. `W_down`, `W_up`, the router and the shared expert are
computed whole, as every chip of the group would. No code stands in for the
other chips. The expert layer is dropless: every held pick of a live token
is computed whatever the imbalance (tokens sorted by held expert, a grouped
product over the stacked expert weights: `ops/pallas/grouped_matmul.py`, the
kernel `grouped_matmul` where the kernels are on and the expert's widths are
multiples of 128, `jax.lax.ragged_dot` elsewhere); slots that carry no token
(past a row's live tokens, idle lanes) are not dispatched.

SERVING (`caches` a paged state, serving/block_pool.py). Two kinds of state:
the attention layers' keys and values in the paged arena (`kv_slabs` = the
attention layers, `kv_heads` heads), and a state a lane for every Mamba
layer, which the pool owns beside the arenas (`lane_state_spec`): `ssm`
`[ssm layers, lanes, heads, head_dim, state]` float32 and `conv`
`[ssm layers, lanes, conv_kernel - 1, channels]` in the weights' type (the
convolution's last inputs). A step of width W is one chunk of the chunked
scan, matrix products throughout:

    y = ((C B^T) o L) (dt x) + C (decay S_in) + D x
    S_out = decay_all S_in + sum_t decay_t dt_t x_t B_t^T

with `L[t, s] = exp(sum_{s < r <= t} dt_r A)` below the diagonal; width 1 is
the same with one token. Slots past a row's live tokens and lanes without a
request get `dt = 0` (decay 1, nothing added) and leave the convolution's
window alone, so the state a row carries is a function of its live tokens
only. A row flagged `fresh` (its first chunk, or its first after
preemption) starts from zeros. The decode group (one token a row, row i
lane i) updates its lanes' state where it lies: the kernel
`ssm_state_update` (`ops/pallas/ssm_state_update.py`, where the kernels are
on and the state is float32 with a state size that is a multiple of 128)
reads a layer of the leaf once and writes the new state back into it; a
chunk group gathers its rows' lanes of the leaf and scatters them back. The
plain path (`caches` None) runs the same chunk form over the whole
sequence, `chunk_size` tokens at a time.

Precision. Weights in the type they are given (bfloat16 when served);
matrix products take operands in that type and accumulate in float32; norms,
the router (input, product at `highest`, sigmoid), `dt`, the decays and the
recurrent state are float32. Why the state is float32: it is summed over up
to a thousand tokens at decays near 1, and a bfloat16 state would round
away what each token adds (tests/test_nemotron_h_model.py shows it failing
the comparison). The residual stream is float32 too (`RESIDUAL_DTYPE`; the
published model keeps it in the weights' type).

Left out: the multi-token-prediction module (a drafter beside the model);
`rope_theta` / `partial_rotary_factor` (read by nothing). Training this
block is not wired (no tape is recorded).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..nn import initializer as I
from ..ops.pallas import ssm_state_update as state_update
from ..ops.pallas._backend import note_xla_fallback
from ..ops.pallas.grouped_matmul import grouped_matmul
from .experts import (held_expert_sum, pair_counts, sigmoid_route,
                      sum_step_stats)
from .looped import _Gain, _Matrix, causal_attention, rms_norm
from .looped import project as _project

# the residual stream's type: float32 under bf16 weights, as models/looped.py
# keeps it (the published model states `residual_in_fp32: false`). What it
# buys here is fewer exchanged picks: the router's choice is discrete, and
# every rounding of the stream in front of it exchanges the 22nd pick for the
# 23rd a little more often (PERF.md section 6, PR 36)
RESIDUAL_DTYPE = jnp.float32
# the recurrent state's type; a test turns it to bfloat16 to show that its
# comparison would catch that
SSM_STATE_DTYPE = jnp.float32
# (the router's type, float32 too, is models/experts.py ROUTER_DTYPE, which
# this block shares with models/dots3.py; same kind of test)

# what a step of a model with expert layers and lane state reports, in the
# order of the vector `forward` hands to `caches.step_stats` (the engine
# appends it to the one packed result the host reads, serving/engine.py)
STEP_STATS = ("moe_pairs", "moe_pairs_local", "moe_experts_hit",
              "moe_max_load", "state_rows")


class NemotronHConfig:
    def __init__(self, vocab_size=131072, hidden_size=4096,
                 pattern="MEMEMEMEM*E", num_heads=32, num_kv_heads=2,
                 head_dim=128, mamba_num_heads=128, mamba_head_dim=64,
                 ssm_state_size=128, n_groups=8, conv_kernel=4,
                 chunk_size=128, moe_latent_size=1024,
                 moe_intermediate_size=2688,
                 shared_intermediate_size=5376, router_experts=512,
                 experts_held=None, experts_from=0, experts_per_tok=22,
                 routed_scaling=5.0, norm_topk_prob=True, norm_eps=1e-5,
                 max_seq_len=2048):
        if set(pattern) - set("ME*"):
            raise ValueError(f"pattern {pattern!r}: layers are M, E or *")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = pattern
        self.num_layers = len(pattern)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.moe_latent_size = moe_latent_size
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.router_experts = router_experts
        self.experts_held = (router_experts if experts_held is None
                             else experts_held)
        self.experts_from = experts_from
        if not 0 <= experts_from <= router_experts - self.experts_held:
            raise ValueError(
                f"experts [{experts_from}, {experts_from + self.experts_held}"
                f") do not lie within the router's {router_experts}")
        self.experts_per_tok = experts_per_tok
        self.routed_scaling = float(routed_scaling)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.norm_eps = float(norm_eps)
        self.max_seq_len = max_seq_len

    @property
    def mamba_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_channels(self):
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size


def ssm_chunk(x, B, C, dt, A, S_in):
    """One chunk of the Mamba-2 scan, every row its own sequence.

    x `[b, w, heads, p]`, B and C `[b, w, groups, n]`, dt `[b, w, heads]`
    (after softplus; 0 where a slot carries no token), A `[heads]`, S_in
    `[b, heads, p, n]`: all float32. Returns `(y [b, w, heads, p], S_out)`
    without the `D x` term. Matrix products throughout; width 1 is the
    recurrence itself."""
    b, w, heads, _ = x.shape
    groups = B.shape[2]
    a = dt * A                                            # [b, w, heads] <= 0
    cs = jnp.cumsum(a, axis=1)
    # L[t, s] = exp(cs_t - cs_s) for s <= t: the decay from after token s
    # to token t
    diff = cs[:, :, None, :] - cs[:, None, :, :]          # [b, t, s, heads]
    causal = (jnp.arange(w)[:, None] >= jnp.arange(w)[None, :])[None, :, :,
                                                               None]
    L = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    CB = jnp.einsum("btgn,bsgn->btsg", C, B)              # [b, t, s, groups]
    M = jnp.repeat(CB, heads // groups, axis=3) * L       # [b, t, s, heads]
    dtx = dt[..., None] * x                               # [b, w, heads, p]
    y = jnp.einsum("btsh,bshp->bthp", M, dtx)
    # what the state the chunk started from adds: C_t (decay to t) S_in
    Ch = jnp.repeat(C, heads // groups, axis=2)           # [b, w, heads, n]
    y = y + jnp.einsum("bthn,bhpn->bthp", Ch * jnp.exp(cs)[..., None], S_in)
    # the state after the chunk
    to_end = jnp.exp(cs[:, -1:, :] - cs)                  # [b, w, heads]
    Bh = jnp.repeat(B, heads // groups, axis=2)
    S_out = (jnp.exp(cs[:, -1, :])[:, :, None, None] * S_in
             + jnp.einsum("bshp,bshn->bhpn", dtx * to_end[..., None], Bh))
    return y, S_out


class MambaMixer(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, inner, heads = cfg.hidden_size, cfg.mamba_inner, cfg.mamba_num_heads
        self.in_proj = _Matrix(H, inner + cfg.conv_channels + heads,
                               (None, None))
        self.conv = nn.Layer()
        # [kernel, channels]: row k multiplies the input k - (kernel - 1)
        # tokens back, so the last row is the current token's tap
        self.conv.weight = self.create_parameter(
            [cfg.conv_kernel, cfg.conv_channels],
            default_initializer=I.Normal(0.0, 0.02))
        self.conv.bias = self.create_parameter(
            [cfg.conv_channels], is_bias=True)
        self.dt_bias = self.create_parameter(
            [heads], default_initializer=I.Constant(0.0))
        self.A_log = self.create_parameter(
            [heads], default_initializer=I.Constant(0.0))
        self.D = self.create_parameter(
            [heads], default_initializer=I.Constant(1.0))
        self.gate_norm = _Gain(inner)
        self.out_proj = _Matrix(inner, H, (None, None))

    def forward(self, a, S_in, window, live, n_live):
        """`a` `[b, w, hidden]` the normed stream; `S_in` `[b, heads, p, n]`
        and `window` `[b, kernel - 1, channels]` the state the rows carry;
        `live` `[b, w]` which slots hold a token (a prefix of each row) and
        `n_live` `[b]` how many. Returns `(out [b, w, hidden], S_out,
        window_out)`."""
        z, x, B, C, dt, window_out = self.mix_in(a, window, live, n_live)
        y, S_out = ssm_chunk(x, B, C, dt, self.decay_rate(),
                             S_in.astype(jnp.float32))
        return self.mix_out(y, x, z), S_out.astype(S_in.dtype), window_out

    def mix_in(self, a, window, live, n_live):
        """What the scan reads, from the stream and the convolution's
        window (arguments as `forward`'s): `(z [b, w, inner], x [b, w,
        heads, p], B, C [b, w, groups, n], dt [b, w, heads], window_out)`,
        `x` and `dt` 0 in the slots that hold no token."""
        cfg = self.cfg
        b, w, _ = a.shape
        inner, heads, groups = (cfg.mamba_inner, cfg.mamba_num_heads,
                                cfg.n_groups)
        P, N, K = cfg.mamba_head_dim, cfg.ssm_state_size, cfg.conv_kernel
        z, xBC, dt = jnp.split(_project(a, self.in_proj),
                               [inner, inner + cfg.conv_channels], axis=-1)
        # a token's convolution input is kept in the window's type from the
        # start, so it is the same number whether it is read in its own
        # chunk or from the window in the next
        xBC = xBC.astype(window.dtype)
        seq = jnp.concatenate([window, xBC], axis=1)      # [b, K - 1 + w, c]
        conv_w = self.conv.weight._array.astype(jnp.float32)
        conv = sum(seq[:, k:k + w].astype(jnp.float32) * conv_w[k]
                   for k in range(K))
        xBC = jax.nn.silu(conv + self.conv.bias._array.astype(jnp.float32))
        # the window after the row's live tokens: the K - 1 inputs that end
        # at its last one (none live: the window as it was)
        window_out = jax.vmap(
            lambda s, n: jax.lax.dynamic_slice_in_dim(s, n, K - 1, 0)
        )(seq, n_live)
        x, B, C = jnp.split(xBC, [inner, inner + groups * N], axis=-1)
        x = jnp.where(live[..., None], x, 0.0).reshape(b, w, heads, P)
        B = B.reshape(b, w, groups, N)
        C = C.reshape(b, w, groups, N)
        dt = jax.nn.softplus(dt + self.dt_bias._array.astype(jnp.float32))
        dt = jnp.where(live[..., None], dt, 0.0)
        return z, x, B, C, dt, window_out

    def decay_rate(self):
        """`A` `[heads]` float32: a head's state decays by `exp(dt A)`."""
        return -jnp.exp(self.A_log._array.astype(jnp.float32))

    def mix_out(self, y, x, z):
        """The layer's output `[b, w, hidden]` from the scan's `y` `[b, w,
        heads, p]` and `mix_in`'s `x` and `z`: `D x`, the gated norm by
        group and `out_proj`."""
        cfg = self.cfg
        b, w = z.shape[:2]
        inner, groups = cfg.mamba_inner, cfg.n_groups
        y = y + self.D._array.astype(jnp.float32)[:, None] * x
        g = (y.reshape(b, w, inner) * jax.nn.silu(z)).reshape(
            b, w, groups, inner // groups)
        g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.norm_eps)
        g = g.reshape(b, w, inner) \
            * self.gate_norm.weight._array.astype(jnp.float32)
        return _project(g, self.out_proj)


class GroupedQueryAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, d = cfg.hidden_size, cfg.head_dim
        # Wq, Wk, Wv side by side, as models/looped.py keeps them
        self.qkv = _Matrix(H, (cfg.num_heads + 2 * cfg.num_kv_heads) * d,
                           (None, None))
        self.o = _Matrix(cfg.num_heads * d, H, (None, None))

    def forward(self, a, cache=None):
        cfg = self.cfg
        b, s, _ = a.shape
        heads, kv, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        kv_type = self.qkv.weight._array.dtype
        q, k, v = jnp.split(_project(a, self.qkv),
                            [heads * d, (heads + kv) * d], axis=-1)
        q = q.reshape(b, s, heads, d).astype(kv_type)
        k = k.reshape(b, s, kv, d).astype(kv_type)
        v = v.reshape(b, s, kv, d).astype(kv_type)
        if cache is None:
            mixed = causal_attention(q, jnp.repeat(k, heads // kv, axis=2),
                                     jnp.repeat(v, heads // kv, axis=2))
        else:
            mixed = cache.attend(Tensor._from_op(q), Tensor._from_op(k),
                                 Tensor._from_op(v))._array
        return _project(mixed.reshape(b, s, heads * d), self.o)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


class LatentMoE(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        H, Z, F = (cfg.hidden_size, cfg.moe_latent_size,
                   cfg.moe_intermediate_size)
        self.router = nn.Layer()
        self.router.weight = self.create_parameter(
            [H, cfg.router_experts], default_initializer=I.Normal(0.0, 0.02))
        # the published `e_score_correction_bias`: chooses, never weighs
        self.router.bias = self.create_parameter(
            [cfg.router_experts], is_bias=True)
        self.latent_down = _Matrix(H, Z, (None, None))
        self.latent_up = _Matrix(Z, H, (None, None))
        self.experts = nn.Layer()
        self.experts.w1 = self.create_parameter(
            [cfg.experts_held, Z, F], default_initializer=I.Normal(0.0, 0.02))
        self.experts.w2 = self.create_parameter(
            [cfg.experts_held, F, Z], default_initializer=I.Normal(0.0, 0.02))
        self.shared = nn.Layer()
        self.shared.v1 = _Matrix(H, cfg.shared_intermediate_size,
                                 (None, None))
        self.shared.v2 = _Matrix(cfg.shared_intermediate_size, H,
                                 (None, None))

    def route(self, a):
        """`(picks [T, k], weights [T, k])` for the normed stream `a`
        `[T, hidden]`, over the router's whole width."""
        cfg = self.cfg
        return sigmoid_route(
            a, self.router.weight._array, self.router.bias._array,
            cfg.experts_per_tok, cfg.routed_scaling, cfg.norm_topk_prob)

    def forward(self, a, live):
        """`a` `[T, hidden]` float32, `live` `[T]` which slots hold a token.
        Returns `(out [T, hidden] float32, stats)`; `stats` are int32
        scalars `(pairs, pairs_local, experts_hit, max_load)`."""
        cfg = self.cfg
        with jax.named_scope("moe_route"):
            picks, weights = self.route(a)
        with jax.named_scope("moe_experts"):
            w1, w2 = self.experts.w1._array, self.experts.w2._array

            def expert(x, sizes):
                hidden = grouped_matmul(x, w1, sizes)
                return grouped_matmul(_relu2(hidden).astype(w2.dtype), w2,
                                      sizes)

            u = _project(a, self.latent_down).astype(w1.dtype)
            routed, local, sizes = held_expert_sum(
                u, picks, weights, live, cfg.experts_from, cfg.experts_held,
                expert)
            routed = _project(routed, self.latent_up)
        with jax.named_scope("moe_shared"):
            shared = _project(_relu2(_project(a, self.shared.v1)),
                              self.shared.v2)
        stats = pair_counts(live, local, sizes)
        return routed + shared, stats


class NemotronHLayer(nn.Layer):
    def __init__(self, cfg, kind):
        super().__init__()
        self.kind = kind
        self.norm = _Gain(cfg.hidden_size)
        if kind == "M":
            self.mixer = MambaMixer(cfg)
        elif kind == "*":
            self.attn = GroupedQueryAttention(cfg)
        else:
            self.moe = LatentMoE(cfg)


class NemotronH(nn.Layer):
    # which of `LLMEngine`'s model-shaped options this block takes: none of
    # "tp", "quantize", "lora" (engine.py `_refuse_option`)
    serving_options = frozenset()
    step_stat_names = STEP_STATS

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed = nn.Embedding(cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList(
            [NemotronHLayer(cfg, kind) for kind in cfg.pattern])
        self.norm_f = _Gain(cfg.hidden_size)
        self.head = nn.Embedding(cfg.vocab_size, cfg.hidden_size)

    # -- what the serving pool is to hold for this model -------------------

    @property
    def kv_slabs(self):
        """Cache slabs a token's keys and values take: one an ATTENTION
        layer (the other layers keep none)."""
        return max(1, self.cfg.pattern.count("*"))

    @property
    def kv_heads(self):
        return self.cfg.num_kv_heads

    def lane_state_spec(self):
        """State a lane that is not addressed by position: `{name: (shape
        without the lane axis, dtype or None for the weights')}`; the lane
        axis goes in second (serving/block_pool.py)."""
        cfg, n = self.cfg, self.cfg.pattern.count("M")
        if not n:
            return {}
        return {
            "ssm": ((n, cfg.mamba_num_heads, cfg.mamba_head_dim,
                     cfg.ssm_state_size), SSM_STATE_DTYPE),
            "conv": ((n, cfg.conv_kernel - 1, cfg.conv_channels), None),
        }

    def serving_gauges(self):
        return {"experts_held": self.cfg.experts_held,
                "experts_total": self.cfg.router_experts}

    # ----------------------------------------------------------------------

    def _mamba_plain(self, mixer, a):
        """A Mamba layer over whole sequences `a` `[b, s, hidden]`, from
        zeros, `chunk_size` tokens at a time through the serving form."""
        cfg = self.cfg
        b, s, _ = a.shape
        S = jnp.zeros((b, cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), SSM_STATE_DTYPE)
        window = jnp.zeros((b, cfg.conv_kernel - 1, cfg.conv_channels),
                           mixer.in_proj.weight._array.dtype)
        outs = []
        for t0 in range(0, s, cfg.chunk_size):
            w = min(cfg.chunk_size, s - t0)
            out, S, window = mixer(
                a[:, t0:t0 + w], S, window, jnp.ones((b, w), bool),
                jnp.full((b,), w, jnp.int32))
            outs.append(out)
        return jnp.concatenate(outs, axis=1)

    @staticmethod
    def _mamba_paged(mixer, i, st, g, a):
        """Mamba layer `i` over one group `g` of a step's rows, `a` `[rows,
        width, hidden]`: the rows' state out of the pool's `st` and back.
        A group whose row i is lane i (`g.lanes` None) uses the state in
        place: one token a row, through the kernel `ssm_state_update`
        where the leaf takes it (`ops/pallas/ssm_state_update.py
        why_xla`), which writes the new state into the leaf itself.
        Another group gathers its rows' lanes and scatters them back (an
        idle row names no lane: its update is dropped)."""
        live = g.live_slots()
        n_live = jnp.sum(live, axis=1).astype(jnp.int32)
        if g.lanes is None and g.width == 1:
            why = state_update.why_xla(st["ssm"])
            if why is None:
                fresh = g.fresh != 0
                z, x, B, C, dt, window = mixer.mix_in(
                    a, jnp.where(fresh[:, None, None], 0, st["conv"][i]),
                    live, n_live)
                y, st["ssm"] = state_update.ssm_state_update(
                    st["ssm"], i, x[:, 0], B[:, 0], C[:, 0], dt[:, 0],
                    mixer.decay_rate(), fresh, live[:, 0])
                st["conv"] = st["conv"].at[i].set(window)
                return mixer.mix_out(y[:, None], x, z)
            note_xla_fallback("ssm_state_update", why)
        if g.lanes is None:
            S_in, window = st["ssm"][i], st["conv"][i]
        else:
            # the rows' lanes alone, not the whole layer first
            S_in = st["ssm"].at[i, g.lanes].get(mode="clip")
            window = st["conv"].at[i, g.lanes].get(mode="clip")
        fresh = g.fresh != 0
        part, S_out, window = mixer(
            a, jnp.where(fresh[:, None, None, None], 0, S_in),
            jnp.where(fresh[:, None, None], 0, window), live, n_live)
        if g.lanes is None:
            st["ssm"] = st["ssm"].at[i].set(S_out)
            st["conv"] = st["conv"].at[i].set(window)
        else:
            st["ssm"] = st["ssm"].at[i, g.lanes].set(S_out, mode="drop")
            st["conv"] = st["conv"].at[i, g.lanes].set(window, mode="drop")
        return part

    def forward(self, input_ids, caches=None):
        """Logits `[b, s, vocab]` float32; with a paged `caches`,
        `(logits, caches)` as GPT's paged path returns them, and the step's
        counts (`STEP_STATS`) left on `caches.step_stats`."""
        cfg = self.cfg
        ids = input_ids._array if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids.shape
        if caches is not None and not getattr(caches, "is_paged", False):
            raise ValueError(
                "NemotronH takes a paged cache (serving) or none")
        live = jnp.ones((b, s), bool) if caches is None \
            else caches.live_slots()
        h = jnp.take(self.embed.weight._array, ids.astype(jnp.int32),
                     axis=0)
        h = h.astype(RESIDUAL_DTYPE)
        moe_stats, slab, ssm_layer = [], 0, 0
        for layer in self.layers:
            a = rms_norm(h, layer.norm, cfg.norm_eps)
            if layer.kind == "M":
                with jax.named_scope("ssm_mixer"):
                    if caches is None:
                        part = self._mamba_plain(layer.mixer, a)
                    else:
                        part = caches.per_group(functools.partial(
                            self._mamba_paged, layer.mixer, ssm_layer,
                            caches.lane_state), a)
                        ssm_layer += 1
            elif layer.kind == "*":
                with jax.named_scope("gqa_attention"):
                    part = layer.attn(
                        a, None if caches is None else caches.layer(slab))
                    slab += 1
            else:
                part, stats = layer.moe(a.reshape(b * s, -1),
                                        live.reshape(-1))
                part = part.reshape(b, s, -1)
                moe_stats.append(stats)
            h = h + part.astype(h.dtype)
        h = rms_norm(h, self.norm_f, cfg.norm_eps)
        w = self.head.weight._array
        logits = jnp.einsum("bsh,vh->bsv", h.astype(w.dtype), w,
                            preferred_element_type=jnp.float32)
        if caches is None:
            return Tensor._from_op(logits)
        # STEP_STATS: sums over the expert layers, but the largest load
        caches.step_stats = jnp.concatenate([
            *sum_step_stats(moe_stats),
            sum(jnp.sum(jnp.any(g.live_slots(), axis=1), keepdims=True)
                for g in caches.groups).astype(jnp.int32)])
        return Tensor._from_op(logits), caches
