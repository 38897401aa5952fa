"""GPT: the flagship decoder-only LM (GPT-2-style block; 1.3B with TP+PP).

Built from the framework's own TP layers (ColumnParallelLinear /
RowParallelLinear / VocabParallelEmbedding — the Megatron partitioning of the
reference's fleet/layers/mpu/mp_layers.py) with flash attention on the
Pallas kernel and activation remat. Sequence-parallel activations are
annotated on the 'sp' axis; ring attention (context parallel) is selected by
`attn_impl='ring'`.
"""
from __future__ import annotations

import math

import numpy as np

from .. import nn
from ..core import autograd
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
    _constraint,
)
from ..nn import initializer as I
from ..ops import common_nn as F
from ..ops import manipulation as M


class GPTConfig:
    def __init__(
        self,
        vocab_size=50304,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        max_seq_len=1024,
        intermediate_size=None,
        dropout=0.0,
        attn_impl="flash",  # flash | ring | xla
        remat=False,
        dtype="float32",
        fused_head_chunks=None,  # seq chunks for the fused CE head (None=auto)
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.dropout = dropout
        self.attn_impl = attn_impl
        self.remat = remat
        self.dtype = dtype
        self.fused_head_chunks = fused_head_chunks


def _split_fused_qkv(qkv, b, s, num_heads, head_dim):
    """Split the fused QKV projection PER-HEAD-GROUPED (the Megatron
    column order): column block for head i is its contiguous
    ``[q_i, k_i, v_i]``, so a contiguous tp shard of the 3h axis IS a
    head group — head-sharding the split q/k/v costs no cross-chip
    realignment in the tensor-parallel serving path. A qkv-major
    ``[b, s, 3, heads, hd]`` order would put all Q heads first and force
    XLA to re-gather the sharded axis every layer; hlolint's seeded
    tp=2 regression (tests/test_ir_contracts.py) patches this function
    with exactly that order to prove the collective-budget contract
    (analysis/contracts.py IR001) trips on it."""
    qkv = M.reshape(qkv, [b, s, num_heads, 3, head_dim])
    q = M.squeeze(M.slice(qkv, [3], [0], [1]), 3)
    k = M.squeeze(M.slice(qkv, [3], [1], [2]), 3)
    v = M.squeeze(M.slice(qkv, [3], [2], [3]), 3)
    return q, k, v


class CausalSelfAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        self.qkv = ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False
        )
        self.proj = RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True
        )
        self.dropout = cfg.dropout

    def forward(self, x, cache=None):
        b, s, _ = x.shape
        paged = getattr(cache, "is_paged", False)
        # [b, s, 3h] (mp-sharded on last dim); a paged cache does the
        # projections that serving has a say in (PagedLayerView)
        qkv = (cache.column_parallel(self.qkv, x, "attn_qkv") if paged
               else self.qkv(x))
        # per-head-grouped regroup (module-level so hlolint's seeded
        # regression can patch in the qkv-major layout it exists to catch)
        q, k, v = _split_fused_qkv(qkv, b, s, self.num_heads, self.head_dim)
        if paged:
            # serving path: the cache appends K/V to its block arena and
            # attends through this sequence's block table
            out = M.reshape(
                cache.attend(q, k, v), [b, s, self.num_heads * self.head_dim]
            )
            return cache.row_parallel(self.proj, out, "attn_proj"), cache
        if cache is not None:
            # incremental decode: fixed-size KV cache so every step compiles
            # once (reference fused_multi_transformer's cache_kv role).
            # cache = (k_buf [b, L, h, d], v_buf, cur_len int32 scalar).
            # Inference-only path: computed in plain jnp, no tape.
            import jax
            import jax.numpy as jnp

            k_buf, v_buf, cur = cache
            kb = jax.lax.dynamic_update_slice_in_dim(k_buf, k._array, cur, 1)
            vb = jax.lax.dynamic_update_slice_in_dim(v_buf, v._array, cur, 1)
            L = kb.shape[1]
            scale = 1.0 / np.sqrt(self.head_dim)
            s_l = jnp.einsum(
                "bqhd,bkhd->bhqk", q._array, kb,
                preferred_element_type=jnp.float32,
            ) * scale
            kpos = jnp.arange(L)[None, None, None, :]
            qpos = cur + jnp.arange(s)[None, None, :, None]
            s_l = jnp.where(kpos <= qpos, s_l, -1e30)
            p = jax.nn.softmax(s_l, axis=-1)
            o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(vb.dtype), vb)
            out = M.reshape(Tensor._from_op(o), [b, s, self.num_heads * self.head_dim])
            return self.proj(out), (kb, vb, cur + s)
        if self.cfg.attn_impl == "ring":
            from ..parallel.ring_attention import ring_attention

            out, node = autograd.apply(
                lambda qa, ka, va: ring_attention(qa, ka, va, causal=True),
                q, k, v, name="ring_attention",
            )
            out = Tensor._from_op(out, node)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, dropout_p=self.dropout, is_causal=True,
                training=self.training,
            )
        out = M.reshape(out, [b, s, self.num_heads * self.head_dim])
        return self.proj(out)


class GPTBlock(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size)
        self.attn = CausalSelfAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size)
        self.fc1 = ColumnParallelLinear(
            cfg.hidden_size, cfg.intermediate_size, gather_output=False
        )
        self.fc2 = RowParallelLinear(
            cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True
        )
        self.act = nn.GELU(approximate=True)
        self.dropout = nn.Dropout(cfg.dropout)
        self._cfg = cfg

    def _inner(self, x, cache=None):
        if cache is not None:
            attn_out, new_cache = self.attn(self.ln1(x), cache=cache)
            x = x + attn_out
            if getattr(cache, "is_paged", False):
                h = cache.column_parallel(self.fc1, self.ln2(x), "ffn_fc1")
                x = x + cache.row_parallel(self.fc2, self.act(h), "ffn_fc2")
            else:
                x = x + self.fc2(self.act(self.fc1(self.ln2(x))))
            return x, new_cache
        x = x + self.dropout(self.attn(self.ln1(x)))
        x = _constraint(x, "dp", "sp", None)
        x = x + self.dropout(self.fc2(self.act(self.fc1(self.ln2(x)))))
        x = _constraint(x, "dp", "sp", None)
        return x

    def forward(self, x, cache=None):
        if cache is not None:
            return self._inner(x, cache=cache)
        if self._cfg.remat:
            from ..distributed.fleet.utils import recompute

            return recompute(self._inner, x)
        return self._inner(x)


class GPT(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.blocks = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size)
        # LM head is weight-tied to wte (standard GPT; the reference ties via
        # SharedLayerDesc in pp_layers)

    def forward(self, input_ids, caches=None, pos_offset=0, labels=None):
        b, s = input_ids.shape
        if caches is not None and getattr(caches, "is_paged", False):
            # serving path: the paged state's qpos IS each token's absolute
            # position (ragged mixed batches — decode rows and prefill
            # chunks start at different offsets per row)
            pos = Tensor._from_op(caches.qpos)
        elif caches is not None:
            import jax.numpy as jnp

            po = pos_offset._array if isinstance(pos_offset, Tensor) else pos_offset
            pos = Tensor._from_op(po + jnp.arange(s, dtype=jnp.int64)[None])
        else:
            pos = M.reshape(Tensor(np.arange(s, dtype=np.int64)), [1, s])
        x = self.wte(input_ids) + self.wpe(pos)
        x = self.drop(x)
        if caches is None:
            x = _constraint(x, "dp", "sp", None)
        paged = caches is not None and getattr(caches, "is_paged", False)
        new_caches = [] if caches is not None and not paged else None
        for i, blk in enumerate(self.blocks):
            if paged:
                # the shared paged arena threads through every layer; each
                # block's scatter feeds the next layer's trace
                x, _ = blk(x, cache=caches.layer(i))
            elif caches is not None:
                x, c = blk(x, cache=caches[i])
                new_caches.append(c)
            else:
                x = blk(x)
        x = self.ln_f(x)
        if labels is not None and caches is None:
            # training head: loss computed directly from hidden states.
            # Chunked fused linear+CE (ops/fused_ce.py) kicks in when the
            # [b, s, vocab] logits would be big enough that HBM pressure
            # costs more than the backward's logit recompute (~1.5 GB bf16
            # measured crossover on v5e); small shapes keep the one-matmul
            # unfused path, which is faster when memory is free.
            import jax
            import jax.numpy as jnp

            from ..core import autograd
            from ..ops.fused_ce import fused_linear_cross_entropy

            lab = labels._array if isinstance(labels, Tensor) else jnp.asarray(labels)
            n_chunks = self.cfg.fused_head_chunks
            logits_bytes = 2 * b * s * self.cfg.vocab_size
            use_fused = (n_chunks or 0) != 1 and (
                n_chunks is not None or logits_bytes > 1.5e9
            )

            if use_fused:
                fn = lambda xa, wa: fused_linear_cross_entropy(xa, wa, lab, n_chunks)
            else:
                def fn(xa, wa):
                    lg = jax.lax.dot_general(
                        xa, wa, (((2,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    lse = jax.scipy.special.logsumexp(lg, axis=-1)
                    picked = jnp.take_along_axis(
                        lg, lab[..., None].astype(jnp.int32), axis=-1
                    )[..., 0]
                    return jnp.mean(lse - picked)

            out, node = autograd.apply(
                fn, x, self.wte.weight, name="gpt_head_loss",
            )
            return Tensor._from_op(out, node)
        # logits = x @ wte.T  (vocab-parallel output)
        logits = M.reshape(
            F.linear(x, M.t(self.wte.weight)), [b, s, self.cfg.vocab_size]
        )
        if caches is None:
            logits = _constraint(logits, "dp", "sp", "mp")
            return logits
        if paged and getattr(caches, "mesh", None) is not None:
            # tensor-parallel serving (serving/sharded.py): keep the LM
            # head column-parallel — logits stay vocab-sharded on tp out
            # of the matmul; the unified step program's boundary gather
            # (engine.py pins the scored window replicated, the ONE
            # sanctioned all-gather of IR001) is the only place full
            # vocab rows materialize
            logits = Tensor._from_op(
                caches.constrain(logits._array, None, None, "tp")
            )
        return logits, (caches if paged else new_caches)

    @property
    def kv_slabs(self):
        """Cache slabs a token's keys and values take in a paged arena
        (serving/block_pool.py): one a layer."""
        return self.cfg.num_layers

    def init_caches(self, batch_size, max_len, dtype=None):
        """Fixed-size per-layer KV caches for incremental decode. dtype
        defaults to the model's parameter dtype (bf16 models get bf16
        caches)."""
        import jax.numpy as jnp

        from ..core.dtypes import convert_dtype

        dt = self.wte.weight._array.dtype if dtype is None else convert_dtype(dtype)
        shape = (batch_size, max_len, self.cfg.num_heads,
                 self.cfg.hidden_size // self.cfg.num_heads)
        return [
            (jnp.zeros(shape, dt), jnp.zeros(shape, dt), jnp.int32(0))
            for _ in range(self.cfg.num_layers)
        ]

    def generate(self, input_ids, max_new_tokens=32, temperature=1.0,
                 top_k=None, seed=0, eos_token_id=None):
        """Autoregressive decode with a compiled per-token step and a
        fixed-size KV cache: prefill once, then one [b, 1] step per token
        (the reference's fused_multi_transformer decode loop, TPU-native:
        two cached executables total, static shapes throughout)."""
        import jax
        import jax.numpy as jnp

        from ..core.functional import functional_call, state_dict_arrays

        ids = input_ids if isinstance(input_ids, Tensor) else Tensor(np.asarray(input_ids))
        b, prompt_len = ids.shape
        if max_new_tokens <= 0:
            return ids
        max_len = prompt_len + max_new_tokens
        if max_len > self.cfg.max_seq_len:
            raise ValueError(
                f"generate: prompt {prompt_len} + {max_new_tokens} new tokens "
                f"exceeds max_seq_len {self.cfg.max_seq_len}"
            )
        params, buffers = state_dict_arrays(self)
        caches = self.init_caches(b, max_len)
        model = self

        # compiled executables cached per decode signature (a fresh @jax.jit
        # closure per call would recompile every generate); caches donated —
        # the K/V buffers update in place instead of copying per token
        if not hasattr(self, "_decode_fns"):
            self._decode_fns = {}
        sig = (b, prompt_len, max_len, float(temperature), top_k)
        if sig not in self._decode_fns:

            def sample(logits_last, key):
                lg = logits_last.astype(jnp.float32) / max(temperature, 1e-6)
                if top_k is not None:
                    # jaxlint: disable=JL003 -- top_k is a static Python int from the cache sig (closure constant), evaluated once at trace time, never a traced value
                    kth = jnp.sort(lg, axis=-1)[:, -int(top_k)][:, None]
                    lg = jnp.where(lg < kth, -jnp.inf, lg)
                if temperature == 0.0:
                    return jnp.argmax(lg, axis=-1).astype(jnp.int64)
                return jax.random.categorical(key, lg, axis=-1).astype(jnp.int64)

            def prefill(params, buffers, ids_arr, caches, key):
                (logits, caches), _ = functional_call(
                    model, params, buffers, args=(ids_arr,),
                    kwargs={"caches": caches, "pos_offset": 0}, training=False,
                )
                return sample(logits[:, -1], key), caches

            def step(params, buffers, tok, caches, pos, key):
                (logits, caches), _ = functional_call(
                    model, params, buffers, args=(tok[:, None],),
                    kwargs={"caches": caches, "pos_offset": pos}, training=False,
                )
                return sample(logits[:, -1], key), caches

            self._decode_fns[sig] = (
                # jaxlint: disable=JL004 -- single-device decode jit donating its own KV caches (unsharded); gating would copy the cache per step on CPU. Not IR-checkable: generate()'s per-signature jits are not serving programs; the serving engine's arena donation is the IR002-verified equivalent
                jax.jit(prefill, donate_argnums=(3,)),
                # jaxlint: disable=JL004 -- same: unsharded cache donation, not the mesh miscompile class (see prefill waiver above for the IR002 pointer)
                jax.jit(step, donate_argnums=(3,)),
            )
        prefill, step = self._decode_fns[sig]

        key = jax.random.PRNGKey(seed)
        key, k0 = jax.random.split(key)
        tok, caches = prefill(params, buffers, ids._array, caches, k0)
        out = [tok]
        for t in range(1, max_new_tokens):
            key, kt = jax.random.split(key)
            tok, caches = step(
                params, buffers, tok, caches, jnp.int32(prompt_len + t - 1), kt
            )
            out.append(tok)
            if eos_token_id is not None and bool((tok == eos_token_id).all()):
                break
        gen = jnp.stack(out, axis=1)
        return Tensor._from_op(jnp.concatenate([ids._array.astype(gen.dtype), gen], axis=1))


def gpt_loss_fn(logits_arrays, labels_array):
    """Functional loss for the compiled sharded step (next-token CE).

    Written as picked-logit minus logsumexp so XLA never materializes the
    full [b, s, vocab] log-softmax in fp32 (at vocab 32k+ that array is the
    single largest HBM write in the step); only two [b, s] reductions leave
    the fused loop over the logits."""
    import jax
    import jax.numpy as jnp

    logits = logits_arrays if not isinstance(logits_arrays, (tuple, list)) else logits_arrays[0]
    lg = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(
        lg, labels_array[..., None].astype(jnp.int32), axis=-1
    )[..., 0]
    return jnp.mean(lse - picked)


def gpt_tiny(**kw):
    return GPT(GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4, num_heads=8, max_seq_len=256, **kw))


def gpt_small(**kw):
    return GPT(GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12, max_seq_len=1024, **kw))


def gpt_1p3b(**kw):
    """GPT-3 1.3B shape."""
    return GPT(
        GPTConfig(
            vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
            max_seq_len=2048, **kw,
        )
    )
