"""What the dots3 model's test files share: a small seeded model with both
layer kinds, a dense first layer and expert layers; the plain reference
(`benchmark/reference/dots3_block.py`, loaded by path as
`benchmark/correct.py` loads it) and the configuration keys it reads."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

from paddle_tpu.models.dots3 import FULL, WINDOW, Dots3, Dots3Config

REPO = Path(__file__).resolve().parents[1]
VOCAB = 256
LAYERS = (FULL, FULL, WINDOW, WINDOW)
TOPK, SLIDING = 8, 9       # both smaller than the tests' contexts


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "dots3_reference", REPO / "benchmark/reference/dots3_block.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(layer_types=LAYERS, router_experts=16, experts_held=None,
           experts_from=0, **kw):
    sizes = dict(
        vocab_size=VOCAB, hidden_size=64, layer_types=layer_types,
        num_heads=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7, index_n_heads=4,
        index_head_dim=16, index_topk=TOPK, swa_num_heads=2,
        swa_q_lora_rank=32, swa_kv_lora_rank=24, swa_qk_nope_head_dim=24,
        swa_qk_rope_head_dim=8, swa_v_head_dim=16, swa_rope_theta=5e4,
        sliding_window=SLIDING, intermediate_size=96, first_k_dense=1,
        moe_intermediate_size=32, router_experts=router_experts,
        experts_held=experts_held, experts_from=experts_from,
        experts_per_tok=4, routed_scaling=1.0, max_seq_len=128)
    sizes.update(kw)
    return Dots3Config(**sizes)


def seed_weights(model, dtype=jnp.float32, seed=0):
    """Seeded weights under which no term of the equations is the identity:
    matrices N(0, 0.08), gains near 1, the index norm's bias and the
    router's choosing bias off nought. The router stays float32."""
    key = jax.random.PRNGKey(seed)
    for i, (name, p) in enumerate(
            sorted(model.named_parameters_dict().items())):
        shape, k = p._array.shape, jax.random.fold_in(key, i)
        if name.endswith("router.bias"):
            a = 0.05 * jax.random.normal(k, shape)
        elif len(shape) >= 2:
            a = 0.08 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("bias"):
            a = 0.1 * jax.random.normal(k, shape, jnp.float32)
        else:
            a = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        p._array = a.astype(jnp.float32 if ".router." in name else dtype)
    model.eval()
    return model


def build(dtype=jnp.float32, seed=0, **kw):
    return seed_weights(Dots3(config(**kw)), dtype, seed)


def params_of(model):
    return {k: p._array for k, p in model.named_parameters_dict().items()}


def published_keys(model):
    """The configuration keys the reference reads, for `model`."""
    cfg = model.cfg
    keys = {"rms_norm_eps": cfg.rms_eps, "layer_types": list(cfg.layer_types),
            "apply_mla_qkv_lora_rescale": cfg.lora_rescale,
            "index_head_dim": cfg.index_head_dim,
            "index_n_heads": cfg.index_n_heads, "index_topk": cfg.index_topk,
            "sliding_window_size": cfg.sliding_window,
            "first_k_dense_replace": cfg.first_k_dense,
            "num_experts_per_tok": cfg.experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling,
            "norm_topk_prob": cfg.norm_topk_prob,
            "experts_held_from": cfg.experts_from}
    for prefix, kind in (("", FULL), ("swa_", WINDOW)):
        z = cfg.sizes[kind]
        keys.update({
            prefix + "num_attention_heads": z.heads,
            prefix + "q_lora_rank": z.r_q, prefix + "kv_lora_rank": z.r_kv,
            prefix + "qk_nope_head_dim": z.d_n,
            prefix + "qk_rope_head_dim": z.d_r, prefix + "v_head_dim": z.d_v,
            prefix + "rope_theta": z.theta})
    return keys
