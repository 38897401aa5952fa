"""What the hybrid model's test files share: a small seeded model, the plain
reference (`benchmark/reference/nemotron_h_block.py`, loaded by path as
`benchmark/correct.py` loads it) and the configuration keys it reads."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

from paddle_tpu.models.nemotron_h import NemotronH, NemotronHConfig

REPO = Path(__file__).resolve().parents[1]
VOCAB = 256
PATTERN = "MEM*E"


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "nemotron_h_reference",
        REPO / "benchmark/reference/nemotron_h_block.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(pattern=PATTERN, router_experts=16, experts_held=None,
           experts_from=0, **kw):
    sizes = dict(
        vocab_size=VOCAB, hidden_size=64, pattern=pattern, num_heads=4,
        num_kv_heads=2, head_dim=16, mamba_num_heads=8, mamba_head_dim=16,
        ssm_state_size=16, n_groups=2, conv_kernel=4, chunk_size=8,
        moe_latent_size=32, moe_intermediate_size=48,
        shared_intermediate_size=96, router_experts=router_experts,
        experts_held=experts_held, experts_from=experts_from,
        experts_per_tok=4, routed_scaling=2.5, max_seq_len=128)
    sizes.update(kw)
    return NemotronHConfig(**sizes)


def seed_weights(model, dtype=jnp.float32, seed=0):
    """Seeded weights under which no term of the equations is the identity:
    matrices N(0, 0.08), gains near 1, the convolution's bias and the
    router's choosing bias off nought, `A_log` and `dt_bias` spread over
    decays from near 0 to near 1. The router stays float32."""
    key = jax.random.PRNGKey(seed)
    for i, (name, p) in enumerate(
            sorted(model.named_parameters_dict().items())):
        shape, k = p._array.shape, jax.random.fold_in(key, i)
        if name.endswith("A_log"):
            a = jnp.log(jax.random.uniform(k, shape, minval=1.0, maxval=16.0))
        elif name.endswith("dt_bias"):
            a = jax.random.uniform(k, shape, minval=-4.0, maxval=0.0)
        elif name.endswith("router.bias"):
            a = 0.05 * jax.random.normal(k, shape)
        elif len(shape) >= 2:
            a = 0.08 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("bias"):
            a = jnp.full(shape, 0.1, jnp.float32)
        else:
            a = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        p._array = a.astype(jnp.float32 if ".router." in name else dtype)
    model.eval()
    return model


def build(dtype=jnp.float32, seed=0, **kw):
    return seed_weights(NemotronH(config(**kw)), dtype, seed)


def params_of(model):
    return {k: p._array for k, p in model.named_parameters_dict().items()}


def published_keys(model):
    """The configuration keys the reference reads, for `model`."""
    cfg = model.cfg
    return {"layer_norm_epsilon": cfg.norm_eps,
            "hybrid_override_pattern": cfg.pattern,
            "mamba_num_heads": cfg.mamba_num_heads,
            "n_groups": cfg.n_groups,
            "num_attention_heads": cfg.num_heads,
            "num_key_value_heads": cfg.num_kv_heads,
            "num_experts_per_tok": cfg.experts_per_tok,
            "routed_scaling_factor": cfg.routed_scaling,
            "norm_topk_prob": cfg.norm_topk_prob,
            "experts_held_from": cfg.experts_from}
