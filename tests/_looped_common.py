"""What the looped model's two test files share: a small seeded model, the
plain reference (`benchmark/reference/ouro_loop.py`, loaded by path as
`benchmark/correct.py` loads it) and the configuration keys it reads."""
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp

from paddle_tpu.models.looped import LoopedConfig, LoopedLM

REPO = Path(__file__).resolve().parents[1]
VOCAB = 256


def load_reference():
    spec = importlib.util.spec_from_file_location(
        "ouro_loop_reference", REPO / "benchmark/reference/ouro_loop.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def build(loop_steps, num_layers=2, hidden=128, heads=2, head_dim=64,
          dtype=jnp.float32, seed=0):
    """A LoopedLM with seeded weights: matrices N(0, 0.05), gains near 1 and
    a gate bias off nought, so that no term of the equations is the
    identity."""
    cfg = LoopedConfig(vocab_size=VOCAB, hidden_size=hidden,
                       num_layers=num_layers, num_heads=heads,
                       head_dim=head_dim, intermediate_size=2 * hidden,
                       max_seq_len=128, loop_steps=loop_steps,
                       rope_theta=1e4)
    model = LoopedLM(cfg)
    key = jax.random.PRNGKey(seed)
    for i, (name, p) in enumerate(
            sorted(model.named_parameters_dict().items())):
        shape, k = p._array.shape, jax.random.fold_in(key, i)
        if len(shape) >= 2:
            a = 0.05 * jax.random.normal(k, shape, jnp.float32)
        elif name.endswith("bias"):
            a = jnp.full(shape, 0.3, jnp.float32)
        else:
            a = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
        p._array = a.astype(dtype)
    model.eval()
    return model


def params_of(model):
    return {k: p._array for k, p in model.named_parameters_dict().items()}


def published_keys(model, loop_steps=None):
    """The configuration keys the reference reads, for `model`."""
    cfg = model.cfg
    return {"rms_norm_eps": cfg.rms_eps, "rope_theta": cfg.rope_theta,
            "total_ut_steps": cfg.loop_steps if loop_steps is None
            else loop_steps,
            "num_hidden_layers": cfg.num_layers,
            "num_attention_heads": cfg.num_heads}
