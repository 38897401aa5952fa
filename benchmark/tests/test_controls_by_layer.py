"""`benchmark/tools/controls_by_layer.py` on a hand-made parameter dict: each
kind plants its fault where it says and nowhere else, a layer at a time, and
the sound parameters pass through the patched reference untouched."""
import sys
import types
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import correct  # noqa: E402
from benchmark.tools import controls_by_layer as tool  # noqa: E402


def _params():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    leaf = lambda *shape: jnp.asarray(  # noqa: E731
        rng.normal(size=shape), jnp.bfloat16)
    return {"embed.weight": leaf(8, 4), "head.weight": leaf(8, 4),
            "norm_f.weight": leaf(4),
            "layers.0.attn.o.weight": leaf(4, 4),
            "layers.1.attn.o.weight": leaf(4, 4),
            "layers.1.moe.experts.w2": leaf(3, 2, 4)}


def _reference():
    """A stand-in for the reference module: `sequence_logits` hands back
    what the patched module made of its arguments (a layer's leaves taken
    before the next layer is asked for, as the reference uses them)."""
    ref = types.ModuleType("reference")
    ref._layer_params = lambda params, i: {
        k[len(f"layers.{i}."):]: v for k, v in params.items()
        if k.startswith(f"layers.{i}.")}
    ref.sequence_logits = lambda params, ids, config, rows=None: (
        [dict(ref._layer_params(params, i)) for i in (0, 1)], config)
    return ref


@pytest.fixture
def patched(monkeypatch):
    monkeypatch.setattr(tool, "load", lambda config: _reference())
    return tool.load_reference({})


def _same(a, b):
    return bool((np.asarray(a, np.float32) == np.asarray(b, np.float32)).all())


@pytest.mark.parametrize("kind,outside,attn,expert0,expert1,topk", [
    ("fp8", False, False, False, False, 4),
    ("layers_fp8", True, False, False, False, 4),
    ("dense", True, True, True, True, 1 << 30),
    ("expert_fault", True, True, False, True, 4),
    ("routed_zero", True, True, False, False, 4)])
def test_each_kind_plants_its_fault_and_no_other(patched, kind, outside, attn,
                                                 expert0, expert1, topk):
    params = _params()
    low = tool.lower(params, kind)
    assert _same(low["embed.weight"], params["embed.weight"]) == outside
    assert _same(low["head.weight"], params["head.weight"]) == outside
    assert low["norm_f.weight"] is params["norm_f.weight"]    # a vector
    layers, config = patched.sequence_logits(low, [1], {"index_topk": 4})
    assert config["index_topk"] == topk
    for i in (0, 1):
        assert _same(layers[i]["attn.o.weight"],
                     params[f"layers.{i}.attn.o.weight"]) == attn
    w2, was = layers[1]["moe.experts.w2"], params["layers.1.moe.experts.w2"]
    assert _same(w2[0], was[0]) == expert0
    assert _same(w2[1:], was[1:]) == expert1
    if kind in ("expert_fault", "routed_zero"):
        assert not np.asarray(w2[0], np.float32).any()
    # the sound parameters, a plain dict, pass through untouched
    layers, config = patched.sequence_logits(params, [1], {"index_topk": 4})
    assert config == {"index_topk": 4}
    assert layers[1]["moe.experts.w2"] is was


def test_the_layers_lowering_is_the_harness_own():
    """A matrix, and a stacked leaf lowered a slice at a time: the bits
    `correct.lower_precision` gives the whole leaf."""
    params = _params()
    p = {"attn.o.weight": params["layers.0.attn.o.weight"],
         "moe.experts.w2": params["layers.1.moe.experts.w2"],
         "norm1.weight": params["norm_f.weight"]}
    mine, theirs = tool.layer(p, "fp8"), correct.lower_precision(p, "fp8")
    for name in p:
        assert _same(mine[name], theirs[name]), name
        assert mine[name].dtype == p[name].dtype
    assert not _same(mine["moe.experts.w2"], p["moe.experts.w2"])
    with pytest.raises(ValueError, match="no control"):
        tool.lower(params, "int4")
