"""`run.py --control 1` for a configuration whose second whole copy of the
parameters does not fit beside the first (`dots3-ep8-serve`: 8.2 GB), with the
controls that say what `correct` sees of this model beside the file's own.

    python3 benchmark/tools/controls_by_layer.py [kind,kind,...] \\
        --workload dots3-ep8-serve.notes --seed 211 --seconds 47 --trace 0 \\
        --control 1

It is the harness's comparison as it stands (`correct.serving`: the cell's
own run, the same requests, formula and margin; the result line's `compared`
holds `control.<kind>_deficit` beside its limit, and the run comes out not
correct when any control fails the margin). Only WHERE the control's
parameters are made is replaced: `correct.lower_precision` hands back the
parameters with a note of the fault, and the fault is planted a layer at a
time when the reference asks for that layer (`_layer_params`), eagerly (under
`jit` the compiler drops a round trip through float8). Kinds:

- `fp8`: the file's control, every matrix through float8_e4m3fn
  (`correct.lower_precision`'s own lowering), embedding and head included;
- `layers_fp8`: the same on the layers' matrices alone, embedding and head
  left sound: what the margin sees of the LAYERS' precision under an init
  whose stream the embedding dominates;
- `dense`: no selection, every causal key visible in the full layers
  (`index_topk` past every context);
- `expert_fault`: ONE held routed expert dead in every expert layer (its `W2`
  zero): a fault confined to a routed expert;
- `routed_zero`: every held routed expert dead: the routed sum adds nothing.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
from benchmark import correct  # noqa: E402

KINDS = ("fp8", "layers_fp8", "dense", "expert_fault", "routed_zero")
ROUTED_W2 = "moe.experts.w2"
whole, load, serving = (correct.lower_precision, correct.load_reference,
                        correct.serving)


class Control(dict):
    """The sound parameters, and what is to be planted: `kind`, and the
    configuration keys the reference is to read in place of the file's."""
    kind, config = None, {}


def lower(params, kind):
    """`correct.lower_precision`'s place: the parameters as a `Control`.
    What lies outside the layers is lowered here (`fp8`), the rest when
    the reference asks for a layer (`layer`)."""
    if kind not in KINDS:
        raise ValueError(f"no control {kind!r}: {', '.join(KINDS)}")
    out = Control(params)
    if kind == "fp8":
        out.update(whole({k: v for k, v in params.items()
                          if not k.startswith("layers.")}, "fp8"))
    out.kind = kind
    out.config = {"index_topk": 1 << 30} if kind == "dense" else {}
    return out


def fp8(w):
    """`correct.lower_precision(.., "fp8")` of one leaf, to the last bit, a
    slice of a stacked leaf at a time: the harness's float32 copy of a whole
    `[experts, 5120, 3072]` leaf (1.9 GB) does not fit beside the parameters
    and the reference's pass. Its scale is the harness's: the largest value
    along axis 0 of the WHOLE leaf."""
    import jax.numpy as jnp

    if w.ndim < 3:
        return whole({"w": w}, "fp8")["w"]
    top = jnp.abs(w).max(axis=0).astype(jnp.float32)
    scale = jnp.where(top > 0, top, 1.0) / 448.0
    return jnp.stack([
        ((w[i].astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
         .astype(jnp.float32) * scale).astype(w.dtype)
        for i in range(w.shape[0])])


def layer(p, kind):
    """One layer's leaves `p` with the control's fault planted."""
    if kind in ("fp8", "layers_fp8"):
        return {k: fp8(v) if v.ndim >= 2 else v for k, v in p.items()}
    if kind in ("expert_fault", "routed_zero") and ROUTED_W2 in p:
        w2 = p[ROUTED_W2]
        dead = w2 * 0 if kind == "routed_zero" else w2.at[0].set(0)
        return {**p, ROUTED_W2: dead}
    return p


def load_reference(config):
    ref = load(config)
    layer_params, logits = ref._layer_params, ref.sequence_logits
    planted = []

    def lazily(params, i):
        # the reference asks for a layer when it is done with the one
        # before: that one's planted copies (1.5 GB of experts) go first
        while planted:
            planted.pop().clear()
        p = layer_params(params, i)
        if isinstance(params, Control):
            p = layer(p, params.kind)
            planted.append(p)
        return p

    def sequence_logits(params, ids, config, rows=None):
        if isinstance(params, Control):
            config = {**config, **params.config}
        return logits(params, ids, config, rows=rows)

    ref._layer_params, ref.sequence_logits = lazily, sequence_logits
    return ref


def main(argv):
    kinds = KINDS
    if argv and not argv[0].startswith("--"):
        kinds = tuple(argv.pop(0).split(","))
    correct.lower_precision = lower
    correct.load_reference = load_reference
    correct.serving = lambda *a, control=(), **kw: serving(
        *a, control=kinds if control else (), **kw)
    from benchmark import run

    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
