"""Compile a configuration's programs at their real sizes for a TPU v5e that is
described and not attached, here on the CPU, and print what the compiler says
of their memory:

    python3 benchmark/tools/aot.py --config c1p3b-serve [--set engine.kv_hbm_bytes=...]

`--config` is a configuration's name in `BENCHMARK.json`, or the path of a
configuration file that no cell uses yet.

Nothing runs, so this says nothing of times or results: it finds what the
chip's compiler refuses (a kernel, a program that does not fit 16 GB) before a
chip call is spent on it, and gives the `memory_analysis` figures written into
the configuration files under `aot`. It hands the program the described
devices and opens its backend gates by hand, which is why it is a tool and no
part of a run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path[0] = str(ROOT)

GIB = 1 << 30


def _open_kernel_gates():
    """The dispatches, and the gate that turns donation off on a CPU mesh, ask
    the default backend, which is the CPU here."""
    from paddle_tpu.ops.pallas import flash_attention, paged_attention
    from paddle_tpu.parallel import spmd

    spmd.mesh_donate_argnums = tuple

    flash_attention._use_pallas = lambda: True
    flash_attention.interpret_mode = lambda: False
    paged_attention.use_pallas = lambda: True
    paged_attention.interpret_mode = lambda: False


def _figures(compiled, text):
    m = compiled.memory_analysis()
    out = {
        "arguments_gib": round(m.argument_size_in_bytes / GIB, 3),
        "outputs_gib": round(m.output_size_in_bytes / GIB, 3),
        "aliased_gib": round(m.alias_size_in_bytes / GIB, 3),
        "temporaries_gib": round(m.temp_size_in_bytes / GIB, 3),
        "code_gib": round(m.generated_code_size_in_bytes / GIB, 3),
        "kernel_calls": text.count("tpu_custom_call"),
    }
    out["live_gib"] = round(out["arguments_gib"] + out["outputs_gib"]
                            - out["aliased_gib"] + out["temporaries_gib"]
                            + out["code_gib"], 3)
    return out


def serve(config, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from benchmark.runners.serve import build_engine

    one = SingleDeviceSharding(topo.devices[0])
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)  # noqa: E731
    h = lambda shape, dt=jnp.int32: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=one)
    _, engine = build_engine(config, 0)
    B = engine.max_batch
    out = {"kv_blocks": engine.pool.num_blocks,
           "arena_gib": round(2 * engine.pool.k.nbytes / GIB, 3)}
    for W in engine.width_buckets:
        fn = engine._get_step_fn(B, W)
        args = (
            {k: like(v) for k, v in engine._params.items()},
            {k: like(v) for k, v in engine._buffers.items()},
            like(engine.pool.k), like(engine.pool.v), engine._lora_tables,
            h((B, W)), h((B, engine.max_blocks)), h((B, W)), h((B, W)),
            h((B, W)), h((B,)), h((B,)), h((B,)), h((B,)), h((B,)),
            h((B,), jnp.float32), h((B,)), h((B,), jnp.float32),
            like(engine._key))
        lowered = fn.trace(*args).lower(lowering_platforms=("tpu",))
        out[f"w{W}"] = _figures(lowered.compile(), lowered.as_text())
        print(f"w{W}", json.dumps(out[f"w{W}"]), flush=True)
    return out


def train(config, topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    import paddle_tpu as paddle
    from benchmark import spec, weights
    from paddle_tpu.distributed.mesh import build_mesh, set_mesh

    tr = config["train"]
    model = weights.build_model(config, 0)
    opt = paddle.optimizer.AdamW(learning_rate=tr["learning_rate"],
                                 parameters=model.parameters())
    mesh = set_mesh(build_mesh(tr["mesh"],
                               devices=topo.devices[:config["chips"]]))
    step = spec.resolve(tr["step_builder"])(
        model, spec.resolve(tr["loss"]), opt, mesh,
        zero_stage=tr["zero_stage"], remat=tr["remat"])
    named = model.named_parameters_dict()
    ns = lambda s: NamedSharding(mesh, s)  # noqa: E731
    sds = lambda shape, dt, s: jax.ShapeDtypeStruct(shape, dt, sharding=s)  # noqa: E731
    if step.explicit_update:
        pspec, _, ospec = step._explicit_state_specs()
        flat = {k: jax.ShapeDtypeStruct((n + pad,), named[k]._array.dtype)
                for k, (_, n, pad) in step._flat_meta.items()}
        slots = jax.eval_shape(opt.init_state_arrays, flat)
        params = {k: sds(p._array.shape, p._array.dtype, ns(pspec[k]))
                  for k, p in named.items()}
        opt_state = {k: {s: sds(a.shape, a.dtype, ns(ospec[k][s]))
                         for s, a in sl.items()} for k, sl in slots.items()}
    else:
        from paddle_tpu.parallel.spmd import build_state_shardings

        _, pshard, _, oshard = build_state_shardings(
            model, opt, mesh, tr["zero_stage"])
        slots = jax.eval_shape(
            opt.init_state_arrays,
            {k: jax.ShapeDtypeStruct(p._array.shape, p._array.dtype)
             for k, p in named.items()})
        params = {k: sds(p._array.shape, p._array.dtype, pshard[k])
                  for k, p in named.items()}
        opt_state = {k: {s: sds(a.shape, a.dtype, oshard[k][s])
                         for s, a in sl.items()} for k, sl in slots.items()}
    batch = sds((tr["batch"], tr["seq_len"]), jnp.int32,
                ns(step.batch_specs[0]))
    rep = ns(jax.sharding.PartitionSpec())
    key = jax.random.PRNGKey(0)
    lowered = step._build(2).trace(
        params, {}, opt_state, sds((), jnp.float32, rep),
        sds(key.shape, key.dtype, rep), batch, batch,
    ).lower(lowering_platforms=("tpu",))
    compiled = lowered.compile()
    out = {"step": _figures(compiled, lowered.as_text())}
    text = compiled.as_text()
    out["step"]["compiled_collectives"] = {
        op: text.count(f" {op}(") + text.count(f" {op}-start(")
        for op in ("all-reduce", "all-gather", "reduce-scatter")}
    print("step", json.dumps(out["step"]), flush=True)
    return out


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--set", action="append", default=[],
                   help="dotted.key=json, laid over the configuration")
    args = p.parse_args()

    from jax.experimental import topologies

    from benchmark import spec

    if args.config.endswith(".json"):
        config = spec.load_json(ROOT / args.config)
    else:
        entry = spec.by_name(spec.load_benchmark()["configs"], args.config,
                             "configuration")
        config = spec.load_json(ROOT / entry["file"])
    for item in args.set:
        key, _, value = item.partition("=")
        at = config
        *path, last = key.split(".")
        for part in path:
            at = at[part]
        at[last] = json.loads(value)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    _open_kernel_gates()
    out = {"serve": serve, "train": train}[config["kind"]](config, topo)
    print(json.dumps({"config": args.config, "set": args.set, "aot": out}))


if __name__ == "__main__":
    main()
