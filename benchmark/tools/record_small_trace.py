"""Record a small capture on one chip for `benchmark/tests/data/`: three
annotated steps of a tiny program that holds what the reduction has to tell
apart: matrix products, a reduction, and the program's ragged paged-attention
kernel at a small shape. (On four chips the jitted step is refused, "Mosaic
kernels cannot be automatically partitioned" (my chip run, PR 22): a capture
with collectives needs the kernel under `shard_map`, PERF.md section 7.)

    python3 benchmark/tools/record_small_trace.py --out chiprun_out/small_trace

The capture is a few hundred KB, so a test can keep it and check the reduction
against numbers read off it by hand once.
"""
from __future__ import annotations

import argparse
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path[0] = str(ROOT)


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--out", required=True)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) != 1:
        print("record_small_trace: needs a tpu backend with one chip",
              file=sys.stderr)
        return 3

    B, H, D, bs, nk, NB = 4, 4, 128, 16, 8, 40
    key = jax.random.PRNGKey(0)
    arena = jax.random.normal(key, (1, H, NB, bs, D), jnp.bfloat16)
    q = jax.random.normal(key, (B, 1, H, D), jnp.bfloat16)
    tables = jnp.arange(1, 1 + B * nk, dtype=jnp.int32).reshape(B, nk)
    q_start = jnp.full((B,), 100, jnp.int32)
    kv_live = jnp.full((B,), 7, jnp.int32)
    q_lens = jnp.ones((B,), jnp.int32)
    a = jax.random.normal(key, (1024, 2048), jnp.bfloat16)
    w = jax.random.normal(key, (2048, 2048), jnp.bfloat16)

    @jax.jit
    def step(a, w, q, arena, tables, q_start, kv_live, q_lens):
        o = ragged_paged_attention(q, arena, arena, 0, tables, q_start,
                                   kv_live, q_lens=q_lens)
        y = a @ w
        for _ in range(4):
            y = jnp.tanh(y @ w)
        return o, y, jnp.sum(y.astype(jnp.float32), axis=0)

    operands = (a, w, q, arena, tables, q_start, kv_live, q_lens)
    jax.block_until_ready(step(*operands))
    out = Path(args.out)
    shutil.rmtree(out, ignore_errors=True)
    out.mkdir(parents=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"bench.step {i}"):
                jax.block_until_ready(step(*operands))
            time.sleep(0.002)       # an idle gap between steps, on purpose
    jax.profiler.stop_trace()
    files = list(out.rglob("*.xplane.pb"))
    print("recorded", [(str(f), f.stat().st_size) for f in files])
    return 0


if __name__ == "__main__":
    sys.exit(main())
