"""The attention kernels through the real TPU compiler, Mosaic included,
without a chip: libtpu can compile for a v5e topology it does not have
(`jax.experimental.topologies`). Interpret mode checks what a kernel
computes and `lowering_platforms=("tpu",)` what Pallas will lower; only this
shows what Mosaic refuses (tilings, layouts, casts, VMEM). It compiles and
never runs, so numerics on the chip stay `chip_smoke.py`'s to check.

Runs in a subprocess: the topology client is libtpu's, and nothing it does
should be able to take the test process down with it. Skips when libtpu
cannot describe the topology here.
"""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_SCRIPT = r'''
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:  # no libtpu, or it cannot start here
    print("NO_TOPOLOGY", type(e).__name__, e)
    raise SystemExit(0)
one = SingleDeviceSharding(topo.devices[0])


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one)


def compile_for_v5e(fn, *args):
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    n = lowered.as_text().count("tpu_custom_call")
    lowered.compile()          # the TPU compiler; raises on a refusal
    return n


from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention

# the serving shapes: gpt_1p3b heads, 16-token blocks, 128-slot tables
B, H, D, bs, nk, NB = 8, 16, 128, 16, 128, 64
for quant in (False, True):
    for width in (1, 5, 128):
        arena = sds((2, H, NB, bs, D), "int8" if quant else "bfloat16")
        args = [sds((B, width, H, D), "bfloat16"), arena, arena,
                sds((B, nk), "int32")] + [sds((B,), "int32")] * 3
        if quant:
            args += [sds((2, H, NB), "float32")] * 2

        def ragged(q, k, v, tables, q_start, kv_live, q_lens, *scales):
            kw = dict(zip(("k_scale", "v_scale"), scales))
            return ragged_paged_attention(
                q, k, v, 1, tables, q_start, kv_live, q_lens=q_lens,
                interpret=False, **kw)

        assert compile_for_v5e(ragged, *args) == 1, (quant, width)

# the flash kernels at the training head sizes (the dispatch asks the
# default backend, which is the CPU here, so the gate is opened by hand)
fa._use_pallas = lambda: True
for shape in ((2, 1024, 12, 64), (1, 2048, 16, 128)):
    x = sds(shape, "bfloat16")

    def loss(q, k, v):
        o = fa.flash_attention_array(q, k, v, causal=True)
        return o.astype(jnp.float32).sum()

    n = compile_for_v5e(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    assert n == 3, (shape, n)          # forward, dK/dV, dQ
print("MOSAIC_OK")
'''


def test_attention_kernels_compile_for_v5e():
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip("libtpu cannot describe a v5e topology here: "
                    + proc.stdout.strip().splitlines()[-1][:200])
    assert proc.returncode == 0 and "MOSAIC_OK" in proc.stdout, (
        proc.stdout[-2000:] + proc.stderr[-4000:])
