"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py          # from the root of a checkout, on a TPU

Drives both hot paths once through the entry points a user calls, at the
full width of models the repo ships, with seeded random weights:

- kernels: the ragged paged-attention kernel and the append's block writer
  (bf16 and int8 arenas, every width the serve phase compiles) and the
  three flash kernels (forward, dK/dV, dQ), and the routed experts'
  grouped matmul and the decode group's Mamba state update at the hybrid
  configuration's shapes, compiled (not interpreted), against their XLA
  references, the writer bit for bit;
- serve:   `gpt_1p3b` in bf16 behind `ServingServer` on 127.0.0.1:0, nine
  requests over `/v1/completions`, one of them streamed (SSE);
- train:   `gpt_small` in bf16, AdamW, five `make_sharded_train_step` steps;
- four chips (whenever the device list holds four): the serve phase again
  with `LLMEngine(mesh=4)` and `gpt_1p3b` trained on dp=4 with explicit
  ZeRO-2.

Any failed check fails the run. The process exits non-zero at once unless
`jax.devices()[0].platform` is `tpu`; there is no CPU fallback. Everything
runs in this one process: a chip belongs to one process at a time.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Compile seconds per phase are printed on the line before it. Run twice with
one cache directory (`JAX_COMPILATION_CACHE_DIR`, or `<checkout>/.jax_cache`)
and the second run's compile seconds are the time to load from the cache.

The phase functions take their sizes as arguments, so tests/test_chip_smoke.py
runs the same code on the CPU at `gpt_tiny` size with the kernels interpreted.
Checks only a tpu backend can meet (device placement, `tpu_custom_call`
counts) are made only there.
"""
from __future__ import annotations

import asyncio
import contextlib
import gc
import json
import re
import sys
import time

# Tolerances of the kernel phase, all against an f32 reference computed from
# the same bf16 (or int8 + scale) values under
# jax.default_matmul_precision("highest"):
# - attention outputs: the kernels multiply in bf16 and accumulate in f32,
#   round the probabilities to bf16 before the PV product and round the
#   output to bf16 on the store. bf16 keeps 8 bits: one ulp is 2^-8..2^-7
#   relative (<= 0.8 %), so 2 % of the value plus 2e-2 absolute (for
#   outputs near zero that are sums of O(1) terms) holds with room and
#   still catches a wrong block, mask or scale, which moves values by O(1).
# - gradients: two more bf16 roundings (dS, and P again) feed sums over the
#   whole sequence, so the bound is looser element by element (5 %, 5e-2)
#   and backed by the norm-wise error, which averages the rounding out and
#   must stay under 2 %.
OUT_RTOL, OUT_ATOL = 2e-2, 2e-2
GRAD_RTOL, GRAD_ATOL, GRAD_NORM_TOL = 5e-2, 5e-2, 2e-2

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT, _CACHE_MISS = ("/jax/compilation_cache/cache_hits",
                           "/jax/compilation_cache/cache_misses")


class SmokeFailure(AssertionError):
    """A phase check did not hold."""


def require(cond, what):
    if not cond:
        raise SmokeFailure(what)


_T0 = time.monotonic()


def log(msg):
    print(f"[chip_smoke +{time.monotonic() - _T0:4.0f}s] {msg}", flush=True)


def _on_tpu():
    import jax

    return jax.default_backend() == "tpu"


class CompileClock:
    """Sums what jax reports for getting executables (compiling them, or
    loading them from the persistent cache) and counts cache hits/misses.
    One instance for the process: jax has no call to drop a listener."""

    def __init__(self):
        import jax.monitoring as mon

        self.seconds = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == _COMPILE_EVENT:
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == _CACHE_HIT:
            self.hits += 1
        elif event == _CACHE_MISS:
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


@contextlib.contextmanager
def phase(name, clock, report):
    """Time one phase and record its compile seconds under `name`."""
    log(f"--- {name} ---")
    t0 = time.monotonic()
    s0, h0, m0 = clock.snapshot()
    yield
    gc.collect()    # engine and model cycles: the next phase needs the HBM
    s1, h1, m1 = clock.snapshot()
    report[name] = {"wall_s": round(time.monotonic() - t0, 1),
                    "compile_s": round(s1 - s0, 1),
                    "cache_hits": h1 - h0, "cache_misses": m1 - m0}
    log(f"{name}: passed {json.dumps(report[name])}")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _close(got, want, rtol, atol, what):
    """Element-wise |got - want| <= atol + rtol * |want|, finite, same shape.
    Returns the worst absolute error for the log."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    require(got.shape == want.shape, f"{what}: shape {got.shape} != "
                                     f"{want.shape}")
    require(np.isfinite(got).all(), f"{what}: non-finite values")
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    require(not bad.any(),
            f"{what}: {int(bad.sum())} of {bad.size} values off by more "
            f"than {atol} + {rtol}*|ref| (worst {float(err.max()):.4g})")
    return float(err.max())


def ragged_arenas(key, *, batch, heads, head_dim, block_size, max_blocks):
    """Seeded random K/V arenas big enough for `batch` full block tables
    (+ the null block 0), as bf16 and as int8 with the per-(layer, head,
    block) absmax scales block_pool._quantize_scatter writes. Two layers;
    the checks read layer 1. Jitted, so each shape compiles once."""
    import jax
    import jax.numpy as jnp

    shape = (2, heads, 1 + batch * max_blocks, block_size, head_dim)

    @jax.jit
    def make(key):
        kk, kv = jax.random.split(key)
        out = {}
        for name, sub in (("k", kk), ("v", kv)):
            x = jax.random.normal(sub, shape, jnp.bfloat16)
            f = x.astype(jnp.float32)
            sc = jnp.maximum(jnp.max(jnp.abs(f), axis=(3, 4)) / 127.0, 1e-8)
            q8 = jnp.clip(jnp.round(f / sc[..., None, None]), -127, 127)
            out[name] = x
            out[name + "8"] = q8.astype(jnp.int8)
            out[name + "_scale"] = sc
        return out

    return make(key)


def ragged_batch(seed, *, batch, width, block_size, max_blocks):
    """Ragged metadata shaped as the serve step shapes it: `batch` rows of
    `width` query slots, each row with its own live query count (1..width)
    and its own past length, block tables in shuffled arena order (every
    dead table slot points at the null block, every dead query slot holds
    garbage). Returns the metadata and the [batch, width] mask of live
    outputs."""
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.RandomState(seed)
    max_len = block_size * max_blocks
    # live query counts: 1, the full width, and a spread in between
    counts = np.asarray(
        [1, width] + [int(c) for c in rs.randint(1, width + 1, batch)],
        np.int32)[:batch]
    past = rs.randint(0, max_len - width + 1, batch).astype(np.int32)
    past[1] = max_len - width      # the full-width row fills its table
    past[-1] = 0                   # one row is a fresh prefill
    live_blocks = (past + counts - 1) // block_size + 1
    order = rs.permutation(np.arange(1, 1 + batch * max_blocks))
    tables = np.zeros((batch, max_blocks), np.int32)
    nxt = 0
    for i, nb in enumerate(live_blocks):
        tables[i, :nb] = order[nxt:nxt + nb]
        nxt += nb
    qpos = np.zeros((batch, width), np.int32)
    for i in range(batch):
        qpos[i, :counts[i]] = past[i] + np.arange(counts[i])
    live = np.arange(width)[None, :] < counts[:, None]
    meta = dict(tables=jnp.asarray(tables), qpos=jnp.asarray(qpos),
                q_start=jnp.asarray(past), kv_live=jnp.asarray(live_blocks),
                q_lens=jnp.asarray(counts))
    return meta, live


def check_ragged_kernel(key, arenas, *, quant, width, interpret, batch,
                        heads, head_dim, block_size, max_blocks):
    """ragged_paged_attention against paged_attention_xla on one batch."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_xla,
        ragged_paged_attention,
    )

    seed = int(jax.random.randint(key, (), 0, 2**30))
    m, live = ragged_batch(seed, batch=batch, width=width,
                           block_size=block_size, max_blocks=max_blocks)
    q = jax.random.normal(key, (batch, width, heads, head_dim), jnp.bfloat16)
    if quant:
        k, v = arenas["k8"], arenas["v8"]
        scales = {"k_scale": arenas["k_scale"], "v_scale": arenas["v_scale"]}
    else:
        k, v, scales = arenas["k"], arenas["v"], {}

    @jax.jit
    def kernel(q, k, v, m, scales):
        return ragged_paged_attention(
            q, k, v, 1, m["tables"], m["q_start"], m["kv_live"],
            q_lens=m["q_lens"], interpret=interpret, **scales)

    @jax.jit
    def reference(q, k, v, m, scales):
        f32 = lambda x: x if x.dtype == jnp.int8 else x.astype(jnp.float32)
        return paged_attention_xla(f32(q), f32(k), f32(v), 1, m["tables"],
                                   m["qpos"], **scales)

    out = kernel(q, k, v, m, scales)
    with jax.default_matmul_precision("highest"):
        ref = reference(q, k, v, m, scales)
    mask = live[:, :, None, None]
    return _close(np.where(mask, np.asarray(out, np.float32), 0.0),
                  np.where(mask, np.asarray(ref, np.float32), 0.0),
                  OUT_RTOL, OUT_ATOL,
                  f"ragged kernel width {width} "
                  f"{'int8' if quant else 'bf16'} arena")


def check_append_writer(key, arenas, *, quant, width, interpret, batch,
                        heads, head_dim, block_size, max_blocks):
    """The append's block writer (ops/pallas/paged_kv_append.py) against
    the row scatter it stands in for (`serving/block_pool.py _append`), K
    and V in one call, into layer 1 of the arenas: every element outside
    the null block equal, the other layer's included. Rows as
    `ragged_batch` draws them, their scatter targets from
    `block_pool.positions_to_slots`, the last lane idle. Returns the blocks
    written, an arena."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.paged_kv_append import (
        append_blocks,
        paged_kv_append,
    )
    from paddle_tpu.serving.block_pool import positions_to_slots

    seed = int(jax.random.randint(key, (), 0, 2**30))
    m, _ = ragged_batch(seed, batch=batch, width=width,
                        block_size=block_size, max_blocks=max_blocks)
    tables, past, counts, live = (np.array(m[k]) for k in (
        "tables", "q_start", "q_lens", "kv_live"))
    counts[-1] = 0
    slots, offs = map(np.stack, zip(*(
        positions_to_slots(block_size, tables[i, :live[i]], past[i],
                           counts[i], width)
        for i in range(batch))))
    shape = (batch, width, heads, head_dim)
    kk, kv = jax.random.split(key)
    if quant:
        old = arenas["k8"], arenas["v8"]
        new = tuple(jax.random.randint(x, shape, -127, 128).astype(
            jnp.float32) for x in (kk, kv))
    else:
        old = arenas["k"], arenas["v"]
        new = tuple(jax.random.normal(x, shape, jnp.bfloat16)
                    for x in (kk, kv))

    @jax.jit
    def compare(old, new, slots, offs):
        got = paged_kv_append(old, 1, new, slots, offs, interpret=interpret)
        heads_ = jnp.arange(heads, dtype=jnp.int32)
        same, moved = [], []
        for g, a, x in zip(got, old, new):
            want = a.at[1, heads_, slots[..., None], offs[..., None]].set(
                x.astype(a.dtype))
            same.append(jnp.all(g[:, :, 1:] == want[:, :, 1:]))
            moved.append(jnp.sum(want[:, :, 1:] != a[:, :, 1:]))
        return jnp.stack(same), jnp.stack(moved)

    same, moved = compare(old, new, jnp.asarray(slots), jnp.asarray(offs))
    what = (f"append writer width {width} "
            f"{'int8' if quant else 'bf16'} arena")
    require(int(np.min(moved)) > 0, f"{what}: the scatter moved nothing")
    require(bool(np.all(same)), f"{what}: differs from the row scatter "
            f"outside the null block (K, V equal: {np.asarray(same)})")
    return append_blocks(slots)


def check_flash_kernels(key, *, batch, seq, heads, head_dim):
    """The flash forward and both backward kernels against _attention_xla
    and its jax.grad, through the dispatch the model calls."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention import (
        _attention_xla,
        flash_attention_array,
    )

    kq, kk, kv, kg = jax.random.split(key, 4)
    shape = (batch, seq, heads, head_dim)
    q, k, v = (jax.random.normal(x, shape, jnp.bfloat16)
               for x in (kq, kk, kv))
    g = jax.random.normal(kg, shape, jnp.float32)

    def kernel_loss(q, k, v):
        o = flash_attention_array(q, k, v, causal=True)
        return (o.astype(jnp.float32) * g).sum(), o

    def ref_loss(q, k, v):
        o = _attention_xla(q, k, v, causal=True)
        return (o * g).sum(), o

    (_, out), grads = jax.jit(jax.value_and_grad(
        kernel_loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
    with jax.default_matmul_precision("highest"):
        (_, ref), ref_grads = jax.jit(jax.value_and_grad(
            ref_loss, argnums=(0, 1, 2), has_aux=True))(
                *(x.astype(jnp.float32) for x in (q, k, v)))
    tag = f"flash {shape}"
    worst = {"out": _close(out, ref, OUT_RTOL, OUT_ATOL, f"{tag} forward")}
    for name, got, want in zip(("dq", "dk", "dv"), grads, ref_grads):
        worst[name] = _close(got, want, GRAD_RTOL, GRAD_ATOL, f"{tag} {name}")
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        require(rel < GRAD_NORM_TOL,
                f"{tag} {name}: norm-wise error {rel:.4g} >= {GRAD_NORM_TOL}")
    return worst


def check_grouped_matmul(key, *, rows, inner, outer, groups, hit):
    """The routed experts' grouped matmul through its dispatch
    (ops/pallas/grouped_matmul.py) against `jax.lax.ragged_dot` in float32
    at `highest` over the same bfloat16 values: `hit` of `groups` groups
    hold a quarter of the `rows` rows between them, unevenly, and the rows
    behind them hold NaN, which nothing may read. Both multiply exact
    products of bfloat16 and sum in float32, so they differ in the order
    of `inner` sums: 1e-3 of the result's scale holds with room, and a
    wrong group, tile or mask moves values by the scale itself. Returns the
    worst error over that scale."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.grouped_matmul import grouped_matmul

    kx, kw, kh, ks = jax.random.split(key, 4)
    x = jax.random.normal(kx, (rows, inner), jnp.bfloat16)
    w = (0.05 * jax.random.normal(kw, (groups, inner, outer))).astype(
        jnp.bfloat16)
    live = np.sort(np.asarray(jax.random.permutation(kh, groups))[:hit])
    share = np.asarray(jax.random.dirichlet(ks, jnp.full((hit,), 0.5)))
    sizes = np.zeros(groups, np.int32)
    sizes[live] = 1 + np.floor(share * max(rows // 4 - hit, 0)).astype(
        np.int32)
    total = int(sizes.sum())
    require(0 < total < rows, f"grouped matmul: {total} of {rows} rows")
    x = x.at[total:].set(jnp.nan)
    got = np.asarray(jax.jit(grouped_matmul)(x, w, jnp.asarray(sizes)))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda x, w, s: jax.lax.ragged_dot(
            x, w, s, preferred_element_type=jnp.float32))(
                x[:total].astype(jnp.float32), w.astype(jnp.float32),
                jnp.asarray(sizes)))
    what = f"grouped matmul {rows} x {inner} x {outer}, {hit} of {groups}"
    require(got.shape == (rows, outer) and got.dtype == np.float32,
            f"{what}: {got.dtype}{got.shape}")
    require(not got[total:].any(),
            f"{what}: the rows behind the groups are not zeros")
    scale = float(np.abs(want).max())
    err = float(np.abs(got[:total] - want).max()) / scale
    require(err < 1e-3, f"{what}: off by {err:.3g} of the result's scale")
    return err


def check_ssm_state_update(key, *, layers, lanes, heads, head_dim, state,
                           groups):
    """The decode group's Mamba state update (ops/pallas/ssm_state_update.py)
    on a leaf of `layers` layers, layer 1 of it, against `ssm_chunk` at
    width 1 in float32 at `highest` (models/nemotron_h.py): lane 0 starts
    from zeros, lane 1 carries no token. `y` and the new state differ in
    float32's order of sums only: 1e-5 of their scale holds with room, and a
    wrong lane, head, group or decay moves them by the scale itself. The
    idle lane and every other layer must come back bit for bit. Returns the
    worst error over the scale."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.nemotron_h import ssm_chunk
    from paddle_tpu.ops.pallas.ssm_state_update import ssm_state_update

    ks = jax.random.split(key, 6)
    live = jnp.arange(lanes) != 1
    fresh = jnp.arange(lanes) == 0
    S = jax.random.normal(ks[0], (layers, lanes, heads, head_dim, state))
    x = jnp.where(live[:, None, None],
                  jax.random.normal(ks[1], (lanes, heads, head_dim)), 0.0)
    B = jax.random.normal(ks[2], (lanes, groups, state))
    C = jax.random.normal(ks[3], (lanes, groups, state))
    dt = jnp.where(live[:, None], jax.random.uniform(
        ks[4], (lanes, heads), minval=0.001, maxval=0.1), 0.0)
    A = -jax.random.uniform(ks[5], (heads,), minval=1.0, maxval=16.0)
    with jax.default_matmul_precision("highest"):
        want_y, want_S = jax.jit(lambda S: ssm_chunk(
            x[:, None], B[:, None], C[:, None], dt[:, None], A,
            jnp.where(fresh[:, None, None, None], 0.0, S[1])))(S)
    want_y, want_S = np.asarray(want_y[:, 0]), np.asarray(want_S)
    before = np.asarray(S)
    y, S = jax.jit(lambda S: ssm_state_update(S, 1, x, B, C, dt, A, fresh,
                                              live), donate_argnums=0)(S)
    y, S = np.asarray(y), np.asarray(S)
    what = (f"ssm state update {layers} x {lanes} x {heads} x {head_dim} x "
            f"{state}, {groups} groups")
    require(np.array_equal(S[1, 1], before[1, 1]),
            f"{what}: the idle lane's state moved")
    require(all(np.array_equal(S[i], before[i])
                for i in range(layers) if i != 1),
            f"{what}: another layer's state moved")
    err = max(float(np.abs(got - want)[2:].max() / np.abs(want[2:]).max())
              for got, want in ((y, want_y), (S[1], want_S)))
    err = max(err, float(np.abs(S[1, 0] - want_S[0]).max()
                         / np.abs(want_S[0]).max()))
    require(err < 1e-5, f"{what}: off by {err:.3g} of the scale")
    return err


def kernel_phase(*, ragged_shapes, widths, flash_shapes, grouped_shapes=(),
                 state_shapes=(), seed=0):
    """Every kernel variant the later phases compile, at their shapes."""
    import jax

    from paddle_tpu.ops.pallas import _backend

    require(_backend.use_pallas(), "the Pallas kernels are off "
            "(PADDLE_TPU_DISABLE_PALLAS set, or no tpu backend and no "
            "interpret knob)")
    interpret = _backend.interpret_mode()
    require(not (_on_tpu() and interpret),
            "PADDLE_TPU_FORCE_PALLAS_INTERPRET is set: on a tpu backend this "
            "check is about the compiled kernels")
    key = jax.random.PRNGKey(seed)
    for shape in ragged_shapes:
        key, sub = jax.random.split(key)
        arenas = ragged_arenas(sub, **shape)
        for width in widths:
            for quant in (False, True):
                key, sub = jax.random.split(key)
                err = check_ragged_kernel(sub, arenas, quant=quant,
                                          width=width, interpret=interpret,
                                          **shape)
                log(f"ragged kernel heads={shape['heads']} width={width} "
                    f"arena={'int8' if quant else 'bf16'}: worst |err| "
                    f"{err:.3g}")
                key, sub = jax.random.split(key)
                blocks = check_append_writer(sub, arenas, quant=quant,
                                             width=width,
                                             interpret=interpret, **shape)
                log(f"append writer heads={shape['heads']} width={width} "
                    f"arena={'int8' if quant else 'bf16'}: {blocks} blocks "
                    f"equal to the row scatter's")
        del arenas
    for shape in flash_shapes:
        key, sub = jax.random.split(key)
        worst = check_flash_kernels(sub, **shape)
        log(f"flash kernels {shape}: worst |err| "
            + ", ".join(f"{k} {v:.3g}" for k, v in worst.items()))
    for shape in grouped_shapes:
        key, sub = jax.random.split(key)
        err = check_grouped_matmul(sub, **shape)
        log(f"grouped matmul {shape}: worst |err| {err:.3g} of the scale")
    for shape in state_shapes:
        key, sub = jax.random.split(key)
        err = check_ssm_state_update(sub, **shape)
        log(f"ssm state update {shape}: worst |err| {err:.3g} of the scale")
    require(not _backend.XLA_FALLBACKS,
            f"a kernel's dispatch took the XLA path: "
            f"{dict(_backend.XLA_FALLBACKS)}")


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

async def _http(port, method, path, obj=None):
    """One loopback HTTP exchange -> (status, body bytes)."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    data = json.dumps(obj).encode() if obj is not None else b""
    writer.write((f"{method} {path} HTTP/1.1\r\nHost: smoke\r\n"
                  "Content-Type: application/json\r\n"
                  f"Content-Length: {len(data)}\r\n\r\n").encode() + data)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    with contextlib.suppress(ConnectionError, OSError):
        await writer.wait_closed()
    head, _, body = raw.partition(b"\r\n\r\n")
    return int(head.split(b" ")[1]), body


def _sse_tokens(body):
    """An SSE body -> (token ids, finish_reason, saw [DONE])."""
    toks, reason, done = [], None, False
    for line in body.decode().splitlines():
        if not line.startswith("data: "):
            continue
        payload = line[len("data: "):]
        if payload == "[DONE]":
            done = True
            continue
        choice = json.loads(payload)["choices"][0]
        toks.extend(choice["token_ids"])
        reason = choice["finish_reason"] or reason
    return toks, reason, done


def _quarter_check(name, arr, parts):
    """Every device holds exactly 1/parts of `arr`."""
    shards = arr.addressable_shards
    require(len({s.device for s in shards}) == parts,
            f"{name}: on {len(shards)} devices, not {parts}")
    for s in shards:
        require(s.data.nbytes * parts == arr.nbytes,
                f"{name}: device {s.device} holds {s.data.nbytes} of "
                f"{arr.nbytes} bytes, not 1/{parts}")


def serve_phase(model, *, max_batch, max_seq_len, prompt_lens, max_new,
                prefill_chunk=None, mesh=None, seed=0):
    """Serve `model` behind ServingServer on loopback: warm the program
    table, answer len(prompt_lens) concurrent requests plus the first
    prompt again (streamed; a prefix-cache hit), drain, and check what
    came back and what the engine counted. `prefill_chunk` None is the
    engine's default (128); the CPU test narrows it."""
    import jax
    import numpy as np

    from paddle_tpu.analysis.ir import collective_counts, parse_hlo_ops
    from paddle_tpu.serving import LLMEngine, ServingServer
    from paddle_tpu.serving.sharded import serving_collective_budget

    cfg = model.cfg
    engine = LLMEngine(model, block_size=16, max_batch=max_batch,
                       max_seq_len=max_seq_len, prefill_chunk=prefill_chunk,
                       spec_decoding=True, mesh=mesh, seed=seed)
    widths = engine.width_buckets
    require(len(widths) == 3 and widths[0] == 1
            and widths[1] == 1 + engine.num_spec_tokens
            and widths[2] == engine.prefill_chunk,
            f"width programs {widths}: expected decode, verify and chunk")
    require(max(prompt_lens) > engine.prefill_chunk,
            "no prompt is longer than prefill_chunk")
    tp = 1 if mesh is None else int(mesh)
    require(engine.mesh_info()["tp_degree"] == tp
            and engine.mesh_info()["device_count"] == tp,
            f"mesh_info {engine.mesh_info()} does not say {tp}")

    t0 = time.monotonic()
    compiled = engine.warmup()
    log(f"warm-up compiled {compiled} step programs {widths} in "
        f"{time.monotonic() - t0:.1f}s")
    counters = engine.metrics.counters
    expected = engine.expected_program_count()
    require(compiled == expected and counters["jit_traces"] == expected,
            f"after warm-up: {compiled} programs, {counters['jit_traces']} "
            f"traces, expected {expected}")

    lowered = (engine.lowered_step_programs()
               if _on_tpu() or tp > 1 else {})
    if _on_tpu():
        # read which path ran from the lowered programs, not from a config
        for name, low in lowered.items():
            # a layer's ragged attention call and its append's block
            # writer (one call for K and V in a float arena)
            n = low.as_text().count("tpu_custom_call")
            require(n == 2 * cfg.num_layers,
                    f"step program {name}: {n} tpu_custom_call for "
                    f"{cfg.num_layers} layers, expected two a layer")
        for name, arr in (("params", next(iter(engine._params.values()))),
                          ("arena", engine.pool.k)):
            plats = {d.platform for d in arr.devices()}
            require(plats == {"tpu"}, f"{name} live on {plats}, not tpu")
    if tp > 1:
        _quarter_check("k arena", engine.pool.k, tp)
        _quarter_check("v arena", engine.pool.v, tp)
        sharded = [k for k, s in engine._param_shardings.items()
                   if any(s.spec)]
        require(sharded, "no tp-sharded parameter")
        for k in sharded:
            _quarter_check(f"param {k}", engine._params[k], tp)
        # GSPMD puts the collectives in at compile time, so these are read
        # from the compiled programs (the ones warm-up just compiled)
        budget = serving_collective_budget(cfg, tp)
        for name, low in lowered.items():
            got = collective_counts(parse_hlo_ops(low.compile().as_text()))
            require(got == budget,
                    f"step program {name}: collectives {got} != "
                    f"serving_collective_budget {budget}")

    rs = np.random.RandomState(seed)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).tolist()
               for n in prompt_lens]

    async def drive():
        server = ServingServer(engine, host="127.0.0.1", port=0)
        await server.start()
        try:
            bodies = [{"prompt": p, "max_tokens": max_new} for p in prompts]
            # one sampled request beside the greedy ones
            bodies[1].update(temperature=0.8, top_k=40)
            wave = await asyncio.gather(*(
                _http(server.port, "POST", "/v1/completions", b)
                for b in bodies))
            # the first prompt again, streamed: its blocks are cached now
            again = await _http(
                server.port, "POST", "/v1/completions",
                {"prompt": prompts[0], "max_tokens": max_new,
                 "stream": True})
            health = await _http(server.port, "GET", "/healthz")
        finally:
            await server.shutdown(drain=True)
        return server, wave, again, health

    t0 = time.monotonic()
    server, wave, again, health = asyncio.run(drive())
    log(f"{len(wave) + 1} requests x {max_new} tokens answered in "
        f"{time.monotonic() - t0:.1f}s")

    outs = []
    for i, (status, body) in enumerate(wave):
        require(status == 200, f"request {i}: HTTP {status} {body[:200]!r}")
        choice = json.loads(body)["choices"][0]
        usage = json.loads(body)["usage"]
        require(usage["prompt_tokens"] == prompt_lens[i]
                and usage["completion_tokens"] == max_new
                and len(choice["token_ids"]) == max_new
                and choice["finish_reason"] == "length",
                f"request {i}: usage {usage}, finish "
                f"{choice['finish_reason']}, {len(choice['token_ids'])} "
                f"tokens, wanted {max_new}")
        outs.append(choice["token_ids"])
    status, body = again
    require(status == 200, f"streamed request: HTTP {status}")
    toks, reason, done = _sse_tokens(body)
    require(len(toks) == max_new and reason == "length" and done,
            f"streamed request: {len(toks)} tokens, finish {reason}, "
            f"[DONE] {done}")
    outs.append(toks)
    require(all(0 <= t < cfg.vocab_size for o in outs for t in o),
            "a token id is outside the vocabulary")
    require(health[0] == 200, f"/healthz: HTTP {health[0]}")

    require(counters["jit_traces"] == expected
            and engine.metrics.gauges.get("jit_retraces", 0) == 0,
            f"after traffic: {counters['jit_traces']} traces for {expected} "
            f"programs, jit_retraces "
            f"{engine.metrics.gauges.get('jit_retraces')}")
    require(counters.get("prefix_cache_hit_tokens", 0) > 0,
            "the repeated prompt counted no prefix-cache hit")
    require(not server.engine._thread.is_alive(),
            "engine thread alive after shutdown(drain=True)")
    require(engine.pool.num_free == engine.pool.num_blocks - 1,
            f"after drain {engine.pool.num_free} blocks free of "
            f"{engine.pool.num_blocks - 1}")
    steps = {k: counters.get(k, 0)
             for k in ("decode_steps", "mixed_steps", "verify_steps",
                       "generated_tokens", "prefix_cache_hit_tokens")}
    log(f"engine counted {steps}; repeated prompt decoded "
        f"{'the same' if outs[-1] == outs[0] else 'different'} tokens")
    engine.close()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def train_phase(model, *, degrees, batch, seq, steps=5, zero_stage=0,
                remat=False, seed=0):
    """`steps` AdamW steps of the compiled sharded train step on one
    seeded batch; the loss must be finite throughout and fall."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.analysis.ir import collective_counts, parse_hlo_ops
    from paddle_tpu.distributed.mesh import get_mesh, init_mesh, set_mesh
    from paddle_tpu.models.gpt import gpt_loss_fn
    from paddle_tpu.parallel.spmd import (
        make_sharded_train_step,
        mesh_donate_argnums,
        per_chip_opt_state_bytes,
        train_collective_budget,
    )

    cfg = model.cfg
    dp = int(degrees.get("dp", 1))
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    prev = get_mesh()
    mesh = init_mesh(degrees)
    try:
        step = make_sharded_train_step(model, gpt_loss_fn, opt, mesh,
                                       zero_stage=zero_stage, remat=remat)
        require(step.explicit_update == (zero_stage >= 2 and dp > 1),
                f"explicit ZeRO path is {step.explicit_update} at "
                f"zero_stage={zero_stage} dp={dp}")
        if _on_tpu():
            require(mesh_donate_argnums((0, 2)) == (0, 2),
                    "donation is off (mesh_donate_argnums is empty)")
        sds = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        lowered, _ = step.lower_step(sds, sds)
        text = lowered.as_text()
        if _on_tpu():
            # forward + dK/dV + dQ per layer, and the forward once more
            # when the layer is rematerialized
            n = text.count("tpu_custom_call")
            want = cfg.num_layers * (4 if remat else 3)
            require(n == want, f"train step: {n} tpu_custom_call, wanted "
                               f"{want} for {cfg.num_layers} layers")
        params, buffers, opt_state = step.init_state()
        if step.explicit_update:
            # the explicit path spells its collectives out (shard_map), so
            # the lowered program holds exactly the PR 19 shape on every
            # backend; what XLA makes of them is logged after the steps
            budget = train_collective_budget(len(params), dp)
            asked = {op: len(re.findall(
                rf"stablehlo\.{op.replace('-', '_')}\b", text))
                for op in budget}
            require(asked == budget, f"train step asks for {asked}, not "
                                     f"train_collective_budget {budget}")
            total = sum(int(x.nbytes)
                        for x in jax.tree_util.tree_leaves(opt_state))
            per_chip = per_chip_opt_state_bytes(opt_state)
            # scalar slots (the beta powers) replicate; everything
            # param-shaped is cut dp ways
            require(per_chip * dp <= total * 1.01,
                    f"a chip holds {per_chip} of {total} optimizer-state "
                    f"bytes, not 1/{dp}")
        rs = np.random.RandomState(seed)
        ids = rs.randint(0, cfg.vocab_size, (batch, seq + 1)).astype(np.int32)
        ids, labels = step.shard_batch(ids[:, :-1], ids[:, 1:])
        lr = jnp.asarray(1e-3, jnp.float32)
        losses = []
        for i in range(steps):
            t0 = time.monotonic()
            loss, params, buffers, opt_state = step(
                params, buffers, opt_state, lr, jax.random.PRNGKey(i),
                ids, labels)
            losses.append(float(np.asarray(loss)))
            log(f"train step {i}: loss {losses[-1]:.4f} "
                f"({time.monotonic() - t0:.1f}s)")
        if step.explicit_update:
            # the same program as the steps ran: a cache load, not a compile
            after = collective_counts(
                parse_hlo_ops(lowered.compile().as_text()))
            log(f"train step asks for {asked}; compiled by XLA for "
                f"{jax.default_backend()} it holds {after}")
        require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
        require(losses[-1] < losses[0],
                f"loss did not fall over {steps} steps: {losses}")
        if _on_tpu():
            plats = {d.platform for p in params.values()
                     for d in p.devices()}
            require(plats == {"tpu"}, f"params live on {plats}, not tpu")
    finally:
        set_mesh(prev)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _bf16(model):
    model.to(dtype="bfloat16")
    return model


def run(report, clock):
    """Every phase at full size. Raises on the first failed check."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_1p3b, gpt_small
    from paddle_tpu.ops.pallas import _backend

    four = jax.device_count() >= 4
    # gpt_1p3b serves 16 heads of 128 (4 a chip at tp=4) through 128-slot
    # block tables; gpt_small trains 12 heads of 64 at 1,024 tokens and
    # gpt_1p3b 16 heads of 128 at 2,048 (one sequence a chip at dp=4)
    serve_shape = dict(batch=8, head_dim=128, block_size=16, max_blocks=128)
    ragged = [dict(heads=16, **serve_shape)]
    flash = [dict(batch=8, seq=1024, heads=12, head_dim=64)]
    if four:
        ragged.append(dict(heads=4, **serve_shape))
        flash.append(dict(batch=1, seq=2048, heads=16, head_dim=128))
    # the hybrid configuration's routed experts (nemotron3s-ep4-serve): a
    # decode step's 704 pair rows over two thirds of 128 held experts, a
    # chunk-carrying step's 3,520 over all of them, latent 1,024 <-> 2,688
    grouped = [dict(rows=r, inner=k, outer=n, groups=128, hit=h)
               for r, h in ((704, 85), (3520, 128))
               for k, n in ((1024, 2688), (2688, 1024))]
    # its decode group's Mamba state: the leaf of 5 layers and 32 lanes
    state = [dict(layers=5, lanes=32, heads=128, head_dim=64, state=128,
                  groups=8)]
    with phase("kernels", clock, report):
        kernel_phase(ragged_shapes=ragged, widths=(1, 5, 128),
                     flash_shapes=flash, grouped_shapes=grouped,
                     state_shapes=state)

    # nine requests: eight at once, mixed lengths, one longer than a
    # prefill chunk (128); then the first again. No depth cut: 24 layers.
    serving = dict(max_batch=8, max_seq_len=2048, max_new=32,
                   prompt_lens=(48, 5, 17, 64, 100, 128, 200, 300))
    with phase("serve", clock, report):
        paddle.seed(0)
        serve_phase(_bf16(gpt_1p3b()), **serving)
    with phase("train", clock, report):
        paddle.seed(0)
        train_phase(_bf16(gpt_small()), degrees={"dp": 1}, batch=8,
                    seq=1024)
    if four:
        with phase("serve_4chip", clock, report):
            paddle.seed(0)
            serve_phase(_bf16(gpt_1p3b()), mesh=4, **serving)
        with phase("train_4chip", clock, report):
            paddle.seed(0)
            # one 2,048-token sequence a chip; remat keeps the 24 layers'
            # activations beside 1.3 B params, grads and the model's own
            # copy inside 16 GB
            train_phase(_bf16(gpt_1p3b()), degrees={"dp": 4}, batch=4,
                        seq=2048, zero_stage=2, remat=True)
    require(not _backend.XLA_FALLBACKS,
            f"a kernel's dispatch took the XLA path: "
            f"{dict(_backend.XLA_FALLBACKS)}")


def main():
    from importlib import metadata

    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    versions = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        with contextlib.suppress(metadata.PackageNotFoundError):
            versions[pkg] = metadata.version(pkg)
    log(f"device {json.dumps(device)} versions {json.dumps(versions)}")
    if device["platform"] != "tpu":
        print(f"chip_smoke: needs a tpu backend, found platform "
              f"{device['platform']!r} ({device['kind']}); not run",
              file=sys.stderr)
        return 2

    import paddle_tpu  # noqa: F401 — places the compile cache

    log(f"compile cache at {jax.config.jax_compilation_cache_dir}")
    clock, report = CompileClock(), {}
    t0 = time.monotonic()
    try:
        run(report, clock)
    except Exception:
        import traceback

        traceback.print_exc()
        print(f"chip_smoke: FAILED after {time.monotonic() - t0:.0f}s; "
              f"phases passed: {sorted(report)}", file=sys.stderr)
        return 1
    print(json.dumps({"phases": report,
                      "compile_s": round(clock.seconds, 1),
                      "cache_hits": clock.hits,
                      "cache_misses": clock.misses,
                      "wall_s": round(time.monotonic() - t0, 1),
                      "versions": versions}), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
