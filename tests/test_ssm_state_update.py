"""The decode group's Mamba state update in place (`ops/pallas/
ssm_state_update.py`), here on the CPU with the kernel interpreted: against
`ssm_chunk` at width 1 on random state, fresh lanes, idle rows (bit for bit)
and the hybrid cell's shape, one layer of a stacked leaf replaced and the
others left bit for bit; the dispatch in `models/nemotron_h.py
_mamba_paged` and its fallbacks, counted on a tpu backend; a served engine
whose decode steps take the kernel, against the XLA form's tokens."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models.nemotron_h import NemotronH, ssm_chunk
from paddle_tpu.ops.pallas import _backend
from paddle_tpu.ops.pallas import ssm_state_update as su
from paddle_tpu.serving import LLMEngine
from paddle_tpu.serving.block_pool import PagedGroup

from _nemotron_common import VOCAB, build
from _serving_common import WAVE, prompts_of, serve, wave_of


@pytest.fixture
def interpreted(monkeypatch):
    """The kernel interpreted wherever it applies, and no other kernel."""
    monkeypatch.setattr(su, "use_pallas", lambda: True)
    monkeypatch.setattr(su, "interpret_mode", lambda: True)


def _operands(layers, lanes, heads, P, N, groups, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    return dict(
        S=normal(layers, lanes, heads, P, N), x=normal(lanes, heads, P),
        B=normal(lanes, groups, N), C=normal(lanes, groups, N),
        dt=jnp.asarray(rng.uniform(0.001, 0.5, (lanes, heads)), jnp.float32),
        A=-jnp.asarray(rng.uniform(1.0, 16.0, heads), jnp.float32))


def _xla_form(o, layer, fresh, live):
    """`ssm_chunk` at width 1 on layer `layer`, as `_mamba_paged` calls it:
    `(y, the layer's new state)`."""
    S_in = jnp.where(fresh[:, None, None, None], 0, o["S"][layer])
    y, S = ssm_chunk(o["x"][:, None], o["B"][:, None], o["C"][:, None],
                     o["dt"][:, None], o["A"], S_in)
    return np.asarray(y[:, 0]), np.asarray(S)


# (layers, lanes, heads, head size, state size, groups), which lanes start
# from zeros and which carry no token
CASES = {
    "random": ((3, 4, 16, 8, 128, 2), [], []),
    "fresh": ((3, 4, 16, 8, 128, 2), [1, 3], []),
    "idle": ((3, 5, 16, 8, 128, 4), [0], [2, 4]),
    "two_blocks": ((2, 3, 64, 64, 128, 8), [2], [1]),
    # the hybrid cell's (nemotron3s-ep4-serve) leaf layer
    "cell": ((1, 32, 128, 64, 128, 8), [5], [3, 14]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ssm_chunk_at_width_one(interpreted, case):
    """`y` and the layer's new state within float32's order of sums; a
    fresh lane from zeros whatever it held; an idle row's state bit for bit
    even where it holds -0.0 and NaN (nothing computes it), its `y` the XLA
    form's; every other layer of the leaf bit for bit."""
    shape, fresh_at, idle_at = CASES[case]
    layers, lanes, heads, P, N, groups = shape
    o = _operands(*shape)
    fresh = jnp.isin(jnp.arange(lanes), jnp.asarray(fresh_at, jnp.int32))
    live = ~jnp.isin(jnp.arange(lanes), jnp.asarray(idle_at, jnp.int32))
    o["dt"] = jnp.where(live[:, None], o["dt"], 0.0)
    o["x"] = jnp.where(live[:, None, None], o["x"], 0.0)
    layer = layers // 2
    if idle_at:
        o["S"] = o["S"].at[layer, idle_at[0], 0, 0, :2].set(
            jnp.asarray([-0.0, jnp.nan]))
    before = np.asarray(o["S"])
    want_y, want_S = _xla_form(o, layer, fresh, live)
    y, S = jax.jit(su.ssm_state_update, static_argnums=1)(
        o["S"], layer, o["x"], o["B"], o["C"], o["dt"], o["A"], fresh, live)
    y, S = np.asarray(y), np.asarray(S)
    assert su.head_block(heads, P, N) is not None
    assert y.shape == (lanes, heads, P) and S.shape == before.shape
    keep = np.isin(np.arange(lanes), idle_at)
    scale = np.abs(want_y[~keep]).max()
    assert np.abs(y - want_y)[~keep].max() <= 1e-5 * scale
    assert np.abs(S[layer] - want_S)[~keep].max() \
        <= 1e-6 * np.abs(want_S[~keep]).max()
    for lane in idle_at:
        assert np.array_equal(S[layer, lane], before[layer, lane],
                              equal_nan=True)
        assert np.array_equal(np.signbit(S[layer, lane]),
                              np.signbit(before[layer, lane]))
    for other in set(range(layers)) - {layer}:
        assert np.array_equal(S[other], before[other])


def test_heads_go_in_blocks_of_a_megabyte_and_whole_lane_rows():
    assert su.head_block(128, 64, 128) == 32        # the hybrid cell
    assert su.head_block(64, 64, 128) == 32
    assert su.head_block(16, 8, 128) == 16          # 8 x 8 fills no row
    assert su.head_block(8, 8, 128) == 8            # all the heads
    assert su.head_block(1, 4096, 128) is None      # 2 MiB a head


@pytest.mark.parametrize("shape,dtype,why", [
    ((2, 4, 16, 8, 128), jnp.float32, None),
    ((2, 4, 16, 8, 128), jnp.bfloat16, "a bfloat16 state"),
    ((2, 4, 16, 8, 64), jnp.float32,
     "a state size that is no multiple of 128"),
    ((2, 4, 16, 12, 128), jnp.float32, "a head size that is no multiple of 8"),
    ((2, 4, 1, 4096, 128), jnp.float32, "no block of whole heads fits"),
], ids=["float32", "bfloat16", "state64", "head12", "huge_head"])
def test_why_a_leaf_takes_the_xla_form(interpreted, shape, dtype, why):
    assert su.why_xla(jax.ShapeDtypeStruct(shape, dtype)) == why


def test_kernels_off_take_the_xla_form(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    assert su.why_xla(jax.ShapeDtypeStruct((2, 4, 16, 8, 128),
                                           jnp.float32)) == "kernels off"


# -- the dispatch in the model ----------------------------------------------

def _decode_group(rows, live_rows, fresh_rows, lanes=None, width=1):
    q_lens = np.zeros(rows, np.int32)
    q_lens[live_rows] = width
    fresh = np.zeros(rows, np.int32)
    fresh[fresh_rows] = 1
    return PagedGroup(0, rows, width, None, None, None, None, None,
                      jnp.asarray(q_lens > 0, jnp.int32) + 0,
                      jnp.asarray(q_lens), fresh=jnp.asarray(fresh),
                      lanes=None if lanes is None else jnp.asarray(lanes))


def _layer_and_state(state_size, rows=4, seed=0):
    model = build(pattern="MM", mamba_num_heads=16, mamba_head_dim=8,
                  ssm_state_size=state_size, seed=seed)
    rng = np.random.default_rng(seed)
    spec = model.lane_state_spec()
    st = {name: jnp.asarray(rng.standard_normal((shape[0], rows)
                                                + shape[1:]),
                            dtype or jnp.float32)
          for name, (shape, dtype) in spec.items()}
    a = jnp.asarray(rng.standard_normal((rows, 1, model.cfg.hidden_size)),
                    jnp.float32)
    return model, st, a


def _run_layer(model, st, g, a, i=1):
    st = dict(st)
    part = NemotronH._mamba_paged(model.layers[i].mixer, i, st, g, a)
    return np.asarray(part), {k: np.asarray(v) for k, v in st.items()}


@pytest.fixture
def counted(monkeypatch):
    monkeypatch.setattr(_backend, "XLA_FALLBACKS",
                        type(_backend.XLA_FALLBACKS)())
    return _backend.XLA_FALLBACKS


def test_the_decode_group_takes_the_kernel_and_gives_the_xla_forms_layer(
        counted, monkeypatch):
    """A decode group of four lanes (one fresh, one idle) through Mamba
    layer 1 of two: the kernel's output, state and window against the XLA
    form's; layer 0's state and the idle lane's untouched; nothing
    counted."""
    model, st, a = _layer_and_state(128)
    g = _decode_group(4, [0, 1, 3], [1])
    want_part, want = _run_layer(model, st, g, a)
    calls = []
    real = su.ssm_state_update

    def spy(*args):
        calls.append(args[1])
        return real(*args)

    monkeypatch.setattr(su, "ssm_state_update", spy)
    monkeypatch.setattr(su, "use_pallas", lambda: True)
    monkeypatch.setattr(su, "interpret_mode", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    part, got = _run_layer(model, st, g, a)
    monkeypatch.undo()
    assert calls == [1] and not counted
    assert np.abs(part - want_part).max() <= 1e-5 * np.abs(want_part).max()
    assert np.abs(got["ssm"] - want["ssm"]).max() \
        <= 1e-6 * np.abs(want["ssm"]).max()
    assert np.array_equal(got["conv"], want["conv"])
    before = np.asarray(st["ssm"])
    assert np.array_equal(got["ssm"][0], before[0])
    assert np.array_equal(got["ssm"][1, 2], before[1, 2])


@pytest.mark.parametrize("state_size,kernels,why", [
    (16, True, "a state size that is no multiple of 128"),
    (128, False, "kernels off"),
], ids=["state16", "kernels_off"])
def test_the_decode_groups_fallback_is_counted_on_a_tpu_backend(
        counted, monkeypatch, state_size, kernels, why):
    model, st, a = _layer_and_state(state_size)
    g = _decode_group(4, [0, 2], [])
    want_part, want = _run_layer(model, st, g, a)
    assert not counted                     # a cpu backend counts nothing
    if kernels:
        monkeypatch.setattr(su, "use_pallas", lambda: True)
    else:
        monkeypatch.setenv("PADDLE_TPU_DISABLE_PALLAS", "1")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    part, got = _run_layer(model, st, g, a)
    monkeypatch.undo()
    assert counted == {f"ssm_state_update: {why}": 1}
    assert np.array_equal(part, want_part)
    assert np.array_equal(got["ssm"], want["ssm"])


@pytest.mark.parametrize("width,lanes", [(8, [2, 4]), (8, None)],
                         ids=["chunk_group", "rectangle"])
def test_other_groups_take_the_xla_form_uncounted(counted, monkeypatch,
                                                  width, lanes):
    """A chunk group (its rows name lanes; 4 names none) and a wide
    rectangle are the XLA form's, and not fallbacks."""
    model, st, a = _layer_and_state(128)
    rows = 2 if lanes else 4
    a = jnp.tile(a[:rows], (1, width, 1))
    g = _decode_group(rows, [0], [0], lanes=lanes, width=width)
    want_part, want = _run_layer(model, st, g, a)
    monkeypatch.setattr(su, "ssm_state_update", None)     # not reached
    monkeypatch.setattr(su, "use_pallas", lambda: True)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    part, got = _run_layer(model, st, g, a)
    monkeypatch.undo()
    assert not counted
    assert np.array_equal(part, want_part)
    assert np.array_equal(got["ssm"], want["ssm"])


def test_the_chunk_group_reads_and_writes_only_its_lanes():
    """Lane 2's row carries a fresh chunk, the idle row names lane 4 (one
    past the last): lane 2's state is the chunk's from zeros, every other
    lane's and layer's bit for bit."""
    model, st, a = _layer_and_state(128)
    a = jnp.tile(a[:2], (1, 8, 1))
    g = _decode_group(2, [0], [0], lanes=[2, 4], width=8)
    _, got = _run_layer(model, st, g, a)
    before = np.asarray(st["ssm"])
    mixer = model.layers[1].mixer
    _, S, _ = mixer(a[:1], jnp.zeros_like(st["ssm"][1, :1]),
                    jnp.zeros_like(st["conv"][1, :1]),
                    jnp.ones((1, 8), bool), jnp.full((1,), 8, jnp.int32))
    assert np.abs(got["ssm"][1, 2] - np.asarray(S[0])).max() < 1e-6
    for lane in (0, 1, 3):
        assert np.array_equal(got["ssm"][1, lane], before[1, lane])
    assert np.array_equal(got["ssm"][0], before[0])


# -- served ------------------------------------------------------------------

GROUPED = dict(block_size=4, max_batch=4, max_seq_len=96, prefill_chunk=8,
               token_budget=16, seed=11)


@pytest.mark.parametrize("knobs", [
    dict(temperature=0.0), dict(temperature=0.8, top_k=20, top_p=0.95)],
    ids=["greedy", "seeded"])
def test_a_served_engine_on_the_kernel_gives_the_xla_forms_tokens(
        monkeypatch, knobs):
    """`WAVE` through an engine whose decode groups take the kernel
    (interpreted) and through one whose take the XLA form: the same tokens,
    greedy and sampled, and the lanes' state within float32's order of
    sums."""
    kw = dict(pattern="MEM*E", experts_held=4, experts_from=4,
              mamba_num_heads=16, mamba_head_dim=8, ssm_state_size=128)
    wave = wave_of(prompts_of([n for _, n, _ in WAVE], VOCAB, 4))
    xla = LLMEngine(build(**kw), **GROUPED)
    want, want_plans = serve(xla, wave, **knobs)
    calls = []
    real = su.ssm_state_update

    def spy(*args):
        calls.append(args[1])
        return real(*args)

    monkeypatch.setattr(su, "ssm_state_update", spy)
    monkeypatch.setattr(su, "use_pallas", lambda: True)
    monkeypatch.setattr(su, "interpret_mode", lambda: True)
    kernel = LLMEngine(build(**kw), **GROUPED)
    got, plans = serve(kernel, wave, **knobs)
    # two Mamba layers, traced once for each of the two programs
    assert sorted(calls) == [0, 0, 1, 1]
    assert plans == want_plans and got == want
    for name, state in kernel.pool.lane_state.items():
        x, y = np.asarray(state), np.asarray(xla.pool.lane_state[name])
        assert np.abs(x - y).max() <= 1e-5 * np.abs(y).max(), name
