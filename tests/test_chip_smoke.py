"""chip_smoke.py on the CPU: the script's phase functions at `gpt_tiny`
size with the kernels interpreted, and the script itself refusing to run
without a tpu backend. The same functions run at full size on the chip
(`python chip_smoke.py`); checks only a tpu backend can meet (device
placement, `tpu_custom_call` counts) are skipped here by the script itself.
"""
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import gpt_tiny

import chip_smoke  # tests/conftest.py puts the repo root on sys.path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _interpreted_kernels(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_DISABLE_PALLAS", raising=False)
    monkeypatch.setenv("PADDLE_TPU_FORCE_PALLAS_INTERPRET", "1")


def test_kernel_phase_interpreted():
    # gpt_tiny's attention: 8 heads of 32; tables of 4 blocks of 16
    chip_smoke.kernel_phase(
        ragged_shapes=[dict(batch=3, heads=2, head_dim=32, block_size=16,
                            max_blocks=4)],
        widths=(1, 5, 16),
        flash_shapes=[dict(batch=1, seq=128, heads=2, head_dim=32)],
    )


GROUPED = [dict(rows=48, inner=128, outer=256, groups=8, hit=5),
           dict(rows=48, inner=256, outer=128, groups=8, hit=8)]


def test_kernel_phase_grouped_matmul_interpreted():
    chip_smoke.kernel_phase(ragged_shapes=[], widths=(), flash_shapes=[],
                            grouped_shapes=GROUPED)


def test_kernel_phase_catches_a_wrong_grouped_matmul(monkeypatch):
    """A walk that lays a group's rows one row off fails the phase (at a
    shape of its own: a traced program is not traced again)."""
    from paddle_tpu.ops.pallas import grouped_matmul as module

    real = module._visits

    def shifted(sizes, rows, tm):
        off, *rest = real(sizes, rows, tm)
        return (off + 1, *rest)

    monkeypatch.setattr(module, "_visits", shifted)
    with pytest.raises(chip_smoke.SmokeFailure, match="grouped matmul"):
        chip_smoke.kernel_phase(ragged_shapes=[], widths=(), flash_shapes=[],
                                grouped_shapes=[dict(GROUPED[0], rows=64)])


STATE = dict(layers=3, lanes=4, heads=16, head_dim=8, state=128, groups=2)


def test_kernel_phase_ssm_state_update_interpreted():
    chip_smoke.kernel_phase(ragged_shapes=[], widths=(), flash_shapes=[],
                            state_shapes=[STATE])


def test_kernel_phase_catches_a_wrong_state_update(monkeypatch):
    """A state update that hands a lane's new state to the next lane fails
    the phase."""
    from paddle_tpu.ops.pallas import ssm_state_update as module

    real = module.ssm_state_update

    def shifted(S, layer, *a):
        y, S = real(S, layer, *a)
        return y, S.at[layer].set(S[layer, (jnp.arange(S.shape[1]) + 1)
                                    % S.shape[1]])

    monkeypatch.setattr(module, "ssm_state_update", shifted)
    with pytest.raises(chip_smoke.SmokeFailure, match="ssm state update"):
        chip_smoke.kernel_phase(ragged_shapes=[], widths=(), flash_shapes=[],
                                state_shapes=[STATE])


def test_kernel_phase_catches_a_wrong_kernel(monkeypatch):
    """The comparison has teeth: a kernel that ignores the int8 scales
    (what a dropped sidecar would do) fails the phase."""
    from paddle_tpu.ops.pallas import paged_attention as pa

    real = pa.ragged_paged_attention

    def unscaled(*a, k_scale=None, v_scale=None, **kw):
        ones = None if k_scale is None else k_scale * 0 + 1
        return real(*a, k_scale=ones, v_scale=ones, **kw)

    monkeypatch.setattr(pa, "ragged_paged_attention", unscaled)
    with pytest.raises(chip_smoke.SmokeFailure, match="int8 arena"):
        chip_smoke.kernel_phase(
            ragged_shapes=[dict(batch=3, heads=2, head_dim=32,
                                block_size=16, max_blocks=4)],
            widths=(5,), flash_shapes=[])


def test_kernel_phase_catches_a_wrong_writer(monkeypatch):
    """The writer's check has teeth too: tokens laid one row off their
    offsets fail the phase."""
    from paddle_tpu.ops.pallas import paged_kv_append as module

    real = module.paged_kv_append

    def shifted(arenas, layer, news, slots, offs, **kw):
        return real(arenas, layer, news, slots, (offs + 1) % 16, **kw)

    monkeypatch.setattr(module, "paged_kv_append", shifted)
    with pytest.raises(chip_smoke.SmokeFailure, match="append writer"):
        chip_smoke.kernel_phase(
            ragged_shapes=[dict(batch=3, heads=2, head_dim=32,
                                block_size=16, max_blocks=4)],
            widths=(5,), flash_shapes=[])


def test_serve_phase_tiny():
    paddle.seed(0)
    chip_smoke.serve_phase(
        gpt_tiny(), max_batch=2, max_seq_len=96, prefill_chunk=32,
        max_new=6, prompt_lens=(20, 5, 40))


def test_serve_phase_tiny_tp2():
    paddle.seed(0)
    chip_smoke.serve_phase(
        gpt_tiny(), max_batch=2, max_seq_len=96, prefill_chunk=32,
        max_new=4, prompt_lens=(20, 40), mesh=2)


def test_train_phase_tiny():
    paddle.seed(0)
    chip_smoke.train_phase(gpt_tiny(), degrees={"dp": 1}, batch=2, seq=128)


def test_train_phase_tiny_zero2_dp2():
    paddle.seed(0)
    chip_smoke.train_phase(gpt_tiny(), degrees={"dp": 2}, batch=2, seq=128,
                           zero_stage=2, remat=True)


def test_script_refuses_a_cpu_backend(tmp_path):
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu exits non-zero, names
    the platform it found, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=tmp_path)
    assert proc.returncode != 0
    assert "found platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout
