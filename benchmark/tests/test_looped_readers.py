"""The looped decoder's counts (`reduce/looped.py`) and the three readers PR 34
added, each on a hand-made `Run`: the numbers asserted were worked out by hand
at small sizes. A program whose spans carry no `loop_steps` or `kv_tokens`
(the parent of that PR, or any run of a GPT) reads as None and never raises."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from benchmark import spec  # noqa: E402
from benchmark.record import Run  # noqa: E402
from benchmark.reduce import looped  # noqa: E402

# H 4, A = 2 heads x 3 = 6, F 5, L 2, T 3, V 7: a layer's matrices hold
# 4 x 4 x 6 + 3 x 4 x 5 = 156 elements
CONFIG = {"hidden_size": 4, "num_attention_heads": 2, "head_dim": 3,
          "intermediate_size": 5, "num_hidden_layers": 2,
          "total_ut_steps": 3, "vocab_size": 7, "dtype": "bfloat16"}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def _read(name, run):
    return spec.load_reader("per_layer", name).read(run)


def _run(steps, config=CONFIG, t0=0.0, t1=10.0):
    run = Run({}, {"name": "cell"}, config, {}, None, V5E, None, 0.0)
    run.t0, run.t1, run.engine_steps = t0, t1, steps
    return run


def _step(i, kind, start, end, **args):
    return {"id": i, "kind": kind, "start": start, "end": end,
            "phases": {}, "args": {"step": i, "kind": kind, **args}}


def test_counts_follow_the_published_keys():
    assert looped.sizes(CONFIG) == (4, 6, 5, 2, 3, 7, 2)
    assert looped.layer_matrix_elements(CONFIG) == 156
    # 10 fed tokens through 3 passes of 2 layers, the head over 2 rows
    assert looped.serve_flops(CONFIG, 10, 2) == \
        2 * (10 * 3 * 2 * 156 + 2 * 7 * 4)
    # weights once a pass and the head, 2 bytes each; 5 tokens of context in
    # 6 slabs, K and V of width 6
    assert looped.decode_stream_bytes(CONFIG, 5) == \
        (3 * 2 * 156 + 7 * 4) * 2 + 5 * 6 * 2 * 6 * 2


def test_loop_mfu_counts_every_pass_of_the_windows_steps():
    steps = [_step(1, "mixed", 0.0, 1.0, fed_tokens=40, emitted_tokens=1,
                   loop_steps=3, cache_slabs=6),
             _step(2, "decode", 1.0, 2.0, fed_tokens=8, emitted_tokens=8,
                   loop_steps=3, cache_slabs=6),
             # ends after the window: not read
             _step(3, "decode", 9.5, 10.5, fed_tokens=8, emitted_tokens=8,
                   loop_steps=3, cache_slabs=6)]
    value, n = _read("step.loop_mfu", _run(steps))
    assert n == 2
    assert value == pytest.approx(
        100.0 * looped.serve_flops(CONFIG, 48, 9) / (10.0 * 197e12))


def test_decode_stream_share_is_the_median_over_decode_steps():
    # at 819 GB/s the three decode steps' reads take b / 819e9 seconds
    b = [looped.decode_stream_bytes(CONFIG, k) for k in (5, 50, 500)]
    steps = [_step(1, "decode", 0.0, 1.0, kv_tokens=5),
             _step(2, "mixed", 1.0, 2.0, kv_tokens=999),
             _step(3, "decode", 2.0, 4.0, kv_tokens=50),
             _step(4, "decode", 4.0, 4.5, kv_tokens=500)]
    value, n = _read("loop.decode_stream_share", _run(steps))
    shares = sorted(100.0 * x / 819e9 / d
                    for x, d in zip(b, (1.0, 2.0, 0.5)))
    assert n == 3 and value == pytest.approx(shares[1])


@pytest.mark.parametrize("name", ["step.loop_mfu",
                                  "loop.decode_stream_share",
                                  "kernel.loop_ragged_decode_roofline"])
def test_a_program_without_the_spans_reads_as_none(name):
    gpt_steps = [_step(1, "decode", 0.0, 1.0, fed_tokens=8,
                       emitted_tokens=8)]
    assert _read(name, _run(gpt_steps)) is None
    assert _read(name, _run([])) is None
    assert _read(name, _run(gpt_steps, config={"n_embd": 8})) is None
