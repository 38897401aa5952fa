"""The control of a served model's `correct`: the reference put in the
program's place in the precision below the configuration's (`correct.py
lower_precision`), whose first choice at every position is held to the same
margin as the served tokens, and has to fail it. On the chip, at the cell's
own size, `run.py --control 1` reads it (PERF.md section 2 has the readings);
at a size a test run can hold the real model's control does not fail on
every seed (PERF.md, the same place), so here the arithmetic is held on a table
model whose numbers can be worked out by hand, and the rehearsal shows that a
control run reports the reading."""
import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from benchmark import correct  # noqa: E402

CONFIG = {"reference": "benchmark/tests/data/table_reference.py",
          "n_positions": 64}


def _table():
    """After token 1 the reference puts 3 first (8.1875, what bfloat16 keeps
    of 8.2) and 2 second (8.0); after 3 it puts 1 first. Column 3 also holds
    a 10, so float8 with a scale a column keeps 8.1875 as 352 / 448 x 10 =
    7.857 and 8.0 (its column's largest) whole: in float8 the order turns,
    by 0.1875. int8's 127 levels keep it."""
    import jax.numpy as jnp

    t = np.zeros((8, 8), np.float32)
    t[1, 3], t[1, 2], t[3, 1], t[0, 3] = 8.2, 8.0, 5.0, 10.0
    return {"table": jnp.asarray(t, jnp.bfloat16)}


def _samples(n=5, alter=None):
    out = []
    for i in range(n):
        tokens = [3, 1] * 6
        if alter is not None and i == alter:
            tokens[4] = 2           # where the program should have said 3
        out.append(SimpleNamespace(
            index=i, prompt=[1], tokens=tokens, shared_tokens=0,
            ended=1.0 if i else None, failed=False))
    return out


def _serving(samples, control=()):
    said = []
    ok, compared = correct.serving(CONFIG, _table(), samples, 7, said.append,
                                   control=control)
    return ok, compared, said


def test_the_sound_tokens_are_correct_and_an_altered_one_is_not():
    ok, compared, _ = _serving(_samples())
    assert ok and compared == {
        "logit_deficit": {"value": 0.0, "limit": correct.SERVE_MARGIN}}
    ok, compared, _ = _serving(_samples(alter=2))
    assert not ok
    assert compared["logit_deficit"]["value"] == pytest.approx(0.1875)


def test_the_control_in_float8_fails_the_margin_and_int8_does_not():
    ok, compared, said = _serving(_samples(), control=["fp8", "int8"])
    assert compared["logit_deficit"]["value"] == 0.0
    assert compared["control.fp8_deficit"] == {
        "value": pytest.approx(0.1875), "limit": correct.SERVE_MARGIN}
    assert compared["control.int8_deficit"]["value"] == 0.0
    # the control's run is not correct though every served token is
    assert not ok
    assert any("control fp8" in line and "6 of 12" in line for line in said)
    assert _serving(_samples(), control=["int8"])[0]


def test_lower_precision_rounds_matrices_a_column_and_leaves_vectors():
    import jax
    import jax.numpy as jnp

    w = (jax.random.normal(jax.random.PRNGKey(0), (512, 64)) * 0.02
         ).astype(jnp.bfloat16)
    params = {"w": w, "b": jnp.arange(64, dtype=jnp.bfloat16)}
    err = {}
    for kind in ("fp8", "int8"):
        low = correct.lower_precision(params, kind)
        assert low["w"].dtype == w.dtype and (low["b"] == params["b"]).all()
        d = np.asarray(low["w"], np.float32) - np.asarray(w, np.float32)
        err[kind] = float(np.sqrt((d ** 2).mean())
                          / np.asarray(w, np.float32).std())
        # a column's largest value is kept (to bfloat16's own rounding)
        top = np.abs(np.asarray(w, np.float32)).argmax(axis=0)
        assert np.allclose(np.asarray(low["w"], np.float32)[top, range(64)],
                           np.asarray(w, np.float32)[top, range(64)],
                           rtol=2 ** -8)
    # 255 levels over +-absmax: about 1 % of a weight's spread; three bits
    # of mantissa: 2-3 %, a few times bfloat16's 0.2 %
    assert 0.005 < err["int8"] < 0.02 < err["fp8"] < 0.05
    with pytest.raises(ValueError, match="no control precision"):
        correct.lower_precision(params, "int4")


def test_a_control_run_of_the_rehearsal_reports_the_controls_reading():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(REPO / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "c1p3b-serve.rollout", "--seed", "5", "--seconds", "20", "--trace",
         "0", "--platform", "cpu", "--control", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line["compared"]) == ["logit_deficit", "control.fp8_deficit"]
    low = line["compared"]["control.fp8_deficit"]
    limit = correct.SERVE_MARGIN
    assert low["limit"] == limit and low["value"] >= 0.0
    assert line["correct"] == (
        low["value"] <= limit
        and line["compared"]["logit_deficit"]["value"] <= limit)
    assert f"compared control.fp8_deficit: {low['value']} limit {limit}" \
        in proc.stderr
