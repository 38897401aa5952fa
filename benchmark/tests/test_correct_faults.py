"""`correct` comes out false when the timed path is broken underneath: the
rehearsal (which skips the harness's look for a chip) drives a whole run of
the serving cell with the program's `_emit` altering a token where it is
produced, once for every fault a one-chip serving cell can have. The same
run with nothing broken reads true (`test_harness.py`)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

DRIVER = """
import runpy, sys
sys.path.insert(0, {root!r})
from paddle_tpu.serving import engine

emit, seen = engine.LLMEngine._emit, [0]

def broken(self, req, token):
    seen[0] += 1
    if {when}:
        token = (token + 1) % self.model.cfg.vocab_size
    return emit(self, req, token)

engine.LLMEngine._emit = broken
sys.argv = ["benchmark/run.py", "--workload", "c1p3b-serve.rollout",
            "--seed", "{seed}", "--seconds", "20", "--trace", "0",
            "--platform", "cpu"]
runpy.run_path("benchmark/run.py", run_name="__main__")
"""

FAULTS = {
    # one token in nine of every request, from its second on
    "a token in nine altered": "len(req.output_ids) % 9 == 1",
    # one single token of the whole load
    "one token altered": "seen[0] == 6",
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_an_altered_token_makes_the_run_incorrect(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_FORCE_PALLAS_INTERPRET="1",
               JAX_COMPILATION_CACHE_DIR=str(REPO / ".jax_cache"))
    proc = subprocess.run(
        [sys.executable, "-c",
         DRIVER.format(root=str(REPO), when=FAULTS[fault], seed=7)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    # the load itself was sound: every request answered at its full length
    assert line["failed"] == 0 and line["attempted"] >= 4
    assert line["correct"] is False
    deficit = line["compared"]["logit_deficit"]
    assert deficit["value"] > deficit["limit"]
    assert proc.stderr.strip().splitlines()[-1] == "correct: False"
