"""The harness end to end, as the rehearsal runs it (`--platform cpu`, tiny
sizes, kernels interpreted): the last line is the contract's, and a new
configuration, mix, cell and reader are new files plus one entry each."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "compared"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}


def _run(root, *args, platform=("--platform", "cpu")):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(REPO / ".jax_cache"))
    return subprocess.run(
        [sys.executable, "benchmark/run.py", *args, *platform], cwd=root,
        env=env, capture_output=True, text=True, timeout=900)


def _last_line(proc):
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_no_chip_no_result():
    proc = _run(REPO, "--workload", "c1p3b-serve.rollout", "--seed", "1",
                "--seconds", "2", "--trace", "0", platform=())
    assert proc.returncode != 0
    assert not proc.stdout.strip()
    assert "needs platform 'tpu'" in proc.stderr


def test_last_line_holds_the_contracts_keys_and_no_other():
    proc = _run(REPO, "--workload", "c1p3b-serve.rollout", "--seed", "1",
                "--seconds", "20", "--trace", "0")
    line = _last_line(proc)
    assert set(line) == CONTRACT_KEYS
    # each number compared beside its limit: the line's last key, and the
    # last lines on standard error
    assert list(line)[-1] == "compared"
    deficit = line["compared"]["logit_deficit"]
    assert 0 <= deficit["value"] <= deficit["limit"] == 0.12
    said = proc.stderr.strip().splitlines()
    assert said[-1] == "correct: True"
    assert said[-2] == (f"compared logit_deficit: {deficit['value']} "
                        "limit 0.12")
    assert set(line["device"]) == DEVICE_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4
    # a CPU run reports no timing under a metric's name
    assert line["metrics"] == {}


def test_a_training_window_holds_the_steps_its_mix_states():
    line = _last_line(_run(
        REPO, "--workload", "c590m-train.pretrain", "--seed", "2",
        "--seconds", "30", "--trace", "0"))
    steps = json.loads((REPO / "benchmark/traffic/packed-2k.json")
                       .read_text())["rehearsal"]["window"]["steps"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == steps
    assert list(line["compared"]) == ["first_loss_gap", "losses_not_finite",
                                      "last_loss"]


def test_a_new_configuration_mix_cell_and_reader_are_new_files(tmp_path):
    """A temporary copy of the benchmark gets a throw-away configuration, mix
    and per-layer reader as new files and one entry each in BENCHMARK.json;
    no file that was there is edited, and the new cell runs."""
    shutil.copytree(REPO / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    (tmp_path / "paddle_tpu").symlink_to(REPO / "paddle_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}

    config = json.loads((REPO / "benchmark/configs/c1p3b-serve.json")
                        .read_text())
    config.update(name="throwaway-serve", n_layer=3)
    config["rehearsal"]["n_layer"] = 3
    (tmp_path / "benchmark/configs/throwaway-serve.json").write_text(
        json.dumps(config))
    mix = json.loads((REPO / "benchmark/traffic/rollout.json").read_text())
    mix.update(name="throwaway")
    mix["rehearsal"]["output"] = {"median": 20, "min": 16, "max": 24}
    (tmp_path / "benchmark/traffic/throwaway.json").write_text(
        json.dumps(mix))
    (tmp_path / "benchmark/layer_metrics/throwaway.finished.py").write_text(
        '"""Requests the engine finished in the window."""\n\n\n'
        'def read(run):\n'
        '    return run.counters.get("requests_finished", 0)\n')

    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "throwaway-serve", "source": "test",
        "file": "benchmark/configs/throwaway-serve.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "throwaway-serve.throwaway", "config": "throwaway-serve",
        "traffic": "throwaway", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "throwaway.finished", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "test", "moves": "out_tok_s",
        "workloads": ["throwaway-serve.throwaway"]})
    for m in bench["end_to_end"]:
        if m["name"] == "out_tok_s":
            m["workloads"].append("throwaway-serve.throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    line = _last_line(_run(
        tmp_path, "--workload", "throwaway-serve.throwaway", "--seed", "3",
        "--seconds", "20", "--trace", "1"))
    assert line["correct"] is True
    assert line["metrics"]["throwaway.finished"]["value"] >= 1
    after = {p: p.read_bytes() for p in before}
    assert after == before
