"""Launcher multi-node rendezvous (VERDICT r3 item 8): two 'nodes' (local
launch processes) must resolve ranks, the peer endpoint table and the per-job
RPC authkey through the rank-0 TCPStore WITHOUT any pre-set rank/endpoint env.

Reference: launch/controllers/master.py:65 (HTTP master), :177 (etcd).

The master port is picked dynamically per attempt (the old fixed 29780
collided with unrelated listeners under concurrent bench load — the PR 14
flake), and a collision-shaped failure retries on a fresh port instead of
failing the run: the property under test is the rendezvous protocol, not
this host's port map.
"""
import json
import os
import socket
import subprocess
import sys
import tempfile

import numpy as np

# rendezvous (main.py _RDZV_PORT_OFFSET): the TCPStore listens at
# master_port + 5, and per-rank trainer endpoints at master_port + 100+r
# — the whole window must be free, not just the coordinator port
_PORT_SPAN = (0, 5, 100, 101)


def _free_master_port():
    """A master port whose rendezvous-derived port window is currently
    free. Best-effort (another process may grab one between probe and
    bind) — the caller retries with a fresh pick on failure."""
    for _ in range(64):
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            base = s.getsockname()[1]
        if base + 101 > 65535:
            continue
        try:
            for off in _PORT_SPAN[1:]:
                with socket.socket() as probe:
                    probe.bind(("127.0.0.1", base + off))
            return base
        except OSError:
            continue
    raise RuntimeError("no free rendezvous port window found")


def _run_rendezvous_once(td, script, env, port):
    procs = [
        subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--master", f"127.0.0.1:{port}", "--nnodes", "2",
             "--log_dir", os.path.join(td, f"log{i}"), "--", script],
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out))
    return outs


def test_two_nodes_rendezvous_without_preset_env():
    with tempfile.TemporaryDirectory() as td:
        script = os.path.join(td, "probe.py")
        with open(script, "w") as f:
            f.write(
                "import os, json\n"
                "print('PROBE ' + json.dumps({\n"
                "    'rank': os.environ.get('PADDLE_TRAINER_ID'),\n"
                "    'eps': os.environ.get('PADDLE_TRAINER_ENDPOINTS'),\n"
                "    'key': os.environ.get('PADDLE_RPC_AUTHKEY'),\n"
                "}))\n"
            )
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "NODE_RANK"))}
        env["JAX_PLATFORMS"] = "cpu"
        outs = None
        for attempt in range(3):
            run_dir = os.path.join(td, f"try{attempt}")
            os.makedirs(run_dir)
            outs = _run_rendezvous_once(
                run_dir, script, env, _free_master_port())
            if all(rc == 0 for rc, _ in outs):
                td_run = run_dir
                break
            # a lost port race looks like a nonzero exit with a
            # connect/bind complaint — retry on a fresh window; any
            # OTHER failure is the protocol breaking and must surface
            combined = "\n".join(out for _, out in outs).lower()
            if not any(s in combined for s in
                       ("address already in use", "connection refused",
                        "timed out", "timeout")):
                break
        for rc, out in outs:
            assert rc == 0, out[-2000:]

        probes = []
        for i in range(2):
            log_root = os.path.join(td_run, f"log{i}")
            text = ""
            for fn in os.listdir(log_root):
                with open(os.path.join(log_root, fn)) as f:
                    text += f.read()
            line = [l for l in text.splitlines() if l.startswith("PROBE ")]
            assert line, text
            probes.append(json.loads(line[0][len("PROBE "):]))
        ranks = sorted(p["rank"] for p in probes)
        assert ranks == ["0", "1"], probes
        # both resolved the SAME two-entry endpoint table and authkey
        assert probes[0]["eps"] == probes[1]["eps"]
        assert len(probes[0]["eps"].split(",")) == 2
        assert probes[0]["key"] == probes[1]["key"]
        assert len(probes[0]["key"]) == 32
