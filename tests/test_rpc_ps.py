"""RPC + PS-lite (VERDICT round-2 item 10; reference distributed/rpc/rpc.py
and ps/service/ps_client.h + the_one_ps.py)."""
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

from paddle_tpu.distributed.ps import DenseTable, PSClient, SparseTable


class TestTablesLocal:
    def test_dense_pull_push(self):
        t = DenseTable((2, 3), lr=0.1, init=np.ones((2, 3), np.float32))
        np.testing.assert_allclose(t.pull(), 1.0)
        t.push(np.full((2, 3), 2.0))
        np.testing.assert_allclose(t.pull(), 0.8)

    def test_sparse_lazy_rows_and_sgd(self):
        t = SparseTable(dim=4, lr=0.5, seed=0)
        rows = t.pull([5, 9, 5])
        assert rows.shape == (3, 4)
        np.testing.assert_array_equal(rows[0], rows[2])
        t.push([5], np.ones((1, 4), np.float32))
        np.testing.assert_allclose(t.pull([5])[0], rows[0] - 0.5, atol=1e-6)
        assert t.size() == 2

    def test_sparse_adagrad(self):
        t = SparseTable(dim=2, lr=1.0, optimizer="adagrad", seed=1)
        r0 = t.pull([0])[0].copy()
        t.push([0], np.full((1, 2), 2.0, np.float32))
        # adagrad step: lr * g / (sqrt(g^2) + eps) ~= 1.0
        np.testing.assert_allclose(t.pull([0])[0], r0 - 1.0, atol=1e-4)

    def test_save_load_roundtrip(self):
        t = SparseTable(dim=3, seed=2)
        t.pull([1, 2, 3])
        dump = t.save()
        t2 = SparseTable(dim=3, seed=99)
        t2.load(dump)
        np.testing.assert_array_equal(t.pull([2]), t2.pull([2]))

    def test_ps_client_local_mode(self):
        c = PSClient(server=None)
        c.create_sparse_table("local_emb", dim=2, lr=0.1)
        rows = c.pull_sparse("local_emb", np.array([1, 2]))
        assert rows.shape == (2, 2)
        c.push_sparse("local_emb", np.array([1]), np.ones((1, 2), np.float32))
        assert c.table_size("local_emb") == 2


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def test_two_process_rpc_and_ps(tmp_path):
    """Real 2-process RPC: rendezvous, remote calls, error propagation, and
    a PS server/trainer split (the reference's multi-process test pattern,
    test_dist_base.py)."""
    port = _free_port()
    env = dict(os.environ)
    env["RPC_TEST_DIR"] = str(tmp_path)
    workers = []
    here = os.path.dirname(os.path.abspath(__file__))
    for rank in range(2):
        workers.append(
            subprocess.Popen(
                [sys.executable, os.path.join(here, "_rpc_worker.py"),
                 str(rank), "2", str(port)],
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env,
            )
        )
    outs = []
    for w in workers:
        try:
            out, _ = w.communicate(timeout=180)
        except subprocess.TimeoutExpired:
            w.kill()
            out, _ = w.communicate()
        outs.append(out)
    for rank, (w, out) in enumerate(zip(workers, outs)):
        assert w.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"RPC_OK rank={rank}" in out, out


def test_sparse_table_capacity_and_shrink():
    """Eviction/growth policy (r3 verdict missing #8 note): LRU capacity cap
    + reference-style Shrink by access count."""
    import numpy as np

    from paddle_tpu.distributed.ps import SparseTable

    t = SparseTable(dim=4, lr=0.1, max_rows=4, seed=0)
    t.pull([0, 1, 2, 3])
    assert t.size() == 4 and t.evictions == 0
    t.pull([0])           # 0 becomes most-recent
    t.pull([4, 5])        # evicts LRU rows 1, 2
    assert t.size() == 4 and t.evictions == 2
    assert 0 in t.rows and 1 not in t.rows and 2 not in t.rows

    # evicted id re-initializes (fresh row), survivors keep training state
    r0_before = t.rows[0].copy()
    t.push([0], np.ones((1, 4), np.float32))
    assert not np.allclose(t.rows[0], r0_before)

    # shrink drops cold rows only
    t2 = SparseTable(dim=4)
    t2.pull([10, 11, 12])
    t2.pull([10, 10])     # 10 is hot
    dropped = t2.shrink(threshold=2)
    assert dropped == 2 and t2.size() == 1 and 10 in t2.rows
    # access counters reset after shrink
    assert t2.shrink(threshold=1) == 1  # 10 now cold again


def test_ps_runtime_deployment():
    """TheOnePSRuntime shape (reference the_one_ps.py:1031): a PSERVER
    process hosts tables, a TRAINER process auto-creates them from a model,
    trains through distributed_lookup_table (backward pushes row grads),
    and stop_worker shuts the server down."""
    import subprocess
    import sys

    port = _free_port()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    script = os.path.join(os.path.dirname(__file__), "_ps_runtime_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, script, role, str(port)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for role in ("PSERVER", "TRAINER")
    ]
    try:
        # TRAINER first: if it dies before stop_worker, the server would
        # block forever — failing fast here surfaces the real error
        trainer_out, _ = procs[1].communicate(timeout=240)
        assert procs[1].returncode == 0, trainer_out[-2000:]
        server_out, _ = procs[0].communicate(timeout=60)
        assert procs[0].returncode == 0, server_out[-2000:]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    assert "SERVER DONE" in server_out, server_out[-500:]
    assert "TRAINER DONE" in trainer_out, trainer_out[-500:]
