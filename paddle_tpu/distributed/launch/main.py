"""python -m paddle_tpu.distributed.launch — multi-host job launcher.

Reference parity: python/paddle/distributed/launch/main.py:18 +
controllers/collective.py build_pod:32 (per-rank env PADDLE_TRAINER_ID /
PADDLE_TRAINER_ENDPOINTS / PADDLE_MASTER:154-161), job/container.py per-rank
log files, watcher.

TPU-native design: ONE process per host drives all local chips (SPMD), so the
launcher spawns one training process per host entry instead of one per
device; rank env maps to jax.distributed coordination (process_id/
coordinator_address). On a single host it simply execs the script with rank 0
after exporting the coordination env. Elastic restart: watches the child and
relaunches up to --max_restarts on nonzero exit (the ElasticManager role at
epoch/checkpoint granularity — slice failures restart the whole program from
the latest checkpoint, the TPU failure model).
"""
from __future__ import annotations

import argparse
import os
import secrets
import socket
import subprocess
import sys
import time

_RDZV_PORT_OFFSET = 5  # rendezvous store listens beside the coordinator port


def _local_ip(master_host):
    if master_host in ("127.0.0.1", "localhost"):
        return "127.0.0.1"
    s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
    try:
        s.connect((master_host, 1))
        return s.getsockname()[0]
    finally:
        s.close()


def rendezvous(master, nnodes, rank, job_id, timeout=300.0):
    """Master-based rendezvous (reference launch/controllers/master.py:65,177
    HTTP/etcd master, TPU-native over the csrc TCPStore):

    - the node on the MASTER HOST serves the store at master_port + 5 (first
      local binder wins); every other node connects to it
    - rank -1 means "assign me one": after all nodes register intent,
      unclaimed ranks are handed out atomically, so nodes can join with NO
      pre-set rank or endpoint env and mix freely with explicit-rank nodes
    - every node publishes its reachable IP; all block until nnodes have
      registered, then read back the full peer table
    - rank 0 also mints the per-job RPC authkey (distributed through the
      store, never typed by a user)

    Returns (rank, endpoints_list, authkey, store).
    """
    from ..store import TCPStore

    host, port = master.rsplit(":", 1)
    store_port = int(port) + _RDZV_PORT_OFFSET
    my_ip = _local_ip(host)
    # only a node ON the master host may try to serve the store: a bind on a
    # different machine would succeed locally (the port is free THERE), leak
    # a listener, and mislead the who-is-master race
    on_master_host = my_ip == "127.0.0.1" or host in (my_ip, "localhost")
    store = None
    if on_master_host:
        try:
            store = TCPStore(host, store_port, is_master=True,
                             world_size=nnodes, timeout=int(timeout))
        except RuntimeError:
            store = None  # another local node already serves it
    serving = store is not None
    if store is None:
        deadline = time.monotonic() + timeout
        while True:
            try:
                store = TCPStore(host, store_port, is_master=False,
                                 world_size=nnodes, timeout=int(timeout))
                break
            except RuntimeError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.5)

    pfx = f"rdzv/{job_id}"
    # TWO-PHASE rank assignment so explicit NODE_RANK nodes and
    # auto-assigned (-1) nodes mix safely: phase 1 registers every node's
    # intent (explicit nodes claim their rank; double-claims fail loudly);
    # only after ALL nnodes intents are in do auto nodes pick from the
    # unclaimed ranks — an auto node can never steal a rank an explicit
    # node is about to claim.
    if rank >= 0 and store.add(f"{pfx}/claim/{rank}", 1) != 1:
        raise RuntimeError(
            f"rendezvous: rank {rank} claimed twice — two nodes were "
            "launched with the same NODE_RANK/--rank"
        )
    n_int = store.add(f"{pfx}/intents", 1)
    deadline = time.monotonic() + timeout
    while n_int < nnodes:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"rendezvous: only {n_int}/{nnodes} nodes registered intent "
                f"within {timeout}s"
            )
        time.sleep(0.2)
        n_int = store.add(f"{pfx}/intents", 0)
    if rank == -1:
        for cand in range(nnodes):
            if store.add(f"{pfx}/claim/{cand}", 1) == 1:
                rank = cand
                break
        else:
            raise RuntimeError(
                f"rendezvous: all {nnodes} ranks already claimed "
                "(more nodes launched than --nnodes?)"
            )
    store.set(f"{pfx}/node/{rank}", f"{my_ip}:{int(port) + 100 + rank}")
    if rank == 0:
        store.set(f"{pfx}/authkey", secrets.token_hex(16))
    n = store.add(f"{pfx}/joined", 1)
    deadline = time.monotonic() + timeout
    while n < nnodes:
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"rendezvous: only {n}/{nnodes} nodes joined within {timeout}s"
            )
        time.sleep(0.2)
        n = store.add(f"{pfx}/joined", 0)
    endpoints = [
        store.get(f"{pfx}/node/{r}").decode() for r in range(nnodes)
    ]
    authkey = store.get(f"{pfx}/authkey").decode()
    # every node says when it has read the table, and the node that serves
    # the store leaves last: its launcher exits, and takes the store down,
    # as soon as its trainer does, while a peer may still be polling
    # `joined` (seen as "TCPStore.add failed" on a loaded host)
    n = store.add(f"{pfx}/read", 1)
    while serving and n < nnodes and time.monotonic() < deadline:
        time.sleep(0.05)
        n = store.add(f"{pfx}/read", 0)
    return rank, endpoints, authkey, store


def launch_main(argv=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--master", default=None, help="coordinator host:port")
    parser.add_argument("--nnodes", type=int, default=1)
    parser.add_argument(
        "--rank", type=int, default=int(os.getenv("NODE_RANK", "-1")),
        help="-1 = let the master's rendezvous assign one",
    )
    parser.add_argument("--log_dir", default="log")
    parser.add_argument("--max_restarts", type=int, default=0)
    parser.add_argument("--devices", default=None, help="unused on TPU (SPMD)")
    parser.add_argument(
        "--elastic_level", type=int, default=0,
        help="0: restart-on-exit only; 1: also heartbeat-register in the "
        "master TCPStore and restart when a peer node goes stale "
        "(reference fleet/elastic/manager.py)",
    )
    parser.add_argument("--job_id", default=os.getenv("PADDLE_ELASTIC_JOB_ID", "default"))
    parser.add_argument("script", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if not args.script:
        parser.error("no training script given")
    script = args.script
    if script and script[0] == "--":
        script = script[1:]

    env = dict(os.environ)
    store = None
    if args.master and args.nnodes > 1:
        # no pre-set rank/endpoint env required: resolve everything through
        # the rank-0 TCPStore rendezvous
        args.rank, endpoints, authkey, store = rendezvous(
            args.master, args.nnodes, args.rank, args.job_id
        )
        env["PADDLE_TRAINER_ENDPOINTS"] = ",".join(endpoints)
        env["PADDLE_RPC_AUTHKEY"] = authkey
        env["PADDLE_MASTER"] = args.master
    elif args.rank < 0:
        args.rank = 0
    env["PADDLE_TRAINER_ID"] = str(args.rank)
    env["PADDLE_TRAINERS_NUM"] = str(args.nnodes)
    if args.master:
        env["PADDLE_MASTER"] = args.master
    os.makedirs(args.log_dir, exist_ok=True)

    manager = None
    if args.elastic_level >= 1:
        from ..fleet.elastic import ElasticManager

        host, port = (args.master or "127.0.0.1:29600").rsplit(":", 1)
        manager = ElasticManager(
            args.job_id, args.rank, args.nnodes,
            host=host, port=int(port) + 7,  # registry beside the coordinator
            endpoint=f"{host}:{port}",
        )
        manager.register()

    _PEER_RESTART = -1001  # sentinel: peer-triggered, does not burn a restart

    restarts = 0
    try:
        while True:
            if manager is not None:
                env = manager.export_env(env)
            log_path = os.path.join(args.log_dir, f"workerlog.{args.rank}")
            with open(log_path, "ab") as logf:
                proc = subprocess.Popen(
                    [sys.executable] + script, env=env,
                    stdout=logf, stderr=subprocess.STDOUT,
                )
                code = _watch(proc, manager, _PEER_RESTART)
            if code == 0:
                return 0
            if code == _PEER_RESTART:
                # a PEER died: hold until the world is whole again (the peer
                # rejoins, or the scheduler rewrites its endpoint), THEN
                # relaunch — this restart is not the local trainer's fault
                # and does not count against --max_restarts
                _hold_until_whole(manager)
                continue
            if restarts >= args.max_restarts:
                print(f"worker exited with {code}; giving up after {restarts} restarts")
                return code
            restarts += 1
            print(f"worker exited with {code}; restart {restarts}/{args.max_restarts}")
            time.sleep(3)
    finally:
        if manager is not None:
            manager.exit()


def _hold_until_whole(manager, log_every=30.0):
    gen0 = manager.generation()
    last_log = 0.0
    while True:
        if manager.all_alive():
            print("elastic: world whole again — relaunching")
            return
        if manager.generation() != gen0:
            print("elastic: endpoints rewritten — relaunching")
            return
        now = time.monotonic()
        if now - last_log > log_every:
            print(f"elastic: holding for dead nodes {manager.dead_nodes()} "
                  "(waiting for rejoin or endpoint rewrite)")
            last_log = now
        time.sleep(manager.heartbeat_interval)


def _watch(proc, manager, peer_restart_code):
    """Wait on the child; under elastic mode also watch peer heartbeats and
    kill+restart when another node goes stale (manager.py watch:611)."""
    if manager is None:
        return proc.wait()
    from ..fleet.elastic import ElasticStatus

    while True:
        code = None
        try:
            code = proc.wait(timeout=manager.heartbeat_interval)
        except subprocess.TimeoutExpired:
            pass
        if code is not None:
            return code
        if manager.watch_once(child_alive=True) == ElasticStatus.RESTART:
            print("elastic: peer node heartbeat stale — stopping local trainer")
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            return peer_restart_code


if __name__ == "__main__":
    sys.exit(launch_main())
