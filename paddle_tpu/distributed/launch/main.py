"""``python -m paddle_tpu.distributed.launch`` — multi-process job launcher.

Reference: ``python/paddle/distributed/launch/main.py:18`` +
``launch/controllers/collective.py`` (per-device process spawn, PADDLE_*
env surface, log_dir, restart policy).

TPU-native redesign: on real TPU pods jax is one process PER HOST (all
local chips visible), so ``--nproc_per_node`` defaults to 1 and the launcher
mainly wires the coordinator address for ``jax.distributed.initialize``
(rendezvous comes from slice metadata; no TCPStore). For CPU testing (and
parity with the reference's one-proc-per-device model) it spawns N local
processes with the PADDLE_* env surface and a shared coordinator —
``init_parallel_env`` in each worker completes the rendezvous.

A chip belongs to one process at a time, so with ``--nproc_per_node > 1``
every child is pinned to its own chip (``TPU_VISIBLE_CHIPS`` = its local
rank; see :func:`_chip_env`) — left to the default each child would claim
every chip of the host and all but the first would fail or hang. The
parent stays stdlib-only: it never touches jax, so it never holds a chip.
"""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="multi-process distributed job launcher",
    )
    p.add_argument("--master", default=None,
                   help="coordinator ip:port (default: local free port)")
    p.add_argument("--rank", type=int, default=0, help="node rank")
    p.add_argument("--nnodes", type=int, default=1, help="number of nodes")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per node (TPU: keep 1 per host)")
    p.add_argument("--log_dir", default="log", help="per-rank log directory")
    p.add_argument("--job_id", default="default", help="job id for log names")
    p.add_argument("--devices", default=None,
                   help="accepted for reference compat (XLA owns devices)")
    p.add_argument("--max_restart", type=int, default=0,
                   help="restart attempts when a worker fails")
    p.add_argument("--backend", default=None,
                   help="collective backend hint; 'gloo' forces CPU "
                        "multi-process collectives (testing)")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


#: TPU_PROCESS_BOUNDS of a single host split into one-chip processes, by
#: chip count (the table jax's own multi-process TPU tests use)
_ONE_CHIP_PROCESS_BOUNDS = {4: "2,2,1", 8: "4,2,1"}


def _chip_env(local_rank, nproc, ports):
    """libtpu variables that give local process ``local_rank`` of ``nproc``
    exactly one chip. On a single host of 4 or 8 chips the processes also
    form one connected slice (collectives between them run over ICI); for
    any other count each process gets an isolated chip, and a connected
    slice needs the ``TPU_PROCESS_*`` variables from the caller. Variables
    the caller already set are left alone. Harmless without a TPU."""
    bounds = _ONE_CHIP_PROCESS_BOUNDS.get(nproc)
    env = {
        "TPU_VISIBLE_CHIPS": str(local_rank),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": bounds or "1,1,1",
        "TPU_PROCESS_PORT": str(ports[local_rank]),
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }
    if bounds:
        env["CLOUD_TPU_TASK_ID"] = str(local_rank)
        env["TPU_PROCESS_ADDRESSES"] = ",".join(
            f"localhost:{p}" for p in ports)
    return {k: v for k, v in env.items() if k not in os.environ}


def _spawn(args, master, attempt):
    os.makedirs(args.log_dir, exist_ok=True)
    world = args.nnodes * args.nproc_per_node
    procs = []
    ports = []
    while len(ports) < args.nproc_per_node:
        port = _free_port()
        if port not in ports:
            ports.append(port)
    for local_rank in range(args.nproc_per_node):
        rank = args.rank * args.nproc_per_node + local_rank
        env = dict(os.environ)
        if args.nproc_per_node > 1:
            env.update(_chip_env(local_rank, args.nproc_per_node, ports))
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_COORDINATOR_ADDRESS": master,
            "PADDLE_JOB_ID": args.job_id,
        })
        if args.backend:
            env["PADDLE_DISTRIBUTED_BACKEND"] = args.backend
        cmd = [sys.executable, args.training_script] + args.training_script_args
        log_path = os.path.join(
            args.log_dir, f"{args.job_id}.rank{rank}.log"
        )
        log_f = open(log_path, "ab")
        if attempt:
            log_f.write(f"\n--- restart attempt {attempt} ---\n".encode())
        procs.append((rank, subprocess.Popen(
            cmd, env=env, stdout=log_f, stderr=subprocess.STDOUT
        ), log_f, log_path))
    return procs


def _wait(procs):
    """Wait for all; on any failure terminate the rest. Returns (ok, failed_ranks)."""
    failed = []
    alive = dict((rank, p) for rank, p, _, _ in procs)
    try:
        while alive:
            for rank in list(alive):
                rc = alive[rank].poll()
                if rc is None:
                    continue
                del alive[rank]
                if rc != 0:
                    failed.append(rank)
            if failed and alive:
                for p in alive.values():
                    p.send_signal(signal.SIGTERM)
                for p in alive.values():
                    try:
                        p.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        p.kill()
                alive.clear()
            time.sleep(0.2)
    finally:
        for _, p, log_f, _ in procs:
            if p.poll() is None:
                p.kill()
            log_f.close()
    return not failed, failed


def launch(argv=None):
    args = _parse(argv)
    if args.nnodes > 1 and not args.master:
        print("launch: --nnodes > 1 requires an explicit --master "
              "(a default local port cannot rendezvous across nodes)",
              file=sys.stderr)
        return 2
    master = args.master or f"127.0.0.1:{_free_port()}"
    for attempt in range(args.max_restart + 1):
        procs = _spawn(args, master, attempt)
        ok, failed = _wait(procs)
        if ok:
            print(f"launch: all {args.nproc_per_node} local ranks exited cleanly")
            return 0
        print(f"launch: ranks {failed} failed "
              f"(attempt {attempt + 1}/{args.max_restart + 1}); "
              f"logs in {args.log_dir}/", file=sys.stderr)
        if attempt < args.max_restart:
            # fresh port: the old coordinator is gone
            master = args.master or f"127.0.0.1:{_free_port()}"
    for _, _, _, log_path in procs:
        sys.stderr.write(f"--- tail {log_path} ---\n")
        try:
            with open(log_path) as f:
                sys.stderr.write("".join(f.readlines()[-15:]))
        except OSError:
            pass
    return 1


def main():
    raise SystemExit(launch())


if __name__ == "__main__":
    main()
