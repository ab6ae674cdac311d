"""paddle.distributed.launch — the job launcher.

Reference analog: python/paddle/distributed/launch/ (one worker process per
GPU, env-var rendezvous contract, elastic master).

TPU model (SURVEY.md §3.5): ONE process per TPU host drives all local
chips (single-controller SPMD), so "launch" degenerates to: set the
coordination-service env vars, run the script.  Multi-host: run this same
command on every host with --nnodes/--node_rank/--master; it maps the
paddle env contract onto jax.distributed.initialize inputs, which
init_parallel_env consumes.
"""

from __future__ import annotations

import os
import runpy
import sys


def build_env(nnodes=1, node_rank=0, master="127.0.0.1:8765"):
    env = {
        "PADDLE_TRAINERS_NUM": str(nnodes),
        "PADDLE_TRAINER_ID": str(node_rank),
        "PADDLE_TRAINER_ENDPOINTS": master,
        "PADDLE_CURRENT_ENDPOINT": master if node_rank == 0 else "",
        "JAX_COORDINATOR_ADDRESS": master,
        "JAX_NUM_PROCESSES": str(nnodes),
        "JAX_PROCESS_ID": str(node_rank),
    }
    return env


def main(argv=None):
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a (multi-host) TPU training job: one process per "
                    "host, chips driven via the global mesh.")
    parser.add_argument("--nnodes", type=int,
                        default=int(os.environ.get("PADDLE_NNODES", "1")),
                        help="number of hosts in the job")
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_NODE_RANK", "0")),
                        help="this host's rank")
    parser.add_argument("--master", type=str,
                        default=os.environ.get("PADDLE_MASTER", "127.0.0.1:8765"),
                        help="coordinator host:port (rank-0 host)")
    parser.add_argument("--devices", "--gpus", type=str, default=None,
                        help="accepted for reference-CLI parity; chip "
                             "visibility is controlled by the TPU runtime")
    parser.add_argument("--log_dir", type=str, default=None)
    parser.add_argument("--run_all_nodes", action="store_true",
                        help="spawn EVERY node's worker from this one "
                             "launcher (single-box multi-host simulation / "
                             "CPU validation; on a real pod each host runs "
                             "its own launcher)")
    parser.add_argument("--elastic_max_restarts", type=int, default=0,
                        help="with --run_all_nodes: supervise the pod and, "
                             "when ANY node dies, kill the rest, "
                             "re-rendezvous on a FRESH master port, and "
                             "relaunch up to this many times (reference "
                             "elastic 'kill pod -> re-rendezvous -> "
                             "restart'; workers resume from their "
                             "checkpoints)")
    parser.add_argument("script", help="training script to run")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    args = parser.parse_args(argv)

    if args.run_all_nodes:
        # nnodes == 1 included: a single supervised worker still gets the
        # elastic kill-pod -> fresh-port -> relaunch treatment
        return _run_all_nodes(args)
    if args.elastic_max_restarts:
        raise SystemExit(
            "--elastic_max_restarts needs --run_all_nodes (per-host "
            "launchers are supervised by the cluster manager, not here)")

    env = dict(os.environ)
    env.update(build_env(args.nnodes, args.node_rank, args.master))
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        env["PADDLE_LOG_DIR"] = args.log_dir

    if args.nnodes > 1:
        # multi-process: the worker must import the framework FRESH so the
        # bootstrap joins the coordination service before any backend touch
        # — same spawn model as the reference launcher's worker processes.
        # This launcher has imported the package but initialised no backend
        # (tests/test_startup.py), so the chip is free for the worker.
        import subprocess

        proc = subprocess.run([sys.executable, args.script] +
                              list(args.script_args), env=env)
        return proc.returncode
    os.environ.update(env)
    sys.argv = [args.script] + list(args.script_args)
    runpy.run_path(args.script, run_name="__main__")
    return 0


def _fresh_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_all_nodes(args):
    """Single-box multi-host: spawn one worker per node rank, optionally
    under elastic supervision (PodSupervisor semantics: any death kills the
    pod, the rendezvous is rebuilt on a fresh coordinator port — the dead
    job's coordination service must never be rejoined — and the pod
    relaunches; workers resume from their latest checkpoint)."""
    from ..elastic import PodSupervisor

    host, _, _ = args.master.partition(":")

    def make_workers(attempt):
        # fresh master port per attempt = the re-rendezvous
        master = f"{host or '127.0.0.1'}:{_fresh_port()}"
        specs = []
        for r in range(args.nnodes):
            env = dict(os.environ)
            env.update(build_env(args.nnodes, r, master))
            env["PADDLE_RESTART_ATTEMPT"] = str(attempt)
            if args.log_dir:
                os.makedirs(args.log_dir, exist_ok=True)
                env["PADDLE_LOG_DIR"] = args.log_dir
            specs.append(([sys.executable, args.script]
                          + list(args.script_args), env))
        return specs

    return PodSupervisor(make_workers,
                         max_restarts=args.elastic_max_restarts).run()
