"""Simulated multi-host elastic recovery (VERDICT r4 missing #6): the
launch CLI runs a 2-"host" job (--run_all_nodes --elastic_max_restarts),
host 1 SIGKILLs itself mid-training on the first attempt, the supervisor
kills the pod, re-rendezvouses on a FRESH coordinator port, relaunches,
and the workers resume from orbax — the final loss curve must equal an
uninterrupted run's, step for step.

This is the cross-process twin of tests/test_fault_injection.py driven
through the public CLI entry (python -m paddle_tpu.distributed.launch)
instead of a hand-built PodSupervisor, so the multi-node env contract
(--nnodes/--master fan-out, fresh-port re-rendezvous, restart-attempt
plumbing) is what's under test.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = r"""
import json, os, signal, sys
os.environ.pop("XLA_FLAGS", None)  # one CPU device per "host"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist
from paddle_tpu.io.checkpoint import CheckpointManager
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

dist.init_parallel_env()
rank = dist.get_rank()
attempt = int(os.environ.get("PADDLE_RESTART_ATTEMPT", "0"))

TOTAL = 8
KILL_AT = int(os.environ.get("KILL_AT_STEP", "-1"))
ckpt_dir = os.environ["CKPT_DIR"]
loss_log = os.environ["LOSS_LOG"]

paddle.seed(0)
m = nn.Sequential(nn.Linear(16, 32), nn.Tanh(), nn.Linear(32, 4))
o = opt.Momentum(learning_rate=0.05, momentum=0.9, parameters=m.parameters())
step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss()).globalize()

mesh = Mesh(np.asarray(jax.devices()), ("dp",))
rs = np.random.RandomState(7)
x_np = rs.randn(32, 16).astype("float32")
y_np = rs.randint(0, 4, (32,)).astype("int64")

def gbatch(arr):
    half = arr.shape[0] // 2
    local = arr[rank * half:(rank + 1) * half]
    return paddle.Tensor(jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, arr.shape))

x, y = gbatch(x_np), gbatch(y_np)

mgr = CheckpointManager(ckpt_dir, max_to_keep=2)
start = mgr.latest_step()
if start is not None:
    state = mgr.restore(start, template=step.state_dict(), to_tensors=False)
    step.set_state_dict(state)
    step.globalize()  # restored leaves are process-local again
    start = int(start)
else:
    start = 0

for t in range(start, TOTAL):
    loss = float(step(x, y))
    if rank == 0:
        with open(loss_log, "a") as f:
            f.write(json.dumps({"step": t, "loss": loss,
                                "attempt": attempt}) + "\n")
    mgr.save(t + 1, step.state_dict())
    mgr.wait_until_finished()
    if rank == 1 and attempt == 0 and t + 1 == KILL_AT:
        os.kill(os.getpid(), signal.SIGKILL)  # real process death

print(f"WORKER_DONE rank={rank} attempt={attempt}", flush=True)
"""


def _run_job(tmp_path, tag, kill_at):
    ckpt = tmp_path / f"ckpt_{tag}"
    log = tmp_path / f"losses_{tag}.jsonl"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_", "JAX_COORD", "XLA_FLAGS"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["CKPT_DIR"] = str(ckpt)
    env["LOSS_LOG"] = str(log)
    env["KILL_AT_STEP"] = str(kill_at)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nnodes", "2", "--run_all_nodes", "--elastic_max_restarts", "2",
         str(script)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, f"{tag}:\n{r.stdout}\n{r.stderr}"
    rows = [json.loads(l) for l in open(log)]
    # last write per step wins (the killed attempt re-logs resumed steps)
    by_step = {}
    for row in rows:
        by_step[row["step"]] = row
    return by_step, r.stdout + r.stderr


def test_sigkilled_host_restarts_and_reproduces_loss_curve(tmp_path):
    clean, _ = _run_job(tmp_path, "clean", kill_at=-1)
    faulty, out = _run_job(tmp_path, "faulty", kill_at=3)

    assert "[elastic] pod restart 1/" in out, out
    assert any(r["attempt"] == 1 for r in faulty.values()), faulty
    assert sorted(faulty) == sorted(clean) == list(range(8))
    for t in range(8):
        np.testing.assert_allclose(
            faulty[t]["loss"], clean[t]["loss"], rtol=1e-6, atol=1e-7,
            err_msg=f"step {t}")
