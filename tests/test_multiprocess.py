"""True multi-process rendezvous (SURVEY.md §3.5/§5.8): a 2-process CPU
pair joins the jax coordination service through the reference env-var
contract (PADDLE_TRAINER_ENDPOINTS / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ID — what the launch CLI exports), then exercises
cross-process primitives: process identity, object all-gather, and a
global psum over per-process shards.

The workers run in clean subprocesses (the conftest's in-process CPU mesh
must not leak into them), mirroring the reference's subprocess-pair test
pattern for its TCPStore/Gloo path.
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ.pop("XLA_FLAGS", None)  # one local CPU device per process
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.distributed as dist

env = dist.init_parallel_env()   # joins the coordination service from env vars
rank = dist.get_rank()
world = dist.get_world_size()
assert world == 2, f"world {world}"
assert jax.device_count() == 2, jax.devices()      # both processes' chips visible
assert jax.local_device_count() == 1

# object all-gather: every process contributes a DIFFERENT object
objs = []
dist.all_gather_object(objs, {"rank": rank, "payload": "x" * (10 + 40 * rank)})
assert [o["rank"] for o in objs] == [0, 1], objs
assert len(objs[1]["payload"]) == 50

# global psum over per-process shards through the public mesh path
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils as mh
mesh = Mesh(np.asarray(jax.devices()), ("world",))
local = np.full((1, 4), float(rank + 1), np.float32)
garr = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("world", None)), local, (2, 4))
total = jax.jit(lambda a: a.sum())(garr)
assert float(total) == (1.0 + 2.0) * 4, float(total)

# HCG per-axis rank: with one device per process on a dp=2 mesh, the
# coordinate is real (not the single-controller 0-with-warning)
import paddle_tpu.distributed.fleet as fleet
strategy = fleet.DistributedStrategy()
strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1, "pp_degree": 1}
fleet.init(is_collective=True, strategy=strategy)
hcg = fleet.get_hybrid_communicate_group()
assert hcg.get_data_parallel_rank() == rank, \
    (hcg.get_data_parallel_rank(), rank)

print(f"WORKER_OK rank={rank}", flush=True)
"""


def test_two_process_rendezvous(tmp_path):
    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    portno = port.getsockname()[1]
    port.close()
    eps = f"127.0.0.1:{portno},127.0.0.1:{portno + 1}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_COORD"))}
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_TRAINER_ENDPOINTS"] = eps
        env["PADDLE_TRAINERS_NUM"] = "2"
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_CURRENT_ENDPOINT"] = eps.split(",")[rank]
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=150)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"WORKER_OK rank={rank}" in out, out
