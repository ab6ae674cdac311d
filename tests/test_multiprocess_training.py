"""Cross-process distributed TRAINING parity (VERDICT r4 missing #1).

The reference's core distributed test pattern (SURVEY.md §4): a launcher
spawns N worker processes, each worker trains the same model under data /
hybrid parallelism, and the per-step losses must match a single-process
run of the identical model on the identical global batch.

Here: 2 processes x 2 virtual CPU devices each -> a 4-device global mesh
through the jax coordination service, joined via the launch CLI's env
contract (PADDLE_TRAINER_ENDPOINTS / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ID).  Two jobs train 3 steps each:

- dp4: a small conv net under pure data parallelism (batch sharded over
  all 4 devices via jax.make_array_from_process_local_data, params
  replicated via TrainStep.globalize).
- dp2 x mp2: GPT with real tensor-parallel layers (fleet hybrid mesh
  spanning both processes).

The single-process references are computed IN THIS test process (the
conftest 8-device CPU mesh, unsharded TrainStep) with the same seeds and
batches; per-step losses must agree to 5e-4.
"""

import os
import socket
import subprocess
import sys

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt

_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
import paddle_tpu.distributed as dist
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

dist.init_parallel_env()
rank, world = dist.get_rank(), dist.get_world_size()
assert world == 2 and jax.device_count() == 4, (world, jax.devices())

# ---------------------------------------------------------------- dp4 CNN
mesh = Mesh(np.asarray(jax.devices()), ("dp",))

def global_batch(arr):
    # rows of the GLOBAL batch owned by this process (2 of 4 devices)
    n = arr.shape[0]
    local = arr[rank * (n // 2):(rank + 1) * (n // 2)]
    return paddle.Tensor(jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("dp")), local, arr.shape))

rs = np.random.RandomState(0)
x_np = rs.randn(8, 3, 8, 8).astype("float32")
y_np = rs.randint(0, 4, (8,)).astype("int64")

paddle.seed(3)
m = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                  nn.Flatten(), nn.Linear(8 * 8 * 8, 4))
o = opt.Momentum(learning_rate=0.05, momentum=0.9, parameters=m.parameters())
step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss()).globalize()
dp_losses = [float(step(global_batch(x_np), global_batch(y_np)))
             for _ in range(3)]
print("DP_LOSSES", " ".join(f"{l:.6f}" for l in dp_losses), flush=True)

# ----------------------------------------------------------- dp2 x mp2 GPT
import paddle_tpu.distributed.fleet as fleet

strategy = fleet.DistributedStrategy()
strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1}
fleet.init(is_collective=True, strategy=strategy)
hcg = fleet.get_hybrid_communicate_group()
hmesh = hcg.mesh

from paddle_tpu.text.models.gpt import GPTForCausalLM

CFG = dict(vocab_size=64, hidden_size=16, num_hidden_layers=2,
           num_attention_heads=2, max_position_embeddings=32)
paddle.seed(7)
lm = GPTForCausalLM(**CFG)  # builds TP layers under the mp>1 mesh
# identical start to the single-process reference: TP layers draw their own
# init, so load the reference's snapshotted weights (resharded on set)
snap = np.load(os.environ["REF_WEIGHTS"])
lm.set_state_dict({k: paddle.Tensor(snap[k]) for k in snap.files})
ids_np = np.random.RandomState(1).randint(1, 64, (8, 12)).astype("int64")

def global_ids(arr):
    n = arr.shape[0]
    local = arr[rank * (n // 2):(rank + 1) * (n // 2)]
    return paddle.Tensor(jax.make_array_from_process_local_data(
        NamedSharding(hmesh, P("dp")), local, arr.shape))

o2 = opt.AdamW(learning_rate=1e-3, parameters=lm.parameters())
step2 = paddle.jit.TrainStep(lm, o2, loss_fn=None).globalize(hmesh)
gids = global_ids(ids_np)
mp_losses = [float(step2({"input_ids": gids, "labels": gids}))
             for _ in range(3)]
print("MP_LOSSES", " ".join(f"{l:.6f}" for l in mp_losses), flush=True)
print(f"WORKER_OK rank={rank}", flush=True)
"""


def _reference_losses(weights_path):
    """Single-process references, identical seeds/batches (this process's
    8-device mesh is irrelevant: everything runs unsharded)."""
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(8, 3, 8, 8).astype("float32"))
    y = paddle.to_tensor(rs.randint(0, 4, (8,)).astype("int64"))
    paddle.seed(3)
    m = nn.Sequential(nn.Conv2D(3, 8, 3, padding=1), nn.ReLU(),
                      nn.Flatten(), nn.Linear(8 * 8 * 8, 4))
    o = opt.Momentum(learning_rate=0.05, momentum=0.9,
                     parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=nn.CrossEntropyLoss())
    dp_ref = [float(step(x, y)) for _ in range(3)]

    from paddle_tpu.text.models.gpt import GPTForCausalLM

    paddle.seed(7)
    lm = GPTForCausalLM(vocab_size=64, hidden_size=16, num_hidden_layers=2,
                        num_attention_heads=2, max_position_embeddings=32)
    # snapshot BEFORE training: the workers' TP model starts from these
    np.savez(weights_path,
             **{k: np.array(v.numpy()) for k, v in lm.state_dict().items()})
    ids = paddle.to_tensor(
        np.random.RandomState(1).randint(1, 64, (8, 12)).astype("int64"))
    o2 = opt.AdamW(learning_rate=1e-3, parameters=lm.parameters())
    step2 = paddle.jit.TrainStep(lm, o2, loss_fn=None)
    mp_ref = [float(step2({"input_ids": ids, "labels": ids}))
              for _ in range(3)]
    return dp_ref, mp_ref


def test_two_process_training_matches_single_process(tmp_path):
    weights = str(tmp_path / "ref_init.npz")
    dp_ref, mp_ref = _reference_losses(weights)

    port = socket.socket()
    port.bind(("127.0.0.1", 0))
    portno = port.getsockname()[1]
    port.close()
    eps = f"127.0.0.1:{portno},127.0.0.1:{portno + 1}"
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in range(2):
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "JAX_COORD", "XLA_FLAGS"))}
        env["JAX_PLATFORMS"] = "cpu"
        env["PADDLE_TRAINER_ENDPOINTS"] = eps
        env["PADDLE_TRAINERS_NUM"] = "2"
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_CURRENT_ENDPOINT"] = eps.split(",")[rank]
        env["REF_WEIGHTS"] = weights
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            cwd=repo))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=400)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert f"WORKER_OK rank={rank}" in out, out

    def parse(tag, out):
        line = [l for l in out.splitlines() if l.startswith(tag)][0]
        return [float(v) for v in line.split()[1:]]

    for rank, out in enumerate(outs):
        dp = parse("DP_LOSSES", out)
        mp = parse("MP_LOSSES", out)
        np.testing.assert_allclose(dp, dp_ref, rtol=5e-4, atol=5e-4,
                                   err_msg=f"dp rank {rank}")
        np.testing.assert_allclose(mp, mp_ref, rtol=5e-4, atol=5e-4,
                                   err_msg=f"mp rank {rank}")
