"""paddle_tpu.serving — tensor-parallel (mp) serving over a device mesh.

ISSUE-15 acceptance: ``ServingEngine(mesh=...)`` shards the paged KV
pools and the Megatron-split decoder weights over a ``model`` mesh axis
while keeping scheduling host-side, and every engine type stays greedy
byte-identical to its unsharded twin.

The sharded engines need more than one accelerator, so every scenario
runs in a clean subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (the pytest
process itself keeps the tier-1 single-CPU-device world).  Scenarios are
batched per subprocess — interpreter + jax startup dominates, not the
tiny-model compiles.  Host-side validation (carve divisibility, mixed
device lists) runs in-process: it raises before any device work.
"""

import os
import subprocess
import sys

import pytest

import paddle_tpu  # noqa: F401  (import check — the workers re-import)
from paddle_tpu.serving.cluster import ReplicaPool

pytestmark = pytest.mark.mp


def _run_worker(body, devices, timeout=560):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    proc = subprocess.run([sys.executable, "-c", _COMMON + body],
                          capture_output=True, text=True, timeout=timeout,
                          env=env)
    assert proc.returncode == 0 and "WORKER_OK" in proc.stdout, (
        f"worker failed\n--- stdout ---\n{proc.stdout}\n"
        f"--- stderr ---\n{proc.stderr}")
    return proc.stdout


_COMMON = r"""
import numpy as np
import jax

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models.gpt import GPTForCausalLM

PS = 8
MAXLEN = 64


def tiny_gpt(seed=0):
    paddle.seed(seed)
    m = GPTForCausalLM(vocab_size=96, hidden_size=32, num_hidden_layers=2,
                       num_attention_heads=2, max_position_embeddings=MAXLEN)
    o = opt.AdamW(learning_rate=1e-2, parameters=m.parameters())
    step = paddle.jit.TrainStep(m, o, loss_fn=None)
    ids = paddle.to_tensor(
        np.random.RandomState(0).randint(1, 96, (8, 20)).astype("int64"))
    for _ in range(5):
        step({"input_ids": ids, "labels": ids})
    return m.eval()


def prompt(n, seed):
    return np.random.RandomState(seed).randint(1, 96, (n,)).tolist()


# mixed lengths, crossing page boundaries
PROMPTS = [prompt(3, 2), prompt(8, 3), prompt(13, 4), prompt(16, 5)]


def run_engine(model, **kw):
    with ServingEngine(model, num_slots=3, page_size=PS,
                       max_model_len=MAXLEN, **kw) as eng:
        hs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
        out = [h.result(timeout=300) for h in hs]
        stats = eng.stats()
        traces = eng.step_traces
    return out, stats, traces
"""


@pytest.mark.slow
def test_mp2_greedy_parity_all_engine_types():
    """mp=2 greedy output is byte-identical to mp=1 for the plain, int8,
    chunked-prefill and speculative engines; per-shard bytes_per_page is
    exactly half; the sharded pool admits 2x the sequences at the same
    per-chip HBM budget."""
    _run_worker(r"""
assert jax.device_count() == 2, jax.devices()
m = tiny_gpt()

for name, kw in [("plain", {}), ("int8", {"kv_dtype": "int8"}),
                 ("chunked", {"prefill_chunk_tokens": 8}),
                 ("spec", {"speculative_k": 2})]:
    ref, st1, _ = run_engine(m, **kw)
    out, st2, _ = run_engine(m, mesh=jax.devices(), **kw)
    assert out == ref, (name, ref, out)
    assert st2["mp"] == 2 and st1["mp"] == 1, (name, st1, st2)
    # per-shard accounting: a 2-way KV-head split halves the per-chip
    # cost of a page (payload AND scale pools both split on heads)
    assert st2["bytes_per_page"] * 2 == st1["bytes_per_page"], (name,)

# capacity: same per-chip budget -> 2x resident sequences when sharded
with ServingEngine(m, num_slots=2, page_size=PS, max_model_len=MAXLEN) as e1:
    bm1 = e1._bm
    with ServingEngine(m, num_slots=2, page_size=PS, max_model_len=MAXLEN,
                       mesh=jax.devices()) as e2:
        bm2 = e2._bm
        assert bm2.shards == 2 and bm1.shards == 1
        budget = 64 * bm1.bytes_per_page
        assert bm2.max_resident_sequences(MAXLEN, budget_bytes=budget) \
            == 2 * bm1.max_resident_sequences(MAXLEN, budget_bytes=budget)
print("WORKER_OK")
""", devices=2)


@pytest.mark.slow
def test_mp2_spmd_trace_plateau_and_program_store_keys():
    """One SPMD trace per (phase, batch-shape, sampler) family at mp=2 —
    a mixed workload (varied lengths, varied max_new, greedy AND sampled
    rows) compiles the decode step exactly once; a SECOND mp=2 engine
    over the same model reuses the stored program; and an mp=1 engine
    over the same model keeps its OWN key space (no collision with the
    sharded programs)."""
    _run_worker(r"""
assert jax.device_count() == 2
m = tiny_gpt(seed=7)
mesh = jax.devices()
with ServingEngine(m, num_slots=3, page_size=PS, max_model_len=MAXLEN,
                   mesh=mesh) as eng:
    hs = [eng.submit(prompt(3 + 2 * i, 70 + i), max_new_tokens=4 + 3 * i,
                     temperature=0.0 if i % 2 == 0 else 0.8)
          for i in range(5)]
    for h in hs:
        h.result(timeout=300)
    assert eng.step_traces == 1, eng.step_traces

# second mp=2 engine: program-store hit, zero new decode traces
with ServingEngine(m, num_slots=3, page_size=PS, max_model_len=MAXLEN,
                   mesh=mesh) as eng2:
    eng2.generate(prompt(4, 75), max_new_tokens=3, timeout=300)
    assert eng2.step_traces == 1, eng2.step_traces

# mp=1 twin: the ("mp", 2) key component keeps the families apart, so
# this engine traces its own unsharded decode step (count still 1)
with ServingEngine(m, num_slots=3, page_size=PS,
                   max_model_len=MAXLEN) as eng3:
    eng3.generate(prompt(4, 76), max_new_tokens=3, timeout=300)
    assert eng3.step_traces == 1, eng3.step_traces

# perf attribution saw both key spaces as distinct families, and the
# bandwidth-bound hint for the UNSHARDED family on this 2-device host
# points at the mesh (the @mp2 family points at int8 pools instead)
from paddle_tpu.observability import perf as obs_perf
fams = {r["program"] for r in obs_perf.snapshot()}
assert any(f.startswith("decode@mp2") for f in fams), fams
assert "decode" in fams, fams
hint = obs_perf.candidate_hint("decode", "bandwidth-bound")
assert "mesh=" in hint, hint
print("WORKER_OK")
""", devices=2)


@pytest.mark.slow
def test_mp2_ledger_per_shard_bytes_and_chaos_restart():
    """Ledger rows for the sharded pools carry the shard= label and
    /statusz kv_capacity surfaces it; a TransientError mid-decode
    restarts the engine, _recover rebuilds the SHARDED pools through the
    adapter, and the requeued requests finish greedy byte-identical."""
    _run_worker(r"""
from paddle_tpu.observability import faults
from paddle_tpu.observability.memory import ledger
from paddle_tpu.resilience import TransientError

assert jax.device_count() == 2
m = tiny_gpt()
ref, _, _ = run_engine(m)

with ServingEngine(m, num_slots=3, page_size=PS, max_model_len=MAXLEN,
                   mesh=jax.devices(), replica="mpA") as eng:
    rows = [r for r in ledger().report()["owners"]
            if r.get("replica") == "mpA"
            and (r.get("meta") or {}).get("kind") == "kv"]
    assert rows, "no kv ledger rows for the sharded engine"
    for r in rows:
        assert r["meta"].get("shard") == "model:2", r
    caps = [c for c in ledger().statusz()["kv_capacity"]
            if c["replica"] == "mpA"]
    assert caps and all(c.get("shard") == "model:2" for c in caps), caps

    # chaos: crash the scheduler mid-decode; recovery re-shards the
    # rebuilt pools and replays prompt+tokens-so-far bit-exactly
    eng.generate(prompt(4, 72), max_new_tokens=2, timeout=300)  # warm

    def boom():
        raise TransientError("injected decode crash")

    faults.inject("serving.step_crash", fn=boom, at_trips={4})
    try:
        hs = [eng.submit(p, max_new_tokens=12) for p in PROMPTS]
        out = [h.result(timeout=300) for h in hs]
    finally:
        faults.clear()
    assert eng._engine_restarts == 1, eng._engine_restarts
    assert out == ref, (ref, out)
print("WORKER_OK")
""", devices=2)


@pytest.mark.slow
def test_dp2_mp2_cluster_parity_through_router():
    """ReplicaPool carves 4 devices into two mp=2 submeshes; the
    prefix-affinity router serves greedy byte-identical results across
    the dp x mp topology."""
    _run_worker(r"""
from paddle_tpu.serving.cluster import ReplicaPool, ServingCluster

assert jax.device_count() == 4
m = tiny_gpt()
ref, _, _ = run_engine(m)

cluster = ServingCluster(m, replicas=2, devices="auto", mp=2, num_slots=3,
                         page_size=PS, max_model_len=MAXLEN,
                         replica_prefix="dpmp")
with cluster:
    pool = cluster._pool
    assert len(pool) == 2 and pool.meshes is not None
    assert [len(g) for g in pool.meshes] == [2, 2]
    assert all(e.stats()["mp"] == 2 for e in pool.engines)
    hs = [cluster.submit(p, max_new_tokens=12) for p in PROMPTS]
    out = [h.result(timeout=300) for h in hs]
assert out == ref, (ref, out)

# explicit submeshes spell the same topology
devs = jax.devices()
with ReplicaPool(m, devices=[devs[:2], devs[2:]], num_slots=3, page_size=PS,
                 max_model_len=MAXLEN, replica_prefix="subm") as pool2:
    got = pool2.engines[1].generate(PROMPTS[0], max_new_tokens=12,
                                    timeout=300)
assert got == ref[0]
print("WORKER_OK")
""", devices=4)


# ------------------------------------------------- host-side validation
def test_candidate_hint_recognizes_mp_families():
    """@mp<N> families hint at cutting per-shard bytes (int8 pools; int8
    weights once quantized) — never at sharding again."""
    from paddle_tpu.observability.perf import (
        candidate_hint, is_mp_family, mp_degree)

    assert is_mp_family("decode@mp2") and is_mp_family("prefill/64@mp4")
    assert not is_mp_family("decode@int8")
    assert mp_degree("decode@int8@mp4") == 4
    assert mp_degree("verify/k2@int8@mp2") == 2
    assert mp_degree("decode") == 1
    h = candidate_hint("decode@mp2", "bandwidth-bound")
    assert "sharded" in h and "int8" in h and "mesh=" not in h
    hq = candidate_hint("verify/k2@int8@mp2", "bandwidth-bound")
    assert "weight" in hq and "mp2" in hq



def test_pool_carve_divisibility_error():
    """mp carve validation raises before any engine is built, with the
    counts in the message."""
    with pytest.raises(ValueError, match="not divisible by mp=3"):
        ReplicaPool(object(), mp=3, num_slots=1)  # 1 visible CPU device


def test_pool_rejects_mixed_devices_and_submeshes():
    import jax

    dev = jax.devices()[0]
    with pytest.raises(ValueError, match="mixes single devices"):
        ReplicaPool(object(), devices=[dev, [dev]], num_slots=1)


def test_pool_rejects_mp_with_explicit_submeshes():
    import jax

    dev = jax.devices()[0]
    with pytest.raises(ValueError, match="EITHER mp=N"):
        ReplicaPool(object(), devices=[[dev]], mp=2, num_slots=1)
