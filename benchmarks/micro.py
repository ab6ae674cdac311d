"""Roofline and bandwidth microbenchmarks for bench.py (SURVEY.md §6).

Three probes that turn the single headline number into an explained number
(VERDICT r3 "what's weak" #1: the artifact must carry its own perf-ceiling
evidence):

- matmul_tflops: peak achievable bf16 matmul throughput through this exact
  dispatch path (the practical roofline — every MFU in the bench is also
  reported as a fraction of THIS, which needs no hardware datasheet).
- hbm_bandwidth: streaming add over a large array (the bandwidth roofline).
- allreduce_bw: psum bus bandwidth over all visible devices
  (BASELINE.md metric #3).  On a one-chip host n=1 makes a cross-chip
  collective unmeasurable; the probe then reports the degenerate result
  explicitly (n_devices=1, value=None) rather than a fake number — the
  multi-device path is exercised on the 8-device CPU mesh in
  tests/test_bench_micro.py and on four chips by chip_smoke.py.

Peak FLOPs table: v5e datasheet is 197 TFLOP/s bf16 per chip (394 is the
int8 TOPS line, which BASELINE.md's "~394 bf16" conflates).  MFU-vs-peak
uses the bf16 figure; unknown device kinds get None and only the
fraction-of-measured-matmul field.
"""

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

PEAK_BF16 = {
    # device_kind -> peak bf16 FLOP/s per chip (datasheet values)
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e/Trillium
}


def device_peak_flops():
    kind = jax.devices()[0].device_kind
    return kind, PEAK_BF16.get(kind)


def _sync(out):
    """Force completion with a host readback of one scalar — the same sync
    discipline bench.py's train loops use (float(loss))."""
    leaf = jax.tree_util.tree_leaves(out)[0]
    np.asarray(jax.device_get(leaf.ravel()[0]))


def _time_jitted(fn, args, iters, warmup=2):
    out = None
    for _ in range(warmup):
        out = fn(*args)
    _sync(out)
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    _sync(out)
    return (time.time() - t0) / iters


def matmul_tflops(n=4096, chain=32, iters=10):
    """Chained dependent bf16 matmuls: amortizes dispatch, defeats DCE."""

    @jax.jit
    def f(a, b):
        def body(_, c):
            c = jax.lax.dot_general(a, c, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            return c.astype(jnp.bfloat16)

        return jax.lax.fori_loop(0, chain, body, b)

    k = jax.random.key(0)
    a = jax.random.normal(k, (n, n), jnp.bfloat16)
    b = jax.random.normal(k, (n, n), jnp.bfloat16)
    dt = _time_jitted(f, (a, b), iters)
    return (2 * n**3 * chain) / dt / 1e12


def hbm_bandwidth_gbs(mb=512, chain=16, iters=10):
    """Streaming x+1 over a large f32 array; bytes = (read+write) per pass."""

    @jax.jit
    def f(x):
        return jax.lax.fori_loop(0, chain, lambda _, v: v + 1.0, x)

    x = jnp.zeros((mb * 1024 * 1024 // 4,), jnp.float32)
    dt = _time_jitted(f, (x,), iters)
    return 2 * x.size * 4 * chain / dt / 1e9


def allreduce_bus_bw(mb=256, iters=20, devices=None):
    """psum bus bandwidth over a 1-axis mesh of all visible devices.

    Bus bandwidth convention (matches NCCL's nccl-tests): for ring allreduce
    each device sends/receives 2*(n-1)/n of the buffer, so
    bus_bw = bytes * 2*(n-1)/n / time.  Returns (bw_gbs_or_None, n).
    """
    from jax.sharding import Mesh, PartitionSpec as P
    from paddle_tpu.distributed.communication import shard_map

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n < 2:
        return None, n
    mesh = Mesh(np.array(devices), ("x",))
    words = mb * 1024 * 1024 // 4

    ar = shard_map(lambda x: jax.lax.psum(x, "x"), mesh, P("x"), P("x"))

    f = jax.jit(ar)
    x = jax.device_put(
        jnp.ones((n * words,), jnp.float32),
        jax.sharding.NamedSharding(mesh, P("x")))
    dt = _time_jitted(f, (x,), iters)
    # per-device shard is `words` f32; allreduce moves the full logical
    # buffer: bytes counted on the logical array per the bus-bw convention
    bytes_logical = n * words * 4
    return bytes_logical * 2 * (n - 1) / n / dt / 1e9, n


def attention_sweep(seqs=(1024, 2048, 4096), batch=4, heads=16, head_dim=128,
                    causal=True, iters=10):
    """Pallas flash kernel vs XLA attention, fwd and fwd+bwd, per seq len.

    Replaces the README's asserted 1.2-1.9x with measured numbers in the
    bench artifact (VERDICT r3 "what's weak" #3).
    """
    from paddle_tpu.ops.flash_attention import flash_attention_fn

    def xla_attn(q, k, v):
        return jax.nn.dot_product_attention(q, k, v, is_causal=causal,
                                            implementation="xla")

    def pallas_attn(q, k, v):
        return flash_attention_fn(q, k, v, causal=causal)

    # REPS dependent applications chained inside ONE jit, so per-dispatch
    # host latency does not swamp the kernel time at short sequence lengths
    REPS = 8

    def chained(fn, remat=False):
        # remat=True for the grad measurement: without it the scan saves
        # every rep's attention residuals (REPS x the single-call footprint
        # -> OOM at seq 4096 f32 scores under the XLA path).  Both kernels
        # get the same policy, so the SPEEDUP comparison stays apples-to-
        # apples; absolute fwd+bwd times include one recomputed fwd.
        body_fn = jax.checkpoint(fn) if remat else fn

        def run(q, k, v):
            def body(c, _):
                return body_fn(c, k, v).astype(c.dtype), None

            out, _ = jax.lax.scan(body, q, None, length=REPS)
            return out

        return run

    results = []
    for s in seqs:
        k0 = jax.random.key(0)
        shape = (batch, s, heads, head_dim)
        q = jax.random.normal(k0, shape, jnp.bfloat16)
        k = jax.random.normal(k0, shape, jnp.bfloat16)
        v = jax.random.normal(k0, shape, jnp.bfloat16)
        entry = {"seq": s, "batch": batch, "heads": heads,
                 "head_dim": head_dim, "causal": causal, "reps_per_call": REPS}
        for name, fn in (("pallas", pallas_attn), ("xla", xla_attn)):
            fwd = jax.jit(chained(fn))

            def train(qq, kk, vv, _fn=fn):
                def loss(t):
                    return chained(_fn, remat=True)(
                        t[0], t[1], t[2]).astype(jnp.float32).sum()

                return jax.grad(loss)((qq, kk, vv))

            trn = jax.jit(train)
            entry[f"{name}_fwd_ms"] = round(
                _time_jitted(fwd, (q, k, v), iters) * 1e3 / REPS, 3)
            entry[f"{name}_fwdbwd_ms"] = round(
                _time_jitted(trn, (q, k, v), iters) * 1e3 / REPS, 3)
        entry["speedup_fwd"] = round(
            entry["xla_fwd_ms"] / entry["pallas_fwd_ms"], 3)
        entry["speedup_fwdbwd"] = round(
            entry["xla_fwdbwd_ms"] / entry["pallas_fwdbwd_ms"], 3)
        results.append(entry)
    return results


def cost_fields(compiled):
    """flops / bytes-accessed of a compiled XLA executable — recorded for
    BOTH the framework and the raw baseline steps so an HLO-level
    regression (the framework computing more than the hand-written step)
    is visible in the bench artifact itself, not just as a throughput
    delta (VERDICT r4 weak #1)."""
    try:
        ca = compiled.cost_analysis()
        return {"gflops": round(ca.get("flops", 0) / 1e9, 1),
                "gbytes_accessed": round(ca.get("bytes accessed", 0) / 1e9, 2)}
    except Exception as e:  # cost analysis is best-effort on some backends
        return {"error": str(e)}
