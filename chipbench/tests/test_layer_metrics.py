"""Every per-layer reader on a made-up trace with known answers; a reader
that finds nothing to read returns nothing, and a share over 100% raises."""

import pytest

from chipbench import peaks, spec as _spec, trace_reduce as T

MS = 1_000_000
MOSAIC = 'custom-call(...), custom_call_target="tpu_custom_call"'
CFG = {"family": "gpt2", "n_layer": 2, "n_embd": 1024, "n_head": 16,
       "n_inner": 4096, "vocab_size": 50257}


class Obs:
    def __init__(self, ops, modules, workload, host=None, end_to_end=None):
        self.spec = _spec.Spec()
        self.trace = T.Trace({0: ops}, {0: modules},
                             [("main", "bench.window", 0, 100 * MS)])
        self.t0, self.t1 = T.window(self.trace)
        self.config, self.workload = CFG, workload
        self.peak = peaks.peaks("TPU v5 lite")
        self.host, self.end_to_end = host or {}, end_to_end or {}
        busy, window = T.busy_seconds(self.trace, self.t0, self.t1)
        self.device = {"busy_s": busy, "window_s": window}

        class cell:
            chips = 1
        self.cell = cell

    def read(self, name):
        return self.spec.module("layer_metrics", name).read(self)


def _train_obs(fwd_ms, bwd_ms):
    ops = []
    for layer in range(2):
        t = layer * 20 * MS
        ops += [(f"%jvp___.{layer} = bf16[64,1024,128] {MOSAIC}", t, fwd_ms * MS),
                (f"%transpose_jvp___.{layer} = (f32[1]) {MOSAIC}", t + 5 * MS,
                 bwd_ms * MS),
                (f"%transpose_jvp___.{9 + layer} = f32[1] {MOSAIC}", t + 12 * MS,
                 bwd_ms * MS),
                (f"%fusion.{layer} = bf16[4] fusion(...)", t + 18 * MS, MS)]
    return Obs(ops, [("jit_step(1)", 0, 40 * MS)],
               {"batch_size": 4, "seq_len": 1024})


def test_flash_rooflines():
    # forward of B=4, S=1024, 16 heads of 64, causal: 4*16*4*64*(1024*1025/2)
    flops = 4 * 16 * 4 * 64 * (1024 * 1025 // 2)
    least_ms = flops / 197e12 * 1e3
    obs = _train_obs(fwd_ms=1, bwd_ms=2)
    assert obs.read("flash_fwd_roofline") == pytest.approx(100 * least_ms / 1.0)
    # backward: twice the forward's operations for a pair of kernels (4 ms)
    assert obs.read("flash_bwd_roofline") == \
        pytest.approx(100 * 2 * least_ms / 4.0)
    assert obs.host["flash_fwd_roofline_bound"] == "compute"
    bare = Obs([("%fusion.1 = bf16[4] fusion(...)", 0, MS)], [],
               {"batch_size": 4, "seq_len": 1024})
    assert bare.read("flash_fwd_roofline") is None
    assert bare.read("flash_bwd_roofline") is None


def test_a_kernel_faster_than_its_roofline_fails_the_run():
    obs = _train_obs(fwd_ms=0.01, bwd_ms=2)
    with pytest.raises(ValueError, match="over 100%"):
        obs.read("flash_fwd_roofline")


def _serve_obs():
    ops, modules = [], []
    for i in range(4):                       # four decode steps of 5 ms
        t = i * 20 * MS
        modules.append(("jit_step(7)", t, 5 * MS))
        ops += [(f"%step.{j} = f32[16,16,64] {MOSAIC}", t + j * MS, MS)
                for j in range(2)]
        ops.append(("%copy.249 = bf16[24,1025,16,16,64] copy(...)", t + 2 * MS,
                    3 * MS))
    modules += [("jit_chunk(8)", 6 * MS, 10 * MS), ("jit_prefill(9)", 30 * MS, 2 * MS)]
    ops += [("%chunk.1 = f32[256,16,64] " + MOSAIC, 6 * MS, 10 * MS),
            ("%fusion.3 = bf16[1] fusion(...)", 30 * MS, 2 * MS)]
    host = {"traced_decode_contexts": [500] * 64,
            "counters": {"serving.step_seconds_sum": 0.2,
                         "serving.step_seconds_count": 4},
            "queue_ms": [1.0, 2.0, 3.0], "flops_in_window": 197e12 * 0.02,
            "window_s": 2.0}
    return Obs(ops, modules, {"engine": {"kv_dtype": None}}, host,
               {"ttft_p95_ms": 1234.5, "itl_p95_ms": 339.0})


def test_serving_readers():
    obs = _serve_obs()
    assert obs.read("decode_device_ms") == 5.0
    assert obs.read("chunk_device_ms") == 10.0
    assert obs.device["busy_s"] == pytest.approx(0.032)
    assert obs.read("prefill_device_share") == pytest.approx(100 * 12 / 32)
    assert obs.read("device_idle_share.serve") == pytest.approx(68.0)
    assert obs.read("decode_step_ms") == pytest.approx(50.0)
    assert obs.read("queue_wait_p95_ms") == pytest.approx(2.9)
    assert obs.read("first_token_p95_ms") == 1234.5
    assert obs.read("token_gap_p95_ms") == 339.0
    assert obs.read("serve_mfu") == pytest.approx(1.0)
    # 64 tokens at context 500, 2 layers: K and V bytes over 8 ms of kernels
    moved = 2 * 2 * (64 * 500) * 16 * 64 * 2
    assert obs.read("paged_decode_roofline") == \
        pytest.approx(100 * moved / 819e9 / 0.008)
    assert obs.host["paged_decode_roofline_bound"] == "bandwidth"
    quiet = Obs([("%fusion.3 = bf16[1] fusion(...)", 0, MS)], [],
                {"engine": {}}, {})
    for name in ("decode_device_ms", "chunk_device_ms", "prefill_device_share",
                 "paged_decode_roofline", "decode_step_ms", "serve_mfu",
                 "queue_wait_p95_ms", "first_token_p95_ms",
                 "token_gap_p95_ms"):
        assert quiet.read(name) is None, name


def test_training_readers():
    obs = _train_obs(1, 2)
    obs.host.update(train_ips=32.0, tokens_per_sample=1024, input_wait_s=0.1,
                    window_s=20.0)
    per_sample = obs.spec.module("costs", "gpt2").train_flops_per_sample(
        CFG, 1024)
    assert obs.read("train_mfu") == pytest.approx(100 * 32 * per_sample / 197e12)
    assert obs.read("input_wait_share") == pytest.approx(0.5)
    assert obs.read("device_idle_share.train") == pytest.approx(88.0)
    obs.host["train_ips"] = 1e6
    with pytest.raises(ValueError, match="over 100%"):
        obs.read("train_mfu")
