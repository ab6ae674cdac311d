"""The ``deepseek_v3`` family at a toy size on the CPU in float32: weights
from the seed, the reference against the program's forward and one step,
a whole run of a toy cell through the train entry with the control and a
fault failing where the program passes, the per-layer readers on a made-up
trace, the cost functions against hand counts and the published
configuration's parameter counts."""

import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import peaks, run, spec, trace_reduce

from . import toy

moe_costs = importlib.import_module("chipbench.costs.moe")
mla_flash = importlib.import_module("chipbench.costs.mla_flash")
costs = importlib.import_module("chipbench.costs.deepseek_v3")

with open(os.path.join(toy.BENCH, "configs", "kanana2_30b_a3b_ep8.json")) as f:
    KANANA = json.load(f)

#: a share of a toy model: 2 of 8 experts held (experts 4, 5), top-3
TOY = {
    "family": "deepseek_v3", "source": "toy sizes for the CPU tests",
    "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 8,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 6,
    "n_routed_experts": 4, "router_experts": 8, "expert_offset": 2,
    "n_shared_experts": 2, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "routed_scaling_factor": 2.448, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_interleave": True, "vocab_size": 211,
    "initializer_range": 0.02, "bias_update_speed": 0.05, "reduced": []}
TOY_MIX = dict(toy.TOY_TRAIN_MIX, batch_size=2, seq_len=24)
TOY_CELL = dict(toy.TOY_TRAIN_CELL, config="toy_dsv3", recompute=True,
                extra_warm_steps=1)
CELL = "toy_dsv3.toy_batches24"
NEW_METRICS = ("mla_attn_device_ms", "moe_route_device_ms",
               "moe_experts_roofline", "mla_flash_fwd_roofline",
               "mla_flash_bwd_roofline", "expert_load_max_over_mean")


@pytest.fixture(scope="module")
def dsv3_spec(tmp_path_factory):
    root = toy.make_root(tmp_path_factory.mktemp("dsv3") / "root")
    bench = os.path.join(root, "chipbench")
    toy._dump(os.path.join(bench, "configs", "toy_dsv3.json"), TOY)
    toy._dump(os.path.join(bench, "mixes", "toy_batches24.json"), TOY_MIX)
    toy._dump(os.path.join(bench, "workloads", CELL + ".json"), TOY_CELL)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy_dsv3", "source": "tests",
                            "file": "chipbench/configs/toy_dsv3.json",
                            "reduced": [], "why": "toy"})
    data["workloads"].append({"name": CELL, "config": "toy_dsv3",
                              "traffic": "toy_batches24", "chips": 1,
                              "why": "toy"})
    for m in data["end_to_end"] + data["per_layer"]:
        if "kanana2_30b_a3b_ep8.lm_train_4k" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    toy._dump(os.path.join(root, "BENCHMARK.json"), data)
    return spec.Spec(root=root)


@pytest.fixture(scope="module")
def family(dsv3_spec):
    return (dsv3_spec.module("models", "deepseek_v3"),
            dsv3_spec.module("reference", "deepseek_v3"))


def test_weights_come_from_the_seed_and_lie_on_the_host(family):
    _, ref = family
    a, b, c = (ref.init_params(s, TOY) for s in (2 ** 31 + 9, 2 ** 31 + 9, 9))
    assert set(a) == set(ref.param_shapes(TOY))
    for k, shape in ref.param_shapes(TOY).items():
        assert isinstance(a[k], np.ndarray)
        assert a[k].shape == shape and a[k].dtype == np.float32
        assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    assert a["router_w"].shape == (2, 32, 8) and a["exp_gate"].shape[1] == 4
    assert abs(float(a["ln1"].mean()) - 1.0) < 0.01
    assert float(np.abs(a["kv_norm"] - 1.0).max()) > 0   # a dropped gain shows
    assert ref.init_params(9, TOY, dtype=jnp.bfloat16)["embed"].dtype \
        == jnp.bfloat16


def test_forward_agrees_with_the_programs(family):
    import paddle_tpu as paddle

    models, ref = family
    params = ref.init_params(3, TOY)
    model = models.build(TOY, params, ref).eval()
    held = model.model.layers[1].mlp
    assert (held.num_experts, held.experts_held, held.expert_offset) \
        == (8, 4, 2)
    ids = np.random.default_rng(0).integers(0, TOY["vocab_size"], (2, 40))
    got = np.asarray(model(input_ids=paddle.to_tensor(ids))._value)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.logits(params, jnp.asarray(ids), TOY))
    assert got.shape == want.shape == (2, 40, TOY["vocab_size"])
    assert np.abs(got - want).max() < 2e-5 * np.abs(want).max()


def test_the_held_experts_matter_to_the_reference(family):
    """The share is not a no-op: the whole toy (all 8 experts held) gives
    other logits than experts 2..5 alone."""
    _, ref = family
    whole = dict(TOY, n_routed_experts=8, expert_offset=0)
    del whole["router_experts"]
    params = ref.init_params(3, whole)
    part = dict(params, **{k: params[k][:, 2:6] for k in
                           ("exp_gate", "exp_up", "exp_down")})
    ids = jnp.asarray(np.random.default_rng(0).integers(0, 211, (1, 16)))
    a = np.asarray(ref.logits(params, ids, whole))
    b = np.asarray(ref.logits(part, ids, TOY))
    assert np.abs(a - b).max() > 1e-4 * np.abs(a).max()


@pytest.fixture(scope="module")
def sound(dsv3_spec):
    kept = {}
    cell = dsv3_spec.cell(CELL)
    result = run.run_cell(dsv3_spec, cell, 2 ** 31 + 3, 1.0, False,
                          t0=time.time(), kept=kept)
    return cell, result, kept


def test_a_whole_run_is_correct_leaf_by_leaf(sound):
    """Three steps through ``TrainStep`` with recomputation on: losses,
    every leaf's gradient and update against the reference."""
    cell, result, kept = sound
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_ips", "setup_s"}
    numbers = kept["numbers"]
    assert numbers["grad_rel_err"] < 1e-4 and numbers["leaves_left_out"] == 0
    got, want = kept["got"][1], kept["want"][1]
    assert set(got) == set(want) and len(got["exp_gate"]) == 2
    for key in want:
        np.testing.assert_allclose(got[key], want[key], rtol=2e-4, atol=1e-9)


def test_control_and_fault_fail_where_the_program_passes(dsv3_spec, sound):
    from chipbench.tools import readings

    cell, _, kept = sound
    ctx = run.RunContext(dsv3_spec, cell, 2 ** 31 + 3, 0.5, False, time.time())
    got = dsv3_spec.module("entries", "train").stand_ins(ctx, kept)
    for name in ("control", "half_batch"):
        row = readings.judged(cell, 1, name, got[name])
        assert row["correct"] is False and row["failed_numbers"], name


def _fake_trace():
    """One whole step of 10 ms: a forward and two backward flash kernels and
    a projection under ``mla_attention``, two operations under
    ``moe_route``, one grouped product under ``moe_experts``."""
    ms = 1_000_000
    kernel = ' custom-call(...), ' + trace_reduce.MOSAIC
    fwd = "jit(step)/jvp(forward_loss)/checkpoint/mla_attention/"
    bwd = "jit(step)/transpose(jvp(forward_loss))/checkpoint/mla_attention/"
    scoped = [
        ("%flash_fwd.1 =" + kernel, 1 * ms, 1 * ms, fwd + "flash_fwd"),
        ("%dot.2 = dot(...)", 2 * ms, 1 * ms, fwd + "dot_general"),
        ("%mla_attention.3 =" + kernel, 3 * ms, 1 * ms, bwd + "pallas_call"),
        ("%mla_attention.4 =" + kernel, 4 * ms, 1 * ms, bwd + "pallas_call"),
        ("%sort.5 = sort(...)", 5 * ms, ms // 2,
         "jit(step)/jvp(forward_loss)/checkpoint/moe_route/sort"),
        ("%gather.6 = gather(...)", 6 * ms, ms // 2,
         "jit(step)/transpose(jvp(forward_loss))/checkpoint/moe_route/gather"),
        ("%ragged-dot.7 =" + kernel, 7 * ms, 2 * ms,
         "jit(step)/jvp(forward_loss)/checkpoint/moe_experts/ragged_dot"),
        ("%fusion.8 = fusion(...)", 9 * ms, ms // 2, "jit(step)/optimizer_step")]
    trace = trace_reduce.Trace(
        {0: [e[:3] for e in scoped]}, {0: [("jit_step(1)", 0, 10 * ms)]},
        [("main", "bench.window", 0, 10 * ms)])
    trace.scoped = scoped
    return trace


def test_the_new_readers_on_a_made_up_trace(dsv3_spec, monkeypatch):
    """A traced run of the toy cell, reading a made-up trace (the CPU has no
    device plane): every new metric is on the line."""
    monkeypatch.setattr(trace_reduce, "load", lambda path: _fake_trace())
    cell = dsv3_spec.cell(CELL)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    result = run.run_cell(dsv3_spec, cell, 7, 0.5, True, t0=time.time(),
                          device=device)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(m), sorted(m)
    assert m["mla_attn_device_ms"] == pytest.approx(4.0)
    assert m["moe_route_device_ms"] == pytest.approx(1.0)
    assert 1.0 <= m["expert_load_max_over_mean"] <= 4.0
    # 2 x 24 tokens, top-3 of 8 with 4 held: some 72 rows a layer and step
    family = dsv3_spec.module("models", "deepseek_v3")
    counted = family.window_counters()
    assert counted["moe.local_assignments"] > 0
    # reading the counts once more finds nothing new: the window's stand
    assert family.tokens_per_sample(cell.config, cell.workload) == 24
    assert family.window_counters() == counted
    flops, moved = mla_flash.forward(2, 24, 4, 12, 6)
    least = max(flops / 197e12, moved / 819e9)
    assert m["mla_flash_fwd_roofline"] == pytest.approx(100 * least / 1e-3)
    assert 0 < m["moe_experts_roofline"] < 1 and m["mla_flash_bwd_roofline"] > 0


def test_readers_give_nothing_for_a_program_without_the_scopes(dsv3_spec):
    ms = 1_000_000
    trace = trace_reduce.Trace(
        {0: [("%fusion.1", 0, ms)]}, {0: [("jit_step(1)", 0, 2 * ms)]}, [])
    trace.scoped = [("%fusion.1", 0, ms, "jit(step)/forward_loss/dot")]
    cell = dsv3_spec.cell(CELL)

    class Obs:
        pass
    obs = Obs()
    obs.spec, obs.trace, obs.cell = dsv3_spec, trace, cell
    obs.config, obs.workload = cell.config, cell.workload
    obs.host, obs.t0, obs.t1 = {"steps": 3}, 0, 2 * ms
    obs.peak = peaks.peaks("TPU v5 lite")
    dsv3_spec.module("models", "deepseek_v3")._counted.clear()
    for name in NEW_METRICS:
        assert dsv3_spec.module("layer_metrics", name).read(obs) is None, name


# ------------------------------------------------------------ cost functions
def test_moe_costs_by_hand():
    # 5 rows, hidden 4, width 3, 2 experts: three products of 5x4x3
    flops, moved = moe_costs.forward(5, 4, 3, 2, itemsize=2)
    assert flops == 2 * 5 * 3 * 4 * 3 == 360
    assert moved == 2 * (3 * 2 * 4 * 3 + 3 * 5 * 4 + 3 * 5 * 3) == 354
    b_flops, b_moved = moe_costs.backward(5, 4, 3, 2, itemsize=2)
    assert b_flops == 720
    assert b_moved == 2 * (2 * 72 + 9 * (5 * 4 + 5 * 3)) == 918
    assert moe_costs.step(5, 4, 3, 2, layers=4, recomputed=True) \
        == (4 * (2 * 360 + 720), 4 * (2 * 354 + 918))
    assert moe_costs.step(5, 4, 3, 2, layers=1, recomputed=False) \
        == (360 + 720, 354 + 918)


def test_mla_flash_counts_by_hand():
    # one head, q/k 6 wide, v 4 wide, 3 tokens, causal: 6 pairs
    flops, moved = mla_flash.forward(1, 3, 1, 6, 4)
    assert flops == 6 * 2 * (6 + 4) and moved == 3 * ((12 + 8) * 2 + 4)
    b_flops, b_moved = mla_flash.backward(1, 3, 1, 6, 4)
    assert b_flops == 2 * flops and b_moved == 3 * ((24 + 16) * 2 + 4)
    # at one head size it is costs/flash.py
    flash = importlib.import_module("chipbench.costs.flash")
    assert mla_flash.forward(2, 128, 4, 64, 64) == flash.forward(2, 128, 4, 64)
    assert mla_flash.backward(2, 128, 4, 64, 64) \
        == flash.backward(2, 128, 4, 64)


def test_deepseek_v3_counts_by_hand():
    tiny = {"num_hidden_layers": 3, "first_k_dense_replace": 1,
            "hidden_size": 8, "num_attention_heads": 2,
            "qk_nope_head_dim": 4, "qk_rope_head_dim": 2, "v_head_dim": 3,
            "kv_lora_rank": 5, "intermediate_size": 16,
            "moe_intermediate_size": 6, "n_routed_experts": 2,
            "router_experts": 8, "num_experts_per_tok": 4,
            "n_shared_experts": 2, "vocab_size": 10}
    # W_q 8x12, W_kva 8x7, W_kvb 5x14, W_o 6x8
    assert costs.mla_params(tiny) == 96 + 56 + 70 + 48 == 270
    assert costs.expert_params(tiny) == 3 * 8 * 6 == 144
    assert costs.assignments_per_token(tiny) == 4 * 2 / 8 == 1.0
    sparse = 2 * 144 + 8 * 8 + 1.0 * 144
    assert costs.matmul_params_per_token(tiny) \
        == 3 * 270 + 3 * 8 * 16 + 2 * sparse + 80
    # 3 tokens: 6 pairs; per pair and head 2*(6+3); 2 heads, 3 layers
    assert costs.causal_attention_flops(tiny, 3) == 3 * 2 * 18 * 6
    fwd = 2 * costs.matmul_params_per_token(tiny) * 3 + 648
    assert costs.train_flops_per_sample(tiny, 3) == 3 * fwd


def test_kanana_is_the_published_size_and_the_stated_cut():
    ref = importlib.import_module("chipbench.reference.deepseek_v3")
    whole = dict(KANANA, **KANANA["published"])
    del whole["router_experts"]
    assert ref.n_params(whole) == 30_670_809_088 \
        == KANANA["assumed"]["parameters_whole_model"]
    assert ref.n_params(KANANA) == 575_955_456 \
        == KANANA["assumed"]["parameters_held_here"]
    assert costs.mla_params(KANANA) == 26_345_984 - 512       # less the norm
    assert costs.expert_params(KANANA) == 4_718_592
    assert costs.assignments_per_token(KANANA) == 0.75
    # the issue's arithmetic: 2.2 GFLOP a token, 17.7 TFLOP a step of 2 x 4,096
    step = 2 * costs.train_flops_per_sample(KANANA, 4096)
    assert 17.4e12 < step < 18.0e12
    z = ref.sizes(KANANA)
    assert (z["E"], z["held"], z["offset"], z["k"]) == (128, 16, 0, 6)


def test_no_width_differs_from_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] in KANANA["source"])
    differs = {k for k, v in row["config"].items() if KANANA.get(k) != v}
    assert differs == set(KANANA["reduced"]) == set(KANANA["published"])
    for k in differs:
        assert KANANA["published"][k] == row["config"][k]
