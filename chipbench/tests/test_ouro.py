"""The ``ouro`` family of the benchmark on the CPU: the configuration's file
against the catalog and the numbers written here, the parameters and the
cost functions by hand, weights from the seed handed to the program without
a second copy, the three new readers on a made-up trace (and nothing where
the trace or the program lacks what they read), and a whole run of a toy
cell through the serve entry, its pages short of full residency, in which
the control and an altered token fail where the program passes."""

import importlib
import json
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import peaks, run, spec, trace_reduce
from chipbench.tools import readings

from . import toy

costs = importlib.import_module("chipbench.costs.ouro")
ref = importlib.import_module("chipbench.reference.ouro")

with open(os.path.join(toy.BENCH, "configs", "ouro_2_6b.json")) as f:
    OURO = json.load(f)

REAL_CELL = "ouro_2_6b.batch_closed_1k"
TOY = {"family": "ouro", "source": "toy sizes for the CPU tests",
       "hidden_size": 32, "intermediate_size": 48, "num_hidden_layers": 3,
       "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
       "hidden_act": "silu", "max_position_embeddings": 65536,
       "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
       "tie_word_embeddings": False, "total_ut_steps": 4,
       "early_exit_threshold": 1, "vocab_size": 211,
       "initializer_range": 0.02, "serve_positions": 96, "reduced": []}
#: 4 slots x 12 pages would be full residency: 20 pages bind
TOY_CELL = dict(
    toy.TOY_SERVE_CELL, config="toy_ouro",
    weights={"outlier_channels": 2, "outlier_gain": 16},
    engine=dict(toy.TOY_SERVE_CELL["engine"], num_pages=20),
    limits={"logit_gap_max": 1e-4, "logit_gap_mean": 1e-6})
CELL = "toy_ouro.toy_closed"
NEW_METRICS = ("loop_decode_hbm_roofline", "loop_step_device_ms",
               "admissions_blocked_per_step")


@pytest.fixture(scope="module")
def ouro_spec(tmp_path_factory):
    root = toy.make_root(tmp_path_factory.mktemp("ouro") / "root")
    bench = os.path.join(root, "chipbench")
    toy._dump(os.path.join(bench, "configs", "toy_ouro.json"), TOY)
    toy._dump(os.path.join(bench, "workloads", CELL + ".json"), TOY_CELL)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy_ouro", "source": "tests",
                            "file": "chipbench/configs/toy_ouro.json",
                            "reduced": [], "why": "toy"})
    data["workloads"].append({"name": CELL, "config": "toy_ouro",
                              "traffic": "toy_closed", "chips": 1,
                              "why": "toy"})
    for m in data["end_to_end"] + data["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) and CELL not in m["workloads"]:
            m["workloads"].append(CELL)
    toy._dump(os.path.join(root, "BENCHMARK.json"), data)
    return spec.Spec(root=root)


# ------------------------------------------------------- the configuration
def test_every_key_is_the_published_one_and_nothing_is_cut():
    published = {
        "hidden_size": 2048, "intermediate_size": 5632,
        "num_hidden_layers": 48, "num_attention_heads": 16,
        "num_key_value_heads": 16, "head_dim": 128, "hidden_act": "silu",
        "vocab_size": 49152, "max_position_embeddings": 65536,
        "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
        "tie_word_embeddings": False, "total_ut_steps": 4,
        "early_exit_threshold": 1, "model_type": "ouro",
        "sliding_window": None, "use_sliding_window": False,
        "max_window_layers": 48}
    for key, value in published.items():
        assert OURO[key] == value, key
    assert OURO["layer_types"] == ["full_attention"] * 48
    assert OURO["family"] == "ouro" and OURO["reduced"] == []
    assert OURO["serve_positions"] == 1024
    assert "whole model on one chip, bfloat16" in OURO["deployment"]
    for key in ("sandwich norms", "final norm after every step",
                "early_exit_gate", "cache rows", "rotary", "biases",
                "initializer_range", "serve_positions", "parameters"):
        assert key in OURO["assumed"], key
    for key in ("equations", "early exit", "served programs"):
        assert key in OURO["departures"], key
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "ouro_2_6b")
    assert entry["reduced"] == [] and OURO["source"].startswith(
        entry["source"])


def test_no_key_differs_from_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] in OURO["source"])
    assert {k for k, v in row["config"].items() if OURO.get(k) != v} == set()


def test_parameters_by_hand():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416 == costs.layer_params(OURO)
    held = 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert ref.n_params(OURO) == held == 2_667_974_657 \
        == OURO["assumed"]["parameters"]
    # the issue's arithmetic: 5.34 GB in bfloat16; 1.5 MiB of cache a token
    assert round(2 * held / 1e9, 2) == 5.34
    assert costs.kv_bytes_per_token(OURO) == 1_572_864 == 3 * 2 ** 19
    assert 320 * 16 * 1_572_864 == 8_053_063_680


def test_costs_by_hand():
    assert costs.attention_shape(OURO) == (192, 16, 16, 128)
    matmul = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert costs.layer_matmul_params(OURO) == matmul == 51_380_224
    # every layer four times a token
    assert costs.block_params_per_token(OURO) == 4 * 48 * matmul
    assert costs.head_params(OURO) == 49152 * 2048
    attn = 192 * 4 * 250 * 2048
    assert costs.decode_flops(OURO, 250) \
        == 2 * (4 * 48 * matmul + 49152 * 2048) + attn
    assert costs.prefill_flops(OURO, 256) == 2 * 4 * 48 * matmul * 256 \
        + 192 * 4 * 2048 * 256 * 257 // 2 + 2 * 49152 * 2048
    # 3 decode steps over 14 lanes at 250 cached tokens
    contexts = [250] * 14 * 3
    flops, moved = costs.decode_step(OURO, contexts, 3)
    weights = 4 * (48 * 51_388_416 + 2048 + 2049) + 49152 * 2048
    assert moved == 3 * 2 * weights + 14 * 3 * 250 * 1_572_864
    assert flops == 14 * 3 * costs.decode_flops(OURO, 250)
    # the issue's reckoning: 19.7 GB of layer weights a step
    assert round(2 * 4 * 48 * 51_388_416 / 1e9, 1) == 19.7
    # a tiny size, counted by hand: 2 layers twice over
    tiny = {"hidden_size": 8, "num_attention_heads": 2,
            "num_key_value_heads": 1, "head_dim": 4, "intermediate_size": 12,
            "num_hidden_layers": 2, "total_ut_steps": 2, "vocab_size": 10}
    layer = 8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 3 * 8 * 12
    assert costs.layer_matmul_params(tiny) == layer == 480
    assert costs.attention_shape(tiny) == (4, 2, 1, 4)
    assert costs.decode_flops(tiny, 5) \
        == 2 * (2 * 2 * 480 + 80) + 4 * 4 * 5 * 2 * 4
    assert costs.kv_bytes_per_token(tiny) == 2 * 4 * 1 * 4 * 2
    flops, moved = costs.decode_step(tiny, [5, 7], 1)
    assert moved == 2 * (2 * (2 * (480 + 32) + 17) + 80) + 12 * 64


# ------------------------------------------------ weights and the program
def test_weights_come_from_the_seed_leaf_by_leaf():
    a, b, c = (ref.init_params(s, TOY) for s in (2 ** 31 + 9, 2 ** 31 + 9, 9))
    assert set(a) == set(ref.param_shapes(TOY))
    for k, shape in ref.param_shapes(TOY).items():
        assert a[k].shape == shape
        assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    for name in ("model.layers.0.input_layernorm_2.weight",
                 "model.norm.weight"):
        assert abs(float(a[name].mean()) - 1.0) < 0.05, name
        assert float(jnp.abs(a[name] - 1.0).max()) > 0      # drawn, not ones
    # the gate is drawn too: zeros would hide a dropped bias
    assert float(jnp.abs(a["model.early_exit_gate.bias"]).max()) > 0
    bf = ref.init_params(9, TOY, dtype=jnp.bfloat16)
    assert all(v.dtype == jnp.bfloat16 for v in bf.values())


def test_shaped_weights_are_loud_in_the_norms_that_feed_a_product():
    shaped = ref.init_params(5, TOY, shape=TOY_CELL["weights"])
    plain = ref.init_params(5, TOY)
    for name in ("model.layers.0.input_layernorm.weight",
                 "model.layers.2.post_attention_layernorm.weight",
                 "model.norm.weight"):
        loud = np.round(np.asarray(shaped[name] / plain[name]))
        assert (loud == 16).sum() == 2 and (loud == 1).sum() == 30, name
    for name in ("model.layers.0.input_layernorm_2.weight",
                 "model.layers.1.post_attention_layernorm_2.weight",
                 "model.layers.1.mlp.up_proj.weight"):
        assert np.array_equal(shaped[name], plain[name]), name


def test_the_program_takes_the_leaves_without_a_second_copy(ouro_spec):
    models = ouro_spec.module("models", "ouro")
    params = ref.init_params(3, TOY, dtype=jnp.bfloat16)
    model = models.build(TOY, params, ref, dtype="bfloat16")
    named = dict(model.named_parameters())
    # the weights exist ONCE: three layers' leaves for twelve layer-steps
    assert set(named) == set(params) and len(named) == 3 * 11 + 5
    for name, leaf in named.items():
        assert leaf._value is params[name], name
    with pytest.raises(RuntimeError, match="did not take"):
        models.build(TOY, dict(params, stray=params["model.norm.weight"]),
                     ref)
    with pytest.raises(RuntimeError, match="the program wants"):
        models.build(dict(TOY, intermediate_size=40), params, ref)


# ------------------------------------------------------------ a whole run
@pytest.fixture(scope="module")
def sound(ouro_spec):
    kept = {}
    cell = ouro_spec.cell(CELL)
    result = run.run_cell(ouro_spec, cell, 2 ** 31 + 3, 1.0, False,
                          t0=time.time(), kept=kept)
    return cell, result, kept


def test_a_whole_run_through_the_serve_entry_is_correct(sound):
    cell, result, kept = sound
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert kept["numbers"]["tokens_compared"] >= 100
    # prompts past the chunk (32) went in by chunks, the rest whole
    lengths = [len(p) for p, _ in kept["served"]]
    assert max(lengths) > 32 > min(lengths)


def test_control_and_altered_token_fail_where_the_program_passes(ouro_spec,
                                                                 sound):
    cell, _, kept = sound
    ctx = run.RunContext(ouro_spec, cell, 2 ** 31 + 3, 0.5, False,
                         time.time())
    got = ouro_spec.module("entries", "serve").stand_ins(ctx, kept)
    limits = cell.workload["limits"]
    assert got["control"]["logit_gap_max"] > 3 * limits["logit_gap_max"]
    assert got["token_altered"]["logit_gap_max"] \
        > 100 * limits["logit_gap_max"]
    assert got["control"]["tokens_compared"] \
        == kept["numbers"]["tokens_compared"]
    for name in ("control", "token_altered"):
        row = readings.judged(cell, 1, name, got[name])
        assert row["correct"] is False and row["failed_numbers"]


# ------------------------------------------------------------- the readers
MS = 1_000_000


def _fake_trace(scope="loop_step"):
    """Two decode steps of 40 ms with a chunk program between them.  A
    step's loop body runs four times: each pass holds 6 ms of products, a
    decode kernel of 2 ms and a writer of 0.5 ms under ``scope``, then the
    gate; the head follows the loop.  The chunk's operations carry the
    same scopes and are left out."""
    kernel = ' custom-call(...), custom_call_target="tpu_custom_call"'

    def one(t, prog):
        body = f"jit({prog})/while/body/closed_call/"
        out = []
        for i in range(4):
            at = t + i * 9 * MS
            out += [
                ("%fusion.1 = fusion(...)", at, 6 * MS,
                 body + scope + "/dot_general"),
                ("%step.2 =" + kernel, at + 6 * MS, 2 * MS,
                 body + scope + "/gqa_attention/pallas_call"),
                ("%paged_write.3 =" + kernel, at + 8 * MS, MS // 2,
                 body + scope + "/gqa_attention/jit(_paged_write_pallas)/"
                 "paged_write/pallas_call"),
                ("%fusion.4 = fusion(...)", at + 8 * MS + MS // 2, MS // 4,
                 body + "exit_gate/dot_general")]
        return out + [("%fusion.5 = fusion(...)", t + 37 * MS, 2 * MS,
                       f"jit({prog})/dot_general")]

    scoped = one(0, "step") + one(40 * MS, "chunk") + one(80 * MS, "step")
    trace = trace_reduce.Trace(
        {0: [e[:3] for e in scoped]},
        {0: [("jit_step(1)", 0, 40 * MS), ("jit_chunk(2)", 40 * MS, 40 * MS),
             ("jit_step(1)", 80 * MS, 40 * MS)]},
        [("main", "bench.window", 0, 120 * MS)])
    trace.scoped = scoped
    return trace


def _obs(a_spec, trace, contexts, config=None, counters=None):
    cell = a_spec.cell(CELL)

    class Obs:
        pass
    obs = Obs()
    obs.spec, obs.trace, obs.cell = a_spec, trace, cell
    obs.config, obs.workload = config or cell.config, cell.workload
    obs.host = {"traced_decode_contexts": contexts,
                "counters": counters or {}}
    obs.t0, obs.t1 = 0, 120 * MS
    obs.peak = peaks.peaks("TPU v5 lite")
    return obs


def test_the_new_readers_on_a_made_up_trace(ouro_spec, monkeypatch):
    monkeypatch.setattr(trace_reduce, "load", lambda path: _fake_trace())
    cell = ouro_spec.cell(CELL)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    result = run.run_cell(ouro_spec, cell, 7, 0.5, True, t0=time.time(),
                          device=device)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(m), sorted(m)
    # (6 + 2 + 0.5) ms a pass, four passes a step, over R = 4
    assert m["loop_step_device_ms"] == pytest.approx(8.5)
    assert m["decode_device_ms"] == pytest.approx(40.0)
    assert m["admissions_blocked_per_step"] >= 0.0
    assert 0 < m["loop_decode_hbm_roofline"] < 100
    assert 0 < m["gqa_decode_roofline"] < 100


def test_the_decode_roofline_counts_weights_a_step_and_cache_a_lane(
        ouro_spec):
    """At the published sizes: two whole steps of 40 ms over 14 lanes at
    250 cached tokens; the loop's weights four times a step, the head once,
    the lanes' K and V over 192 rows."""
    contexts = [250] * 14 * 2
    obs = _obs(ouro_spec, _fake_trace(), contexts, config=OURO)
    got = ouro_spec.module("layer_metrics",
                           "loop_decode_hbm_roofline").read(obs)
    flops, moved = costs.decode_step(OURO, contexts, 2)
    assert moved / 2 == pytest.approx(25.6e9, rel=0.01)
    assert got == pytest.approx(100 * (moved / 819e9) / 80e-3)
    assert obs.host["loop_decode_hbm_roofline_bound"] == "bandwidth"
    assert obs.host["loop_decode_steps_traced"] == 2
    assert obs.host["loop_decode_lanes_mean"] == 14
    # the decode kernels under gqa_attention: 4 a step, not the writer
    roof = ouro_spec.module("layer_metrics", "gqa_decode_roofline").read(obs)
    assert obs.host["gqa_decode_kernels"] == 8
    kv = sum(contexts) * 1_572_864
    assert roof == pytest.approx(100 * (kv / 819e9) / 16e-3)


def test_admissions_blocked_reads_the_counter_over_the_dispatches(ouro_spec):
    read = ouro_spec.module("layer_metrics",
                            "admissions_blocked_per_step").read
    obs = _obs(ouro_spec, _fake_trace(), [], counters={
        "serving.admissions_blocked": 30.0,
        "serving.decode_batch_size_count": 600.0})
    assert read(obs) == pytest.approx(0.05)
    obs.host["counters"]["serving.admissions_blocked"] = 0.0
    assert read(obs) == 0.0
    # a program without the counter, a window without a dispatch
    assert read(_obs(ouro_spec, _fake_trace(), [], counters={
        "serving.decode_batch_size_count": 600.0})) is None
    assert read(_obs(ouro_spec, _fake_trace(), [], counters={
        "serving.admissions_blocked": 3.0})) is None


def test_readers_give_nothing_where_there_is_nothing_to_read(ouro_spec):
    # a program whose decode step carries no such scope
    obs = _obs(ouro_spec, _fake_trace(scope="forward"), [100] * 4)
    assert ouro_spec.module("layer_metrics",
                            "loop_step_device_ms").read(obs) is None
    # a trace that holds no whole decode step
    empty = trace_reduce.Trace({0: [("%fusion.1", 0, MS)]}, {0: []}, [])
    empty.scoped = [("%fusion.1", 0, MS, "jit(step)/loop_step/dot")]
    obs = _obs(ouro_spec, empty, [100] * 4)
    for name in NEW_METRICS[:2]:
        assert ouro_spec.module("layer_metrics", name).read(obs) is None, name
    # another family: its costs name no decode step, its config no steps
    gpt = dict(toy.TOY_GPT)
    obs = _obs(ouro_spec, _fake_trace(), [100] * 4, config=gpt)
    assert ouro_spec.module("layer_metrics",
                            "loop_decode_hbm_roofline").read(obs) is None
    assert ouro_spec.module("layer_metrics",
                            "loop_step_device_ms").read(obs) is None


def test_benchmark_json_lists_the_cell_where_it_reports():
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    listed = [m["name"] for m in data["per_layer"]
              if REAL_CELL in m.get("workloads", ())]
    assert sorted(listed) == sorted((
        "first_token_p95_ms", "token_gap_p95_ms", "queue_wait_p95_ms",
        "decode_step_ms", "sched_host_ms", "decode_batch_mean",
        "page_util_mean", "decode_device_ms", "prefill_device_share",
        "serve_mfu", "device_idle_share.serve", "idle_named_share.serve",
        "gqa_decode_roofline") + NEW_METRICS)
    layers = {"loop_decode_hbm_roofline": ("looped decoder", "device_trace"),
              "loop_step_device_ms": ("looped decoder", "device_trace"),
              "admissions_blocked_per_step": ("serving engine",
                                              "program_counter")}
    for name, (layer, source) in layers.items():
        entry = next(m for m in data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
        assert (entry["layer"], entry["source"], entry["moves"]) \
            == (layer, source, "serve_tok_s")
    e2e = {m["name"]: m for m in data["end_to_end"]}
    assert e2e["serve_tok_s"]["workloads"][-1] == REAL_CELL
    cell = data["workloads"][-1]
    assert cell["name"] == REAL_CELL and cell["chips"] == 1 \
        and len(cell["why"]) <= 200 and cell["traffic"] == "batch_closed_1k"
    with open(os.path.join(toy.BENCH, "workloads", REAL_CELL + ".json")) as f:
        wl = json.load(f)
    assert wl["engine"] == {"num_slots": 16, "page_size": 16,
                            "max_model_len": 1024, "num_pages": 320,
                            "prefill_chunk_tokens": 256, "kv_dtype": None,
                            "numeric_guard": True}
    assert wl["dtype"] == "bfloat16" and wl["compared_requests"] == 12
    with open(os.path.join(toy.BENCH, "mixes", "batch_closed_1k.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["pool_size"], mix["lengths_seed"]) \
        == (16, 256, 32)
    assert mix["prompt"] == {"median": 128, "sigma": 0.6, "min": 32,
                             "max": 512}
    assert mix["output"] == {"median": 192, "sigma": 0.5, "min": 64,
                             "max": 512}
    assert (mix["max_total"], mix["stagger_s"], mix["ramp_seconds"],
            mix["poll_s"]) == (1024, 5.0, 15.0, 0.001)
