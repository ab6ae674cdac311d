"""The ``lfm2`` family of the benchmark on the CPU: the configuration's file
against the published numbers written here, the cut and the parameters held,
the cost functions by hand, weights from the seed handed to the program
without a second copy, the five new readers on a made-up trace (and nothing
where the trace lacks their scopes), and a whole run of a toy cell through
the serve entry in which the control and an altered token fail where the
program passes."""

import importlib
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import peaks, run, spec, trace_reduce
from chipbench.tools import readings

from . import toy

costs = importlib.import_module("chipbench.costs.lfm2")
paged = importlib.import_module("chipbench.costs.paged_decode")
ref = importlib.import_module("chipbench.reference.lfm2")

with open(os.path.join(toy.BENCH, "configs", "lfm2_24b_a2b_l10.json")) as f:
    LFM2 = json.load(f)

REAL_CELL = "lfm2_24b_a2b_l10.batch_closed_4k"
TOY = {"family": "lfm2", "source": "toy sizes for the CPU tests",
       "hidden_size": 32, "intermediate_size": 48, "moe_intermediate_size": 8,
       "num_hidden_layers": 6,
       "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                       "conv"],
       "num_dense_layers": 2, "num_attention_heads": 4,
       "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
       "norm_topk_prob": True, "routed_scaling_factor": 1,
       "use_expert_bias": True, "conv_L_cache": 3, "conv_bias": False,
       "norm_eps": 1e-5,
       "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
       "max_position_embeddings": 128000, "vocab_size": 211,
       "tie_word_embeddings": True, "initializer_range": 0.02,
       "norm_topk_eps": 1e-6, "serve_positions": 96, "reduced": []}
TOY_CELL = dict(toy.TOY_SERVE_CELL, config="toy_lfm2",
                weights={"outlier_channels": 2, "outlier_gain": 16},
                limits={"logit_gap_max": 1e-4, "logit_gap_mean": 1e-6})
CELL = "toy_lfm2.toy_closed"
NEW_METRICS = ("gqa_decode_roofline", "moe_experts_decode_ms",
               "moe_route_decode_ms", "short_conv_decode_ms",
               "moe_decode_share")


@pytest.fixture(scope="module")
def lfm2_spec(tmp_path_factory):
    root = toy.make_root(tmp_path_factory.mktemp("lfm2") / "root")
    bench = os.path.join(root, "chipbench")
    toy._dump(os.path.join(bench, "configs", "toy_lfm2.json"), TOY)
    toy._dump(os.path.join(bench, "workloads", CELL + ".json"), TOY_CELL)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy_lfm2", "source": "tests",
                            "file": "chipbench/configs/toy_lfm2.json",
                            "reduced": [], "why": "toy"})
    data["workloads"].append({"name": CELL, "config": "toy_lfm2",
                              "traffic": "toy_closed", "chips": 1,
                              "why": "toy"})
    for m in data["end_to_end"] + data["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) and CELL not in m["workloads"]:
            m["workloads"].append(CELL)
    toy._dump(os.path.join(root, "BENCHMARK.json"), data)
    return spec.Spec(root=root)


# ------------------------------------------------------- the configuration
def test_every_width_is_the_published_one():
    published = {
        "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts": 64,
        "num_experts_per_tok": 4, "num_dense_layers": 2, "vocab_size": 65536,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe",
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    for key, value in published.items():
        assert LFM2[key] == value, key
    assert LFM2["family"] == "lfm2" and LFM2["head_dim"] == 64
    assert LFM2["tie_word_embeddings"] is True
    assert LFM2["serve_positions"] == 4096
    for key in ("tie_word_embeddings", "head_dim", "norm_topk_eps",
                "initializer_range", "expert_bias", "serve_positions"):
        assert key in LFM2["assumed"], key


def test_the_cut_is_the_first_ten_layers_and_nothing_else():
    assert LFM2["num_hidden_layers"] == 10
    assert LFM2["layer_types"] == [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv"]
    whole = LFM2["published"]
    assert whole["num_hidden_layers"] == 40 == len(whole["layer_types"])
    assert whole["layer_types"][:10] == LFM2["layer_types"]
    assert whole["layer_types"].count("full_attention") == 10
    assert set(LFM2["reduced"]) == {"num_hidden_layers", "layer_types"} \
        == set(whole) == set(LFM2["reduced_how"])
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2_24b_a2b_l10")
    assert entry["reduced"] == LFM2["reduced"]
    assert entry["source"].startswith(LFM2["source"].split(" ")[0])
    assert "nothing is sharded" in LFM2["deployment"]


def test_no_width_differs_from_the_catalog():
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("the catalog is not on this machine")
    with open(path) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] in LFM2["source"])
    differs = {k for k, v in row["config"].items() if LFM2.get(k) != v}
    assert differs == set(LFM2["reduced"])
    for k in differs:
        assert LFM2["published"][k] == row["config"][k]


def test_parameters_held_by_hand():
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64
    dense = 3 * 2048 * 11776
    experts = 64 * 3 * 2048 * 1536 + 2048 * 64
    norms = 10 * 2 * 2048 + 2048
    held = (8 * conv + 2 * attention + 2 * dense + 8 * experts
            + 65536 * 2048 + norms)
    assert ref.n_params(LFM2) == held == 5_267_089_664 \
        == LFM2["assumed"]["parameters_held_here"]
    whole = dict(LFM2, **LFM2["published"])
    assert ref.n_params(whole) == LFM2["assumed"]["parameters_whole_model"] \
        == (30 * conv + 10 * attention + 2 * dense + 38 * experts
            + 65536 * 2048 + 40 * 2 * 2048 + 2048)
    # the issue's arithmetic: 10.53 GB in bfloat16, an expert layer 1.21 GB
    assert round(2 * held / 1e9, 2) == 10.53
    assert round(2 * experts / 1e9, 2) == 1.21


def test_costs_by_hand():
    assert costs.attention_shape(LFM2) == (2, 32, 8, 64)
    assert costs.conv_params(LFM2) == 16_783_360
    assert costs.attention_params(LFM2) == 10_485_760
    assert costs.expert_params(LFM2) == 9_437_184
    block = (8 * 16_783_360 + 2 * 10_485_760 + 2 * 72_351_744
             + 8 * (2048 * 64 + 4 * 9_437_184))
    assert costs.block_params_per_token(LFM2) == block == 602_980_352
    assert costs.head_params(LFM2) == 134_217_728
    # a token decoded against 2,000 cached: attention in 2 of 10 layers
    attn = 2 * 4 * 2000 * 2048
    assert costs.decode_flops(LFM2, 2000) == 2 * (block + 134_217_728) + attn
    assert costs.prefill_flops(LFM2, 1024) == 2 * block * 1024 \
        + 2 * 4 * 2048 * 1024 * 1025 // 2 + 2 * 134_217_728
    # the decode kernel's cost: K and V of 8 heads of 64, both layers
    flops, moved = paged.step([2000] * 32, 32, 8, 64)
    assert moved == 2 * 32 * 2000 * 8 * 64 * 2
    assert flops == 4 * 32 * 2000 * 32 * 64


# ------------------------------------------------ weights and the program
def test_weights_come_from_the_seed_leaf_by_leaf():
    a, b, c = (ref.init_params(s, TOY) for s in (2 ** 31 + 9, 2 ** 31 + 9, 9))
    assert set(a) == set(ref.param_shapes(TOY))
    for k, shape in ref.param_shapes(TOY).items():
        assert a[k].shape == shape
        assert np.array_equal(a[k], b[k]) and not np.array_equal(a[k], c[k])
    gains = a["model.layers.0.operator_norm.weight"]
    assert abs(float(gains.mean()) - 1.0) < 0.05
    bias = a["model.layers.2.feed_forward.e_score_correction_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.abs(bias).max()) > 0
    bf = ref.init_params(9, TOY, dtype=jnp.bfloat16)
    assert bf["model.embed_tokens.weight"].dtype == jnp.bfloat16
    assert bf["model.layers.2.feed_forward.e_score_correction_bias"].dtype \
        == jnp.float32


def test_shaped_weights_are_loud_where_no_router_listens():
    shaped = ref.init_params(5, TOY, shape=TOY_CELL["weights"])
    plain = ref.init_params(5, TOY)
    loud = np.asarray(shaped["model.layers.0.operator_norm.weight"]
                      / plain["model.layers.0.operator_norm.weight"])
    assert sorted(np.round(loud).astype(int).tolist())[-2:] == [16, 16]
    assert (np.round(loud) == 16).sum() == 2
    for name in ("model.layers.1.ffn_norm.weight",
                 "model.embedding_norm.weight"):
        assert (np.round(np.asarray(shaped[name] / plain[name])) == 16).sum() \
            == 2, name
    # the norm in front of a router stays as drawn
    assert np.array_equal(shaped["model.layers.3.ffn_norm.weight"],
                          plain["model.layers.3.ffn_norm.weight"])


def test_the_program_takes_the_leaves_without_a_second_copy(lfm2_spec):
    models = lfm2_spec.module("models", "lfm2")
    params = ref.init_params(3, TOY, dtype=jnp.bfloat16)
    model = models.build(TOY, params, ref, dtype="bfloat16")
    named = dict(model.named_parameters())
    named.update({k: v for k, v in model.named_buffers()
                  if k.endswith("e_score_correction_bias")})
    assert set(named) == set(params)
    for name, leaf in named.items():
        assert leaf._value is params[name], name
    with pytest.raises(RuntimeError, match="did not take"):
        models.build(TOY, dict(params, stray=params[
            "model.embedding_norm.weight"]), ref)
    with pytest.raises(RuntimeError, match="the program wants"):
        models.build(dict(TOY, intermediate_size=40), params, ref)


def test_reference_counts_the_load_over_real_positions():
    ref.LOAD.update(counts=None, calls=0)
    params = ref.init_params(3, TOY)
    ids = np.zeros((1, 24), np.int64)
    ids[0, :10] = np.arange(1, 11)
    jax.block_until_ready(ref.logits(params, jnp.asarray(ids), TOY))
    jax.effects_barrier()
    counts = ref.LOAD["counts"]
    assert counts.shape == (4, 8) and ref.LOAD["calls"] == 1
    assert (counts.sum(1) == 10 * 2).all()       # 10 real positions, top-2
    summary = ref.load_summary()
    assert summary["requests"] == 1 and summary["busiest_over_mean"] >= 1.0


# ------------------------------------------------------------ a whole run
@pytest.fixture(scope="module")
def sound(lfm2_spec):
    kept = {}
    cell = lfm2_spec.cell(CELL)
    result = run.run_cell(lfm2_spec, cell, 2 ** 31 + 3, 1.0, False,
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


def test_control_and_altered_token_fail_where_the_program_passes(lfm2_spec,
                                                                 sound):
    cell, _, kept = sound
    ctx = run.RunContext(lfm2_spec, cell, 2 ** 31 + 3, 0.5, False,
                         time.time())
    got = lfm2_spec.module("entries", "serve").stand_ins(ctx, kept)
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


def _fake_trace(ragged_a_step=16, ragged_name="ragged-dot", dropped=0):
    """Two decode steps of 10 ms with a chunk program between them; the
    chunk's operations carry the same scopes and are left out.  A step holds
    ``ragged_a_step`` unscoped grouped kernels of 0.25 ms (the toy's four
    expert layers issue 16: ``costs.grouped_kernels``); the LAST step's
    first ``dropped`` of them are missing, as where the profiler lost
    events."""
    kernel = ' custom-call(...), custom_call_target="tpu_custom_call"'
    step, chunk = "jit(step)/", "jit(chunk)/"

    def one_step(t, prog, drop=0):
        grouped = [
            (f"%{ragged_name}-{'metadata' if i % 4 == 0 else 'none'}.{5 + i} ="
             + kernel, t + 4 * MS + i * MS // 4, MS // 4,
             f"{ragged_name}-{'metadata' if i % 4 == 0 else 'none'}")
            for i in range(drop, ragged_a_step)]
        return [
            ("%fusion.1 = fusion(...)", t, MS, prog + "short_conv/dot_general"),
            ("%gqa_attention.2 =" + kernel, t + MS, MS // 2,
             prog + "gqa_attention/pallas_call"),
            ("%paged_write.3 =" + kernel, t + 2 * MS, MS // 4,
             prog + "gqa_attention/jit(_paged_write_pallas)/paged_write/"
             "pallas_call"),
            ("%sort.4 = sort(...)", t + 3 * MS, MS, prog + "moe_route/sort"),
            *grouped,
            ("%fusion.6 = fusion(...)", t + 8 * MS + MS // 2, MS,
             prog + "moe_experts/mul"),
            ("%chunk_attention.7 =" + kernel, t + 9 * MS + MS // 2, MS // 2,
             prog + "gqa_attention/chunk_attention/pallas_call")]

    scoped = one_step(0, step) + one_step(10 * MS, chunk) \
        + one_step(20 * MS, step, dropped)
    trace = trace_reduce.Trace(
        {0: [e[:3] for e in scoped]},
        {0: [("jit_step(1)", 0, 10 * MS), ("jit_chunk(2)", 10 * MS, 10 * MS),
             ("jit_step(1)", 20 * MS, 10 * MS)]},
        [("main", "bench.window", 0, 30 * MS)])
    trace.scoped = scoped
    return trace


def test_the_new_readers_on_a_made_up_trace(lfm2_spec, monkeypatch):
    monkeypatch.setattr(trace_reduce, "load", lambda path: _fake_trace())
    cell = lfm2_spec.cell(CELL)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    result = run.run_cell(lfm2_spec, cell, 7, 0.5, True, t0=time.time(),
                          device=device)
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(m), sorted(m)
    assert m["short_conv_decode_ms"] == pytest.approx(1.0)
    assert m["moe_route_decode_ms"] == pytest.approx(1.0)
    # the compiler's unscoped grouped products count with the scope's own
    assert m["moe_experts_decode_ms"] == pytest.approx(5.0)
    assert m["moe_decode_share"] == pytest.approx(60.0)
    assert m["decode_device_ms"] == pytest.approx(10.0)
    assert m["chunk_attn_device_ms"] == pytest.approx(0.5)


def _obs(lfm2_spec, trace, contexts):
    cell = lfm2_spec.cell(CELL)

    class Obs:
        pass
    obs = Obs()
    obs.spec, obs.trace, obs.cell = lfm2_spec, trace, cell
    obs.config, obs.workload = dict(cell.config, **{
        k: LFM2[k] for k in ("hidden_size", "num_attention_heads",
                             "num_key_value_heads")}), cell.workload
    obs.host = {"traced_decode_contexts": contexts}
    obs.t0, obs.t1 = 0, 30 * MS
    obs.peak = peaks.peaks("TPU v5 lite")
    return obs


def test_the_decode_roofline_counts_the_attention_kernels_alone(lfm2_spec):
    """One attention layer of the toy pattern at the published heads: two
    decode kernels of 0.5 ms in the window, not the writer, not the chunk
    kernel inside a decode step's neighbour, not the grouped products."""
    contexts = [2000] * 64
    obs = _obs(lfm2_spec, _fake_trace(), contexts)
    got = lfm2_spec.module("layer_metrics", "gqa_decode_roofline").read(obs)
    flops, moved = paged.step(contexts, 32, 8, 64)
    least = max(flops / 197e12, moved / 819e9)
    # 2 steps x (decode kernel 0.5 ms + a chunk_attention call 0.5 ms that
    # the made-up step holds): every Mosaic call under gqa_attention but
    # the writer
    assert obs.host["gqa_decode_kernels"] == 4
    assert got == pytest.approx(100 * least / 2e-3)
    assert obs.host["gqa_decode_roofline_bound"] == "bandwidth"


def test_readers_give_nothing_for_a_program_without_the_scopes(lfm2_spec):
    trace = trace_reduce.Trace(
        {0: [("%fusion.1", 0, MS)]}, {0: [("jit_step(1)", 0, 2 * MS)]}, [])
    trace.scoped = [("%fusion.1", 0, MS, "jit(step)/forward/dot")]
    obs = _obs(lfm2_spec, trace, [100] * 4)
    obs.t1 = 2 * MS
    for name in NEW_METRICS:
        assert lfm2_spec.module("layer_metrics", name).read(obs) is None, name
    # nor for a trace that holds no decode step at all
    empty = trace_reduce.Trace({0: [("%fusion.1", 0, MS)]}, {0: []}, [])
    empty.scoped = list(trace.scoped)
    obs = _obs(lfm2_spec, empty, [100] * 4)
    for name in NEW_METRICS:
        assert lfm2_spec.module("layer_metrics", name).read(obs) is None, name


@pytest.mark.parametrize("trace, why", [
    (dict(ragged_a_step=17), "a second user of ragged products"),
    (dict(ragged_a_step=12), "an expert layer's kernels fused away"),
    (dict(ragged_name="grouped-matmul"), "the lowering under another name"),
])
def test_unscoped_grouped_kernels_count_only_in_the_expected_number(
        lfm2_spec, trace, why):
    """The expert readers rest on the compiler's kernel names: where an
    execution holds another number of them than the costs give, they read
    nothing (the driver refuses a traced line that lacks a listed metric)
    and the host line says what was found; the other readers go on."""
    obs = _obs(lfm2_spec, _fake_trace(**trace), [100] * 4)
    read = {name: lfm2_spec.module("layer_metrics", name).read(obs)
            for name in NEW_METRICS}
    assert read["moe_experts_decode_ms"] is None, why
    assert read["moe_decode_share"] is None, why
    assert read["moe_route_decode_ms"] == pytest.approx(1.0)
    assert read["short_conv_decode_ms"] == pytest.approx(1.0)
    found = obs.host["moe_experts_unscoped_kernels"]
    assert found["expected"] == costs.grouped_kernels(obs.config) == 16
    assert found["fullest_execution"] == (
        0 if "ragged_name" in trace else trace["ragged_a_step"])


def test_events_the_profiler_dropped_do_not_silence_the_expert_readers(
        lfm2_spec):
    obs = _obs(lfm2_spec, _fake_trace(dropped=3), [100] * 4)
    read = lfm2_spec.module("layer_metrics", "moe_experts_decode_ms").read
    # (16 + 13) kernels of 0.25 ms and two fusions of 1 ms over two steps
    assert read(obs) == pytest.approx((29 * 0.25 + 2) / 2)
    assert obs.host["moe_experts_ops_per_decode_step"] == 31 / 2
    assert obs.host["moe_experts_ops_by_name_stack_per_decode_step"] == 1.0


def test_a_reader_of_another_program_names_it(lfm2_spec):
    """``decode_scope`` by default reads the decode program; the chunk
    program's share of a scope is the same call with its name."""
    from chipbench import decode_scope

    obs = _obs(lfm2_spec, _fake_trace(), [100] * 4)
    assert decode_scope.executions(obs, program="jit_chunk(") \
        == [(10 * MS, 20 * MS)]
    assert decode_scope.per_step_ms(obs, "moe_experts", "jit_chunk(") \
        == pytest.approx(5.0)
    assert decode_scope.per_step_ms(obs, "short_conv", "jit_chunk(") \
        == pytest.approx(1.0)
    assert decode_scope.per_step_ms(obs, "moe_experts", "jit_prefill(") \
        is None


def test_benchmark_json_lists_the_cell_where_it_reports():
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    listed = [m["name"] for m in data["per_layer"]
              if REAL_CELL in m.get("workloads", ())]
    assert len(listed) == 19 and set(NEW_METRICS) <= set(listed)
    assert "paged_decode_roofline" not in listed
    for name in NEW_METRICS:
        entry = next(m for m in data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
        assert entry["moves"] == "serve_tok_s"
        assert entry["source"] == "device_trace"
    e2e = {m["name"]: m for m in data["end_to_end"]}
    assert REAL_CELL in e2e["serve_tok_s"]["workloads"]
    cell = next(w for w in data["workloads"] if w["name"] == REAL_CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    with open(os.path.join(toy.BENCH, "workloads", REAL_CELL + ".json")) as f:
        wl = json.load(f)
    assert wl["engine"] == {"num_slots": 32, "page_size": 16,
                            "max_model_len": 4096,
                            "prefill_chunk_tokens": 256, "kv_dtype": None,
                            "numeric_guard": True}
    assert wl["dtype"] == "bfloat16" and wl["compared_requests"] == 12
    with open(os.path.join(toy.BENCH, "mixes", "batch_closed_4k.json")) as f:
        mix = json.load(f)
    assert (mix["clients"], mix["pool_size"], mix["lengths_seed"]) \
        == (32, 256, 30)
    assert mix["prompt"] == {"median": 1024, "sigma": 0.6, "min": 288,
                             "max": 3072}
    assert mix["output"] == {"median": 128, "sigma": 0.5, "min": 32,
                             "max": 512}
    assert (mix["max_total"], mix["stagger_s"], mix["ramp_seconds"],
            mix["poll_s"]) == (4096, 5.0, 15.0, 0.001)
