"""The ``smallthinker`` family of the benchmark on the CPU: the
configuration's file against the published config's numbers written here,
the cut and the parameters held, the cost functions by hand, weights drawn
from the seed where they are read (the same inside a jitted forward as
handed to the program), the three new readers on a made-up trace, and a
whole run of a toy cell through the serve entry in which the control and
an altered token fail where the program passes."""

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

costs = importlib.import_module("chipbench.costs.smallthinker")
ref = importlib.import_module("chipbench.reference.smallthinker")

with open(os.path.join(toy.BENCH, "configs",
                       "smallthinker_21b_a3b_l8.json")) as f:
    ST = json.load(f)

REAL_CELL = "smallthinker_21b_a3b_l8.batch_closed_16k"
PERIOD = [0, 1, 1, 1]
#: the published config.json of SmallThinker-21BA3B-Instruct, the keys that
#: say something about its shape
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}

LAYOUT = [0, 1, 1, 1, 0, 1, 1, 1]
TOY = {"family": "smallthinker", "source": "toy sizes for the CPU tests",
       "vocab_size": 211, "hidden_size": 32, "num_hidden_layers": 8,
       "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
       "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 16,
       "moe_num_active_primary_experts": 6,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "rms_norm_eps": 1e-6, "rope_theta": 1500000, "rope_layout": LAYOUT,
       "sliding_window_layout": LAYOUT, "sliding_window_size": 24,
       "max_position_embeddings": 96, "tie_word_embeddings": False,
       "initializer_range": 0.02, "reduced": []}
TOY_CELL = dict(toy.TOY_SERVE_CELL, config="toy_smallthinker",
                weights={"outlier_channels": 2, "outlier_gain": 16},
                limits={"logit_gap_max": 1e-4, "logit_gap_mean": 1e-6})
CELL = "toy_smallthinker.toy_closed"
NEW_METRICS = ("swa_decode_roofline", "swa_chunk_roofline",
               "swa_pages_per_slot_max")


@pytest.fixture(scope="module")
def st_spec(tmp_path_factory):
    root = toy.make_root(tmp_path_factory.mktemp("smallthinker") / "root")
    bench = os.path.join(root, "chipbench")
    toy._dump(os.path.join(bench, "configs", "toy_smallthinker.json"), TOY)
    toy._dump(os.path.join(bench, "workloads", CELL + ".json"), TOY_CELL)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        data = json.load(f)
    data["configs"].append({"name": "toy_smallthinker", "source": "tests",
                            "file": "chipbench/configs/toy_smallthinker.json",
                            "reduced": [], "why": "toy"})
    data["workloads"].append({"name": CELL, "config": "toy_smallthinker",
                              "traffic": "toy_closed", "chips": 1,
                              "why": "toy"})
    for m in data["end_to_end"] + data["per_layer"]:
        if REAL_CELL in m.get("workloads", ()) and CELL not in m["workloads"]:
            m["workloads"].append(CELL)
    toy._dump(os.path.join(root, "BENCHMARK.json"), data)
    return spec.Spec(root=root)


# ------------------------------------------------------- the configuration
def test_published_counts_are_the_configs():
    """Every number of the published config is the file's, under its key, where
    the cut does not name the key; the cut keys keep the published value
    under ``published``."""
    for key, value in PUBLISHED.items():
        if key in ST["reduced"]:
            assert ST["published"][key] == value, key
        else:
            assert ST[key] == value, key
    assert sorted(ST["reduced"]) == sorted(ST["published"])


def test_the_cut_is_the_first_eight_layers_and_nothing_else():
    assert ST["num_hidden_layers"] == 8
    assert ST["rope_layout"] == ST["sliding_window_layout"] == LAYOUT
    assert ST["published"]["rope_layout"][:8] == LAYOUT
    for key in ST["reduced"]:
        assert key in ST["reduced_how"]
    assert set(ST["assumed"]) >= {"router input", "window", "rotary",
                                  "activation", "secondary experts"}


def test_parameters_held_by_hand():
    H, V = 2560, 151936
    attention = 2 * H * 28 * 128 + 2 * H * 4 * 128
    # the router, 64 experts and two RMSNorm gains
    layer = attention + H * 64 + 64 * 3 * H * 768 + 2 * H
    assert (attention, layer) == (20971520, 398627840)
    assert ref.n_params(ST) == 8 * layer + 2 * V * H + H == 3966937600
    assert ST["assumed"]["parameters_held_here"] == ref.n_params(ST)
    whole = dict(ST, num_hidden_layers=52, **{
        k: ST["published"][k] for k in ("rope_layout",
                                        "sliding_window_layout")})
    assert ST["assumed"]["parameters_whole_model"] == ref.n_params(whole)


def test_costs_by_hand():
    H, W = 2560, 4096
    per_token = 8 * (2 * H * 28 * 128 + 2 * H * 4 * 128 + H * 64
                     + 6 * 3 * H * 768)
    assert costs.block_params_per_token(ST) == per_token
    assert costs.grouped_kernels(ST) == 0
    key = 4 * 28 * 128
    assert costs.attention_flops(ST, 10000) == key * (2 * 10000 + 6 * W)
    assert costs.decode_flops(ST, 10) == 2 * (per_token + 151936 * H) \
        + key * 8 * 10
    # a prompt of 5,000: full layers see 1..5000, window layers 1..4096
    # then 4,096 for the last 904
    seen = W * (W + 1) // 2 + 904 * W
    assert costs.prefill_flops(ST, 5000) == 2 * per_token * 5000 \
        + key * (2 * 5000 * 5001 // 2 + 6 * seen) + 2 * 151936 * H
    flops, moved = costs.swa_decode(ST, [100, 9000])
    assert flops == 6 * key * (100 + W)
    assert moved == 6 * (2 * (100 + W) * 4 * 128 * 2 + 2 * 28 * 128 * 6)
    assert costs.swa_chunk_flops(ST, 4000, 256) == 6 * key * sum(
        min(p + 1, W) for p in range(4000, 4256))


# ----------------------------------------------------------- the weights
def test_weights_are_drawn_where_read_and_the_same_inside_a_program():
    w = ref.init_params(2 ** 31 + 5, TOY, dtype=jnp.bfloat16,
                        shape={"outlier_channels": 2, "outlier_gain": 16})
    assert len(w) == len(ref.param_shapes(TOY)) and "lm_head.weight" in w
    name = "model.layers.3.block_sparse_moe.w_up"
    eager = np.asarray(w[name], np.float32)
    inside = np.asarray(jax.jit(lambda p: p[name])(w), np.float32)
    assert eager.shape == (16, 32, 16) and np.array_equal(eager, inside)
    assert np.array_equal(np.asarray(w[name]), np.asarray(
        ref.init_params(2 ** 31 + 5, TOY, dtype=jnp.bfloat16)[name]))
    # loud channels in the experts' norm, none in the router's
    post = np.asarray(w["model.layers.0.post_attention_layernorm.weight"],
                      np.float32)
    pre = np.asarray(w["model.layers.0.input_layernorm.weight"], np.float32)
    assert (post > 8).sum() == 2 and (pre > 8).sum() == 0


def test_the_program_takes_every_leaf(st_spec):
    cell = st_spec.cell(CELL)
    family = st_spec.module("models", "smallthinker")
    params = ref.init_params(3, cell.config)
    model = family.build(cell.config, params, ref)
    names = {k for k, _ in model.named_parameters()}
    assert names == set(ref.param_shapes(cell.config))


# ---------------------------------------------------------- a whole run
@pytest.fixture(scope="module")
def sound(st_spec):
    kept = {}
    cell = st_spec.cell(CELL)
    result = run.run_cell(st_spec, cell, 2 ** 31 + 3, 1.0, False,
                          t0=time.time(), kept=kept)
    return cell, result, kept


def test_a_whole_run_through_the_serve_entry_is_correct(sound):
    cell, result, kept = sound
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"serve_tok_s", "setup_s"}
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert kept["numbers"]["tokens_compared"] >= 50
    # some requests' contexts went past the window of 24
    assert max(len(p) + len(o) for p, o in kept["served"]) > 24


def test_control_and_altered_token_fail_where_the_program_passes(st_spec,
                                                                 sound):
    cell, _, kept = sound
    ctx = run.RunContext(st_spec, cell, 2 ** 31 + 3, 0.5, False,
                         time.time())
    got = st_spec.module("entries", "serve").stand_ins(ctx, kept)
    limits = cell.workload["limits"]
    assert got["control"]["logit_gap_max"] > 3 * limits["logit_gap_max"]
    assert got["token_altered"]["logit_gap_max"] \
        > 100 * limits["logit_gap_max"]
    for name in ("control", "token_altered"):
        row = readings.judged(cell, 1, name, got[name])
        assert row["correct"] is False and row["failed_numbers"]


def test_a_program_that_ignores_the_window_is_not_correct(st_spec):
    """``tools/window_ignored.py``: every window layer attending the whole
    context, held to the reference with the window, fails the comparison;
    the family's program config is its own again afterwards."""
    from chipbench.tools.window_ignored import window_ignored

    cell = st_spec.cell(CELL)
    family = st_spec.module("models", "smallthinker")
    made = family.program_config
    row = window_ignored(st_spec, cell, 2 ** 31 + 3, 1.0, 48)
    assert family.program_config is made
    assert row["longest_compared"] > TOY["sliding_window_size"]
    assert row["correct"] is False
    assert row["numbers"]["logit_gap_max"] > 100 * TOY_CELL["limits"][
        "logit_gap_max"]


# ------------------------------------------------------------- the readers
MS = 1_000_000
KERNEL = ' custom-call(...), custom_call_target="tpu_custom_call"'


def _fake_trace():
    """A decode step of 10 ms, a chunk program of 10 ms, a decode step: a
    step's window-layer decode kernel 0.5 ms and its writer 0.25 ms, the
    full layer's decode kernel 0.5 ms; the chunk's window-layer chunk
    kernel 2 ms, the full layer's 1 ms."""
    step, chunk = "jit(step)/", "jit(chunk)/"

    def one(t, prog):
        att = "chunk_attention/" if prog == chunk else ""
        return [
            ("%swa.1 =" + KERNEL, t + MS, (2 if att else 0.5) * MS,
             prog + f"swa_attention/{att}pallas_call"),
            ("%paged_write.2 =" + KERNEL, t + 3 * MS, MS // 4,
             prog + "swa_attention/jit(_paged_write_pallas)/paged_write/"
             "pallas_call"),
            ("%full.3 =" + KERNEL, t + 4 * MS, (1 if att else 0.5) * MS,
             prog + f"global_attention/{att}pallas_call"),
            ("%fusion.4 = fusion(...)", t + 5 * MS, MS, prog + "moe_route/x")]

    scoped = one(0, step) + one(10 * MS, chunk) + one(20 * MS, step)
    trace = trace_reduce.Trace(
        {0: [e[:3] for e in scoped]},
        {0: [("jit_step(1)", 0, 10 * MS), ("jit_chunk(2)", 10 * MS, 10 * MS),
             ("jit_step(1)", 20 * MS, 10 * MS)]},
        [("main", "bench.window", 0, 30 * MS)])
    trace.scoped = scoped
    # the chunk's dispatch: 256 tokens from position 4,000
    trace.chunk_spans = [(9 * MS, 4000, 256)]
    return trace


def _obs(st_spec, trace, contexts):
    cell = st_spec.cell(REAL_CELL)

    class Obs:
        pass
    obs = Obs()
    obs.spec, obs.trace, obs.cell = st_spec, trace, cell
    obs.config, obs.workload = cell.config, cell.workload
    obs.host = {"traced_decode_contexts": contexts}
    obs.t0, obs.t1 = 0, 30 * MS
    obs.peak = peaks.peaks("TPU v5 lite")
    return obs


def test_the_window_rooflines_count_their_kernels_alone(st_spec):
    contexts = [3000, 9000] * 2
    obs = _obs(st_spec, _fake_trace(), contexts)
    read = lambda name: st_spec.module("layer_metrics", name).read(obs)
    flops, moved = costs.swa_decode(ST, contexts)
    least = max(flops / 197e12, moved / 819e9)
    # two steps' window kernels of 0.5 ms: not the writer, not the full
    # layer's, not the chunk's
    assert read("swa_decode_roofline") == pytest.approx(100 * least / 1e-3)
    assert obs.host["swa_decode_kernels"] == 2
    assert obs.host["swa_decode_roofline_bound"] == "bandwidth"
    want = costs.swa_chunk_flops(ST, 4000, 256) / 197e12
    assert read("swa_chunk_roofline") == pytest.approx(100 * want / 2e-3)
    assert obs.host["swa_chunk_kernels"] == 1


def test_readers_give_nothing_for_a_program_without_the_scopes(st_spec,
                                                               monkeypatch):
    trace = trace_reduce.Trace({0: []}, {0: [("jit_step(1)", 0, MS)]},
                               [("main", "bench.window", 0, 30 * MS)])
    trace.scoped, trace.chunk_spans = [], []
    obs = _obs(st_spec, trace, [100])
    for name in ("swa_decode_roofline", "swa_chunk_roofline"):
        assert st_spec.module("layer_metrics", name).read(obs) is None
    from chipbench import program_counters

    monkeypatch.setattr(program_counters, "snapshot", lambda: {})
    assert st_spec.module("layer_metrics",
                          "swa_pages_per_slot_max").read(obs) is None


def test_the_page_reader_reads_the_programs_gauge(st_spec, monkeypatch):
    from chipbench import program_counters

    monkeypatch.setattr(program_counters, "snapshot", lambda: {
        "serving.swa_pages_per_slot_max": 273.0})
    obs = _obs(st_spec, _fake_trace(), [1])
    assert st_spec.module("layer_metrics",
                          "swa_pages_per_slot_max").read(obs) == 273.0


def test_benchmark_json_lists_the_cell_where_it_reports():
    with open(os.path.join(toy.ROOT, "BENCHMARK.json")) as f:
        data = json.load(f)
    for name in NEW_METRICS:
        entry = next(m for m in data["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
        assert entry["moves"] == "serve_tok_s"
    e2e = {m["name"]: m for m in data["end_to_end"]}
    assert REAL_CELL in e2e["serve_tok_s"]["workloads"]
    cell = next(w for w in data["workloads"] if w["name"] == REAL_CELL)
    assert cell["chips"] == 1 and len(cell["why"]) <= 200
    with open(os.path.join(toy.BENCH, "workloads", REAL_CELL + ".json")) as f:
        wl = json.load(f)
    assert wl["engine"] == {"num_slots": 32, "page_size": 16,
                            "max_model_len": 16384,
                            "prefill_chunk_tokens": 256, "kv_dtype": None,
                            "numeric_guard": True}
    with open(os.path.join(toy.BENCH, "mixes", "batch_closed_16k.json")) as f:
        mix = json.load(f)
    assert mix["prompt"] == {"median": 4096, "sigma": 0.8, "min": 512,
                             "max": 14336}
    assert mix["output"] == {"median": 384, "sigma": 0.6, "min": 64,
                             "max": 2048}
    assert (mix["clients"], mix["max_total"]) == (32, 16384)
