"""A toy copy of the benchmark for the CPU tests: the real harness files,
copied into a scratch root, with toy configurations, mixes and cells dropped
in as NEW files and named in a ``BENCHMARK.json`` of its own.  Nothing that
exists is edited, which is what a later PR must be able to do."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

TOY_GPT = {
    "family": "gpt2", "source": "toy sizes for the CPU tests",
    "n_layer": 2, "n_embd": 64, "n_head": 4, "n_inner": 256,
    "n_positions": 128, "vocab_size": 2039, "layer_norm_epsilon": 1e-5,
    "initializer_range": 0.02, "reduced": []}

TOY_SERVE_MIX = {
    "kind": "closed_loop", "clients": 3, "stagger_s": 0.2,
    "ramp_seconds": 0.5, "lengths_seed": 5, "pool_size": 64,
    "prompt": {"median": 24, "sigma": 0.5, "min": 9, "max": 60},
    "output": {"median": 8, "sigma": 0.4, "min": 3, "max": 16},
    "max_total": 96, "poll_s": 0.001}

#: the same sizes sent on a schedule: the open loop through the same entry
TOY_OPEN_MIX = dict(
    {k: v for k, v in TOY_SERVE_MIX.items()
     if k not in ("clients", "stagger_s")},
    kind="open_loop", rate_per_s=8.0, burst=2, schedule_seed=3, horizon_s=60.0)

TOY_SERVE_CELL = {
    "config": "toy_gpt", "chips": 1, "entry": "serve", "who": "tests",
    "why": "toy", "dtype": "float32",
    "weights": {"position_scale": 10, "outlier_channels": 2,
                "outlier_gain": 16},
    "engine": {"num_slots": 4, "page_size": 8, "max_model_len": 96,
               "prefill_chunk_tokens": 32, "kv_dtype": None,
               "numeric_guard": True},
    "trace_seconds": 0.5,
    "compared_requests": 60,
    "limits": {"logit_gap_max": 1e-5, "logit_gap_mean": 1e-6}}

TOY_TRAIN_MIX = {
    "kind": "batches", "batch_size": 4, "seq_len": 32,
    "dataset_samples": 64, "num_workers": 0, "worker_mode": "thread",
    "steps_in_flight": 2}

TOY_TRAIN_CELL = {
    "config": "toy_gpt", "chips": 1, "entry": "train", "who": "tests",
    "why": "toy", "amp_level": "O0", "amp_dtype": "bfloat16",
    "optimizer": {"name": "adamw", "learning_rate": 1e-3, "beta1": 0.9,
                  "beta2": 0.95, "epsilon": 1e-8, "weight_decay": 0.1},
    "compared_steps": 3, "extra_warm_steps": 0, "trace_seconds": 0.5,
    "limits": {"loss_step1": 1e-5, "loss_step2": 1e-5, "loss_step3": 1e-5,
               "grad_norm_gap": 1e-4, "update_norm_gap": 1e-4,
               "grad_rel_err": 1e-4}}

def _dump(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_root(tmp):
    """Copy the harness into ``tmp`` and add the toy files.  Returns the
    root to hand to ``spec.Spec(root=...)``."""
    root = str(tmp)
    bench = os.path.join(root, "chipbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "data"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        real = json.load(f)
    _dump(os.path.join(bench, "configs", "toy_gpt.json"), TOY_GPT)
    _dump(os.path.join(bench, "mixes", "toy_closed.json"), TOY_SERVE_MIX)
    _dump(os.path.join(bench, "mixes", "toy_batches.json"), TOY_TRAIN_MIX)
    _dump(os.path.join(bench, "workloads", "toy_gpt.toy_closed.json"),
          TOY_SERVE_CELL)
    _dump(os.path.join(bench, "workloads", "toy_gpt.toy_batches.json"),
          TOY_TRAIN_CELL)
    _dump(os.path.join(bench, "mixes", "toy_open.json"), TOY_OPEN_MIX)
    _dump(os.path.join(bench, "workloads", "toy_gpt.toy_open.json"),
          TOY_SERVE_CELL)
    serve, train, opened = ("toy_gpt.toy_closed", "toy_gpt.toy_batches",
                            "toy_gpt.toy_open")
    real["configs"].append({"name": "toy_gpt", "source": "tests",
                            "file": "chipbench/configs/toy_gpt.json",
                            "reduced": [], "why": "toy"})
    real["workloads"] += [
        {"name": serve, "config": "toy_gpt", "traffic": "toy_closed",
         "chips": 1, "why": "toy"},
        {"name": train, "config": "toy_gpt", "traffic": "toy_batches",
         "chips": 1, "why": "toy"},
        {"name": opened, "config": "toy_gpt", "traffic": "toy_open",
         "chips": 1, "why": "toy"}]
    for m in real["end_to_end"] + real["per_layer"]:
        if "workloads" in m:
            kind = list(m["workloads"])
            if any("batch_closed" in w for w in kind):
                m["workloads"] += [serve, opened]
            if any("train" in w for w in kind):
                m["workloads"] += [train]
    _dump(os.path.join(root, "BENCHMARK.json"), real)
    return root
