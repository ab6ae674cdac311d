"""chip_smoke.py rehearsed on the CPU, so that chip time is not spent on a
typo: the script refuses to run without a TPU, and its phase functions run
here at toy size with the Pallas kernels in interpret mode."""

import os
import sys

import jax
import pytest
from jax.experimental import pallas as pl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

_GPT = dict(vocab_size=128, hidden_size=64, num_hidden_layers=1,
            num_attention_heads=4)
TOY = {
    "kernels": {"heads": [(4, 2, 64)], "page_size": 8, "table_pages": 4,
                "rows": 4, "chunk": 3,
                "flash": [(1, 256, 2, 64), (1, 256, 2, 24, 16)],
                "experts": (64, 32, 16, 8, 4, 3)},
    "resnet": {"arch": "resnet18", "classes": 10, "batch": 8, "image": 32,
               "steps": 3},
    "gpt": {"model": dict(_GPT, max_position_embeddings=256), "batch": 2,
            "seq": 256, "steps": 3},
    "serve": {"model": dict(_GPT, max_position_embeddings=64),
              "num_slots": 2, "page_size": 8, "chunk": 16,
              "requests": [(5, 6), (20, 4), (40, 5)], "stream": (7, 4)},
    "multichip": {"chips": 4, "ring": (1, 1024, 2, 64), "allreduce_mb": 1},
    "lfm2": {"model": dict(hidden_size=64, num_attention_heads=4,
                           num_key_value_heads=2, intermediate_size=96,
                           moe_intermediate_size=16, num_experts=4,
                           num_experts_per_tok=2, num_hidden_layers=4,
                           vocab_size=128),
             "num_slots": 2, "page_size": 8, "chunk": 16,
             "max_model_len": 64,
             "requests": [(5, 6), (20, 4), (40, 5)], "stream": (7, 4),
             "kernels": {"heads": (4, 2, 64), "rows": 4, "chunk": 3,
                         "grouped": (256, 64, 128, 8)}},
}


def test_refuses_to_run_without_a_tpu(capsys):
    """``python chip_smoke.py`` on a host held to the CPU: a non-zero exit
    (SystemExit with a message) that names the platform, and no result."""
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert isinstance(exc.value.code, str)      # exit status 1 + the message
    assert "'cpu'" in exc.value.code and "TPU" in exc.value.code
    assert capsys.readouterr().out == ""


def test_result_line_is_the_drivers_contract():
    """The last line of stdout carries exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count``); everything else is the report line."""
    import json

    line = chip_smoke.result_line({"platform": "tpu", "kind": "TPU v5 lite",
                                   "count": 1, "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.fixture
def as_tpu(monkeypatch):
    """Route the public entries to their Pallas kernels (they ask
    ``jax.default_backend()``) and run every kernel interpreted."""
    real = pl.pallas_call
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, **kw: real(*a, **{**kw, "interpret": True}))
    # an interpreted kernel lowers to no custom call; the count is checked
    # against a real TPU lowering in test_served_programs_lower_to_mosaic
    monkeypatch.setattr(chip_smoke, "expect_mosaic",
                        lambda what, jitted, args, want: want)
    # an interpreted kernel is a loop over the whole pool; what the TPU's
    # compiler makes of the served programs is checked, for a described
    # v5e, in tests/test_paged_chunk_compiles.py
    monkeypatch.setattr(chip_smoke, "compiled_pool_facts",
                        lambda jitted, args, pools: {"pool_sized": [],
                                                     "temp_bytes": 0})


@pytest.mark.parametrize("phase", ["kernels", "train_resnet", "train_gpt",
                                   "serve_bf16", "serve_int8", "serve_lfm2"])
def test_phase_rehearsal(as_tpu, phase):
    facts = chip_smoke.run_phase(phase, TOY)
    assert isinstance(facts, dict) and facts


@pytest.mark.slow
def test_multichip_rehearsal(as_tpu):
    from paddle_tpu.distributed import topology

    try:
        facts = chip_smoke.run_phase("multichip", TOY)
    finally:
        topology._HCG[0] = None     # fleet.init's global mesh
    assert set(facts) == {"serve_mp", "ring_attention", "allreduce",
                          "resnet_dp"}


def test_multichip_skips_below_four_devices(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert chip_smoke.run_phase("multichip", TOY) == "skipped: 1 device"


def test_served_programs_lower_to_mosaic(monkeypatch):
    """With the backend reporting "tpu", the programs of a served engine
    lower (for the TPU platform, from here) to their Mosaic custom calls —
    one pool writer a program, and in every layer the decode or the chunk
    kernel — and to none on the reference path."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM

    def engine(replica):
        paddle.seed(0)
        m = GPTForCausalLM(**dict(_GPT, num_hidden_layers=2,
                                  max_position_embeddings=64)).eval()
        return ServingEngine(m, num_slots=2, page_size=8,
                             prefill_chunk_tokens=16, numeric_guard=True,
                             replica=replica)

    with pytest.raises(AssertionError, match="carries 0 Mosaic"):
        chip_smoke._expect_engine_mosaic(engine("lower-ref"), 16, 2)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chip_smoke._expect_engine_mosaic(engine("lower-tpu"), 16, 2)


def test_hybrid_chunk_program_lowers_to_the_grouped_kernel(monkeypatch):
    """A served hybrid's programs, lowered for the TPU from here: a chunk of
    128 tokens x top-2 = 256 assignments over 4 experts takes the repo's
    grouped kernel (3 products an expert layer, each a Mosaic call in the
    lowering), the decode step's and a 8-token prompt's few rows stay
    ``ragged_dot`` (kernels only in the TPU's compiler, after this
    lowering); ``moe.grouped_products_traced`` says so by label."""
    from paddle_tpu.profiler import metrics
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import Lfm2MoeForCausalLM

    def traced():
        counter = metrics.counter("moe.grouped_products_traced")
        return [counter.get(kernel=k) or 0 for k in ("tiled", "ragged_dot")]

    model = Lfm2MoeForCausalLM(**dict(TOY["lfm2"]["model"],
                                      max_position_embeddings=256)).eval()
    expert_layers = sum(layer.sparse for layer in model.model.layers)
    assert expert_layers == 2
    engine = ServingEngine(model, num_slots=2, page_size=8,
                           prefill_chunk_tokens=128, max_model_len=256,
                           numeric_guard=True, replica="lower-lfm2")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    before = traced()
    chip_smoke._expect_engine_mosaic(
        engine, 128, model.model.num_attention_layers,
        chunk_grouped=3 * expert_layers)
    assert [b - a for a, b in zip(before, traced())] \
        == [3 * expert_layers, 2 * 3 * expert_layers]
