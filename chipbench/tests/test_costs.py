"""The cost functions against hand counts, the one table of peaks, and the
rule that a share over 100% fails the run rather than printing."""

import importlib
import json
import os

import pytest

from chipbench import peaks

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
gpt2 = importlib.import_module("chipbench.costs.gpt2")
flash = importlib.import_module("chipbench.costs.flash")
paged = importlib.import_module("chipbench.costs.paged_decode")

with open(os.path.join(HERE, "configs", "gpt2_medium.json")) as f:
    MEDIUM = json.load(f)
TINY = {"n_layer": 2, "n_embd": 8, "n_inner": 32, "vocab_size": 10}


def test_gpt2_counts_by_hand():
    # per layer: qkv 8x24, proj 8x8, two feed-forward 8x32 -> 768 weights
    assert gpt2.block_matmul_params(TINY) == 2 * (192 + 64 + 512) == 1536
    assert gpt2.head_params(TINY) == 80
    # one query over 5 keys: QK^T and PV are 2*5*8 each, two layers
    assert gpt2.attention_flops(TINY, 5) == 2 * 2 * (2 * 5 * 8) == 320
    # a sequence of 3: contexts 1+2+3 = 6 keys in all
    assert gpt2.causal_attention_flops(TINY, 3) == 2 * 4 * 8 * 6 == 384
    fwd = 2 * (1536 + 80) * 3 + 384
    assert gpt2.train_flops_per_sample(TINY, 3) == 3 * fwd
    assert gpt2.prefill_flops(TINY, 3) == 2 * 1536 * 3 + 384 + 2 * 80
    assert gpt2.decode_flops(TINY, 5) == 2 * (1536 + 80) + 320


def test_gpt2_medium_is_the_published_size():
    ref = importlib.import_module("chipbench.reference.gpt2")
    assert ref.n_params(MEDIUM) == 354_823_168 == MEDIUM["assumed"]["parameters"]
    # 6N per token and causal attention: about 9.3 TFLOP for 4 x 1,024 tokens
    step = 4 * gpt2.train_flops_per_sample(MEDIUM, 1024)
    assert 9.0e12 < step < 9.6e12
    blocks = 24 * 12 * 1024 * 1024
    assert gpt2.block_matmul_params(MEDIUM) == blocks
    assert gpt2.decode_flops(MEDIUM, 0) == 2 * (blocks + 50257 * 1024)


def test_flash_counts_by_hand():
    # one head of 4 wide over 3 tokens, causal: 6 pairs, QK^T and PV 2*4 each
    flops, moved = flash.forward(1, 3, 1, 4)
    assert flops == 6 * 16 and moved == 3 * (4 * 4 * 2 + 4)
    assert flash.forward(1, 3, 1, 4, causal=False)[0] == 9 * 16
    bflops, bmoved = flash.backward(1, 3, 1, 4)
    assert bflops == 2 * flops and bmoved == 3 * (8 * 4 * 2 + 4)
    assert flash.forward(2, 3, 5, 4)[0] == 10 * flops


def test_paged_decode_counts_by_hand():
    # two slots holding 3 and 5 tokens, 2 heads of 4: 8 cached tokens
    flops, moved = paged.step([3, 5], heads=2, kv_heads=2, head_dim=4)
    assert flops == 4 * 8 * 2 * 4 and moved == 2 * 8 * 2 * 4 * 2
    assert paged.step([3, 5], 2, 2, 4, kv_itemsize=1)[1] == moved // 2
    assert paged.step([], 2, 2, 4) == (0, 0)


def test_peaks_are_one_table_without_defaults(monkeypatch):
    monkeypatch.setenv("PADDLE_PEAK_FLOPS", "1")          # overrides nothing
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops"] == 393e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
    with open(os.path.join(HERE, "peaks.json")) as f:
        assert "Google Cloud" in json.load(f)["source"]


def test_roofline_says_which_bound():
    peak = peaks.peaks("TPU v5 lite")
    assert peaks.roofline_seconds(197e12, 1.0, peak) == (1.0, "compute")
    t, bound = peaks.roofline_seconds(1.0, 819e9 * 2, peak)
    assert bound == "bandwidth" and t == pytest.approx(2.0)


def test_a_share_over_100_fails_the_run():
    assert peaks.share_percent(1.0, 4.0, "x") == 25.0
    assert peaks.share_percent(1.0, 0.0, "x") is None
    assert peaks.share_percent(1.0, None, "x") is None
    with pytest.raises(ValueError, match="over 100%"):
        peaks.share_percent(1.01, 1.0, "x_roofline")
