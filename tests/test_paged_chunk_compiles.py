"""The chunk attention kernel at the widths the chip runs it, and the
serving adapter's whole programs over donated pools, compiled by the TPU's
own compiler for a described v5e (no chip attached): what Mosaic refuses —
a misaligned slice, too much VMEM, a product it cannot lower — fails here
and costs no chip time, and so does a program that copies, slices or
re-lays the KV pool around its kernels.  Nothing runs, so nothing here is a
number.  Keep every such compile in THIS file: one process may load the
TPU's library at a time, and pytest-xdist hands a file to one worker."""

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pa = importlib.import_module("paddle_tpu.ops.paged_attention")
gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name,B,C,H,HKV,D,quantized", [
    ("batch_closed_chunk", 1, 256, 16, 16, 64, False),
    ("int8_chunk", 1, 256, 16, 16, 64, True),
    ("verify", 16, 4, 16, 16, 64, False),
    ("gqa_head128", 8, 8, 16, 4, 128, True),
    ("heads32_head128", 1, 256, 32, 32, 128, False),   # asks for more VMEM
])
def test_chunk_kernel_compiles_for_v5e(one_chip, name, B, C, H, HKV, D,
                                       quantized):
    ps, NP, P = 16, 64, 1025

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = sds((2, P, ps, HKV, D), jnp.int8 if quantized else jnp.bfloat16)
    scales = (sds((2, P, ps, HKV), jnp.float32),) * 2 if quantized else ()
    fn = pa._paged_chunk_q_flash_pallas if quantized \
        else pa._paged_chunk_flash_pallas
    compiled = jax.jit(
        lambda *a: fn(*a, 1.0 / math.sqrt(D), False, 1)).lower(
        sds((B, C, H, D), jnp.bfloat16), pool, pool, *scales,
        sds((B, NP), jnp.int32), sds((B,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("name,B,H,HKV,D,pool,calls", [
    # the cell: 16 heads of 64 in 128 lanes, one grid step a slot
    ("batch_closed_decode", 16, 16, 16, 128, jnp.bfloat16, "decode"),
    ("mp_shard_4_heads", 16, 4, 4, 128, jnp.bfloat16, "decode"),
    ("generate_f32_gqa", 2, 8, 4, 128, jnp.float32, "decode"),
    # what no DMA takes a page at a time is swept a page a grid step
    ("mp_shard_3_heads", 16, 3, 3, 128, jnp.bfloat16, "page"),
    ("gpt_base_12_heads", 16, 12, 12, 128, jnp.bfloat16, "page"),
    ("rows_of_64_lanes", 16, 16, 16, 64, jnp.bfloat16, "page"),
    ("gqa_head128_int8", 8, 16, 4, 128, jnp.int8, "page"),
])
def test_decode_kernel_compiles_for_v5e(one_chip, name, B, H, HKV, D, pool,
                                        calls):
    ps, NP, P = 16, 64, 1025
    quantized = pool == jnp.int8

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pools = sds((2, P, ps, HKV, D), pool)
    scales = (sds((2, P, ps, HKV), jnp.float32),) * 2 if quantized else ()
    fn = pa._paged_q_flash_pallas if quantized else pa._paged_flash_pallas
    q = sds((B, H, D), jnp.float32 if pool == jnp.float32 else jnp.bfloat16)
    assert (pa._decode_blocking(q, pools, NP) is None) == (calls == "page")
    compiled = jax.jit(
        lambda *a: fn(*a, 0.125, False, 1)).lower(
        q, pools, pools, *scales, sds((B, NP), jnp.int32),
        sds((B,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


# ------------------------------------------------ the pool, served in place
def _served_program(monkeypatch, one_chip, closure, layers, pages, kv_dtype):
    """``GPTAdapter.<closure>`` of a ``layers``-deep model at gpt2-medium's
    widths over ``pages``-page pools, compiled as the engine jits it: the
    pools donated, every argument in the device's own layout."""
    import chip_smoke
    import paddle_tpu as paddle
    from paddle_tpu.serving.adapter import GPTAdapter
    from paddle_tpu.serving.quant import QuantizedGPTAdapter
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(
        vocab_size=50257, hidden_size=1024, num_hidden_layers=layers,
        num_attention_heads=16, max_position_embeddings=1024).eval().bfloat16()
    adapter = (QuantizedGPTAdapter if kv_dtype == "int8"
               else GPTAdapter)(model, page_size=16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params, bufs = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), adapter.params_and_buffers())
    # the adapter asks the backend how wide the chip's lanes are, and which
    # attention to trace: the TPU's, and the kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools = tuple(sds(p.shape, p.dtype)
                  for p in jax.eval_shape(lambda: adapter.init_pools(pages)))
    assert pools[0].shape[-1] == 128            # d = 64 in whole lanes
    B, width = (16, 1) if closure == "step" else \
        (1, 256 if closure == "prefill_chunk" else 128)
    lead = (sds((B, width), jnp.int64),) + (
        (sds((B,), jnp.int32),) if closure == "prefill_chunk" else ())
    first = 2 + len(lead)
    jitted = jax.jit(getattr(adapter, closure),
                     donate_argnums=tuple(range(first, first + len(pools))))
    compiled = jitted.lower(params, bufs, *lead, *pools,
                            sds((B, 64), jnp.int32),
                            sds((B,), jnp.int32)).compile()
    # the int8 engine's scale pools [L, P, ps, h] f32 are 16 lanes wide and
    # still converted on the way in and out (PERF.md section 7): payloads
    moved = chip_smoke.pool_sized_instructions(
        compiled.as_text(), [p.shape for p in pools[:2]])
    return compiled, moved, math.prod(pools[0].shape) * pools[0].dtype.itemsize


@pytest.mark.parametrize("closure,kv_dtype,kernels_a_layer", [
    ("step", None, 2), ("prefill_chunk", None, 2), ("prefill", None, 1),
    ("step", "int8", 2)])
def test_served_programs_touch_the_pool_in_their_kernels_only(
        monkeypatch, one_chip, closure, kv_dtype, kernels_a_layer):
    """No instruction outside the Mosaic calls has a pool's or a layer's
    element count as its result (no copy, slice, update, concatenation,
    transpose or fusion over the stacked pool), and the program's
    temporaries are a small fraction of one pool."""
    compiled, moved, pool_bytes = _served_program(
        monkeypatch, one_chip, closure, 2, 1025, kv_dtype)
    assert moved == []
    assert compiled.as_text().count("tpu_custom_call") == 2 * kernels_a_layer
    # (the int8 engine's two scale pools are still converted: room for them)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / (
        4 if kv_dtype is None else 1)


def test_chunk_program_fits_the_chip_at_4097_pages(monkeypatch, one_chip):
    """24 layers over 4,097-page pools: with the pool copied around the
    kernels the chunk program needed 19.06G of the chip's 15.75G."""
    compiled, moved, _ = _served_program(
        monkeypatch, one_chip, "prefill_chunk", 24, 4097, None)
    mem = compiled.memory_analysis()
    assert moved == []
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < 15.75e9


def test_pool_sized_instructions_finds_what_moves_a_pool():
    import chip_smoke

    text = """
  %p.1 = bf16[2,65,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} parameter(3)
  %copy.18 = bf16[2,65,16,16,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%p.1)
  %slice.2 = bf16[1,65,16,16,64]{4,3,2,1,0} slice(%p.1), slice={[0:1]}
  %fusion.3 = (bf16[2,65,16,16,64]{4,3,2,1,0}, s32[]) fusion(%copy.18)
  %custom-call.5 = bf16[2,65,16,16,64]{4,3,2,1,0} custom-call(%p.1), custom_call_target="tpu_custom_call"
  %gte.1 = bf16[2,65,16,16,64]{4,3,2,1,0} get-tuple-element(%fusion.3), index=0
  ROOT %dot.9 = f32[16,50257]{1,0} dot(%a, %b)
"""
    found = chip_smoke.pool_sized_instructions(text, [(2, 65, 16, 16, 64)])
    assert [f.split(" = ")[0] for f in found] == [
        "%copy.18", "%slice.2", "%fusion.3"]


# ------------------------------------------- latent attention, expert layer
def test_flash_kernels_compile_at_latent_attentions_head_sizes(one_chip):
    """Forward, dk/dv and dq at q/k 192 (256 lanes) and v 128, the batch and
    length ``kanana2_30b_a3b_ep8.lm_train_4k`` trains at: 64 (batch, head)
    rows of 4,096 positions, the forward's 512 x 1,024 blocks."""
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    BH, S, D, DV = 64, 4096, 256, 128
    scale, bf = 192 ** -0.5, jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, v = sds((BH, S, D), bf), sds((BH, S, DV), bf)
    forward = jax.jit(lambda q, k, v: fa._flash_fwd(
        q, k, v, scale, True, fa.DEFAULT_BLOCK_Q, fa.DEFAULT_BLOCK_K, 0,
        with_lse=True)).lower(q, q, v).compile()
    assert forward.as_text().count("tpu_custom_call") == 1
    row = sds((BH, S, 1), jnp.float32)
    backward = jax.jit(lambda q, k, v, g, lse, r: fa._flash_bwd_pallas(
        q, k, v, g, lse, r, scale, True, 0)).lower(
        q, q, v, v, row, row).compile()
    assert backward.as_text().count("tpu_custom_call") == 2


@pytest.mark.parametrize("region,kernels", [
    (None, 3), ({}, 3),
    ({"checkpoint_policy": jax.checkpoint_policies.nothing_saveable}, 4)],
    ids=["no_region", "default_policy", "nothing_saveable"])
def test_a_recomputed_region_compiles_to_one_flash_forward(
        monkeypatch, one_chip, region, kernels):
    """The loss and its gradient through latent attention's kernels at the
    cell's sizes, as the TPU's compiler leaves them: forward, dk/dv and dq;
    under ``recompute()`` still three (the named output and log-sum-exp
    are kept and the second forward is dead code), four where a caller's
    policy keeps nothing."""
    import paddle_tpu as paddle
    from paddle_tpu import ops
    from paddle_tpu.distributed.fleet.utils.recompute import recompute

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def attend(q, k, v):
        return ops.flash_attention(q, k, v, causal=True)

    def loss(*qkv):
        qkv = [paddle.to_tensor(a) for a in qkv]
        out = attend(*qkv) if region is None \
            else recompute(attend, *qkv, **region)
        return jnp.sum(out._value.astype(jnp.float32))

    def sds(width):
        return jax.ShapeDtypeStruct((2, 4096, 32, width), jnp.bfloat16,
                                    sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        sds(192), sds(192), sds(128)).compile()
    assert compiled.as_text().count("tpu_custom_call") == kernels


def test_grouped_products_compile_at_published_widths(monkeypatch, one_chip):
    """The seam over 16 held experts of 2,048 x 768 and a buffer as long as
    all 8,192 x 6 assignments (3,072 rows a group by shape: the shapes keep
    ``jax.lax.ragged_dot``), forward and both gradients: the TPU's compiler
    gives each a grouped kernel of its own (no dense product per group)."""
    M, K, N, G = 8192 * 6, 2048, 768, 16
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w, sizes):
        return jnp.sum(gm.grouped_matmul(x, w, sizes).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds((M, K), jnp.bfloat16), sds((G, K, N), jnp.bfloat16),
        sds((G,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot') + text.count("%ragged-dot") >= 2
    assert "grouped_matmul/pallas_call" not in text
    assert compiled.cost_analysis()["flops"] < 4 * 2 * M * K * N


@pytest.mark.parametrize("rows", [1024, 832])
@pytest.mark.parametrize("K,N", [(2048, 1536), (1536, 2048)])
def test_grouped_kernel_compiles_at_the_hybrid_cells_widths(
        monkeypatch, one_chip, rows, K, N):
    """The seam at a chunk's 1,024 and a 13-page prompt's 832 assignments
    over 64 experts of 2,048 x 1,536, gate / up and down: ONE Mosaic call,
    the repo's, all the rows a tile and all of a group's columns a block;
    under differentiation the same forward and ``ragged_dot``'s kernels
    behind it."""
    G = 64
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((rows, K), jnp.bfloat16), sds((G, K, N), jnp.bfloat16),
            sds((G,), jnp.int32))
    assert gm._blocking(*args[:2]) == (rows, N)
    text = jax.jit(gm.grouped_matmul).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert "grouped_matmul/pallas_call" in text and "ragged-dot" not in text

    def loss(x, w, sizes):
        return jnp.sum(gm.grouped_matmul(x, w, sizes).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        *args).compile().as_text()
    assert "jvp(grouped_matmul)/pallas_call" in text
    assert text.count('op_name="ragged-dot') + text.count("%ragged-dot") >= 2


@pytest.mark.parametrize("closure", ["step", "prefill_chunk", "prefill"])
def test_hybrid_programs_fit_the_chip_at_the_cells_sizes(monkeypatch,
                                                         one_chip, closure):
    """``StatedCacheAdapter``'s decode, chunk and monolithic prefill programs
    for the ten-layer cut of LFM2-24B-A2B as
    ``lfm2_24b_a2b_l10.batch_closed_4k`` serves it (32 slots, 8,193 pages,
    tables 256 wide, bfloat16): 10.5 GB of weights that are never built
    here (every leaf a shape), the paged pools touched by the kernels alone,
    a few tens of MB of temporaries.  The cell's prompts are all longer
    than a chunk, so the monolithic prefill (a prompt of 256 tokens or
    fewer) runs at this size nowhere else: at 513 pages the compiler
    staged both layers of a 34 MB pool in VMEM around the writer
    (``slice-start`` into ``S(1)``: chip_smoke, PR 30); at 1.07 GB a pool
    it cannot, and no instruction is the size of a pool or of a layer of
    one."""
    import json
    import os

    import chip_smoke
    from paddle_tpu.serving import StatedCacheAdapter
    from paddle_tpu.text.models.lfm2 import Lfm2MoeForCausalLM

    spec = importlib.import_module("chipbench.spec")
    ref = spec.load_module("reference", "lfm2")
    family = spec.load_module("models", "lfm2")
    with open(os.path.join(spec.HERE, "configs",
                           "lfm2_24b_a2b_l10.json")) as f:
        cfg = json.load(f)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    # a scalar stands in for every leaf; its shape comes from the reference
    model = Lfm2MoeForCausalLM(
        family.program_config(cfg, "bfloat16"),
        param_init=lambda name, shape: jnp.zeros(
            (), jnp.float32 if name.endswith("bias") else jnp.bfloat16)).eval()
    shapes = ref.param_shapes(cfg)
    for name, leaf in list(model.named_parameters()) \
            + list(model.named_buffers()):
        if name in shapes:
            leaf._value = jax.ShapeDtypeStruct(shapes[name],
                                               leaf._value.dtype)
    slots = 32
    adapter = StatedCacheAdapter(model, 16, slots)
    params, bufs = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), adapter.params_and_buffers())
    assert sum(math.prod(v.shape) for v in params.values()) == 5_267_089_664
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools = tuple(sds(p.shape, p.dtype) for p in jax.eval_shape(
        lambda: adapter.init_pools(slots * 256 + 1)))
    assert [p.shape for p in pools] == [(2, 8193, 16, 8, 128)] * 2 \
        + [(8, slots + 1, 2, 2048)]
    B, width = (slots, 1) if closure == "step" else (1, 256)
    lead = (sds((B, width), jnp.int64),) + (
        (sds((B,), jnp.int32),) if closure == "prefill_chunk" else ())
    slot = () if closure == "step" else (sds((B,), jnp.int32),)
    first = 2 + len(lead)
    compiled = jax.jit(
        getattr(adapter, closure),
        donate_argnums=tuple(range(first, first + 3))).lower(
        params, bufs, *lead, *pools, sds((B, 256), jnp.int32),
        sds((B,), jnp.int32), *slot).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert chip_smoke.pool_sized_instructions(
        text, [p.shape for p in pools[:2]]) == []
    # two attention layers: a writer and an attend kernel each; the rest
    # are the 8 x 3 grouped products: the decode step's 128 rows stay XLA's
    # own Mosaic calls (with their 8 offsets kernels the
    # ``costs/lfm2.grouped_kernels`` that chipbench/decode_scope.py counts
    # by that name), a chunk's 1,024 and a prompt's 832-1,024 rows go
    # through the repo's kernel, which keeps its name stack
    assert text.count("gqa_attention") >= 4
    ragged = text.count('op_name="ragged-dot-none"')
    tiled = len(re.findall(
        r'custom-call\(.*op_name="[^"]*/moe_experts/grouped_matmul/', text))
    costs = spec.load_module("costs", "lfm2")
    if closure == "step":
        assert (ragged, tiled) == (24, 0)
        assert ragged + text.count('op_name="ragged-dot-metadata"') \
            == costs.grouped_kernels(cfg)
    else:
        assert (ragged, tiled) == (0, 24)
        assert "ragged-dot" not in text
    assert mem.temp_size_in_bytes < 100e6
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < 12.5e9


@pytest.mark.parametrize("closure", ["step", "prefill_chunk", "prefill"])
def test_looped_programs_hold_48_layer_bodies_and_fit_the_chip(
        monkeypatch, one_chip, closure):
    """``StatedCacheAdapter``'s decode, chunk and monolithic prefill programs
    for Ouro-2.6B whole as ``ouro_2_6b.batch_closed_1k`` serves it (16
    slots, 321 pages over 192 cache rows, tables 64 wide, bfloat16): 5.34 GB
    of weights that are never built here (every leaf a shape) beside 8.08 GB
    of pools.  THE WEIGHTS ARE SHARED AND THE LOOP IS TRACED: one ``while``
    whose body holds the 48 layer bodies, so a program holds 48 writer
    kernels and 48 attention kernels, not 192 of each, and the cache row
    reaches them as a traced scalar.  The pools ride in the loop's carry
    and are touched by the kernels alone: the one pool-sized instruction is
    the loop itself.  What the compiler adds is its own re-layout of the
    q, k and v weights (concatenated for one product, hoisted out of the
    loop): 1.3 GB of temporaries a program."""
    import json
    import os

    import chip_smoke
    from paddle_tpu.serving import StatedCacheAdapter
    from paddle_tpu.text.models.ouro import OuroForCausalLM

    spec = importlib.import_module("chipbench.spec")
    ref = spec.load_module("reference", "ouro")
    family = spec.load_module("models", "ouro")
    with open(os.path.join(spec.HERE, "configs", "ouro_2_6b.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(spec.HERE, "workloads",
                           "ouro_2_6b.batch_closed_1k.json")) as f:
        engine = json.load(f)["engine"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    # a scalar stands in for every leaf; its shape comes from the reference
    model = OuroForCausalLM(
        family.program_config(cfg, "bfloat16"),
        param_init=lambda name, shape: jnp.zeros((), jnp.bfloat16)).eval()
    shapes = ref.param_shapes(cfg)
    for name, leaf in model.named_parameters():
        leaf._value = jax.ShapeDtypeStruct(shapes[name], leaf._value.dtype)
    slots, width = engine["num_slots"], engine["max_model_len"] // 16
    adapter = StatedCacheAdapter(model, engine["page_size"], slots)
    params, bufs = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), adapter.params_and_buffers())
    assert len(params) == 48 * 11 + 5
    assert sum(math.prod(v.shape) for v in params.values()) == 2_667_974_657
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools = tuple(sds(p.shape, p.dtype) for p in jax.eval_shape(
        lambda: adapter.init_pools(engine["num_pages"] + 1)))
    assert [p.shape for p in pools] == [(192, 321, 16, 16, 128)] * 2
    assert math.prod(pools[0].shape) < 2 ** 31      # 94% of it
    B, C = (slots, 1) if closure == "step" else (1, 256)
    lead = (sds((B, C), jnp.int64),) + (
        (sds((B,), jnp.int32),) if closure == "prefill_chunk" else ())
    first = 2 + len(lead)
    compiled = jax.jit(
        getattr(adapter, closure),
        donate_argnums=(first, first + 1)).lower(
        params, bufs, *lead, *pools, sds((B, width), jnp.int32),
        sds((B,), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    sized = chip_smoke.pool_sized_instructions(text,
                                               [p.shape for p in pools])
    assert len(sized) == 1 and sized[0].startswith("%while"), sized
    assert len(re.findall(r"= \([^\n]*\) while\(", text)) == 1
    # 48 writers and 48 attention kernels (the decode kernel, the chunk
    # kernel, or flash attention over a whole prompt): a layer body each
    assert text.count("tpu_custom_call") == 2 * 48
    assert len(re.findall(r'custom-call\([^\n]*op_name="[^"]*loop_step/'
                          r'gqa_attention/[^"]*paged_write', text)) == 48
    assert mem.temp_size_in_bytes < 1.5e9
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < 15.0e9


@pytest.mark.parametrize("closure", ["step", "prefill_chunk"])
def test_window_programs_fit_the_chip_at_the_cells_sizes(monkeypatch,
                                                         one_chip, closure):
    """``StatedCacheAdapter``'s decode and chunk programs for the eight-layer
    cut of SmallThinker-21BA3B as
    ``smallthinker_21b_a3b_l8.batch_closed_16k`` serves it (32 slots,
    tables 1,024 pages wide, bfloat16): 7.93 GB of weights never built here,
    the full layers' pool of 32,769 pages and the window layers' of 8,737
    (273 a slot), touched by the kernels alone: a writer and an attention
    kernel a layer, and the 8 x 3 grouped products through the repo's own
    kernel, none left to ``ragged_dot`` (``costs/smallthinker
    .grouped_kernels``)."""
    import json
    import os

    import chip_smoke
    from paddle_tpu.serving import StatedCacheAdapter
    from paddle_tpu.text.models.smallthinker import SmallThinkerForCausalLM

    spec = importlib.import_module("chipbench.spec")
    ref = spec.load_module("reference", "smallthinker")
    family = spec.load_module("models", "smallthinker")
    with open(os.path.join(spec.HERE, "configs",
                           "smallthinker_21b_a3b_l8.json")) as f:
        cfg = json.load(f)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=one_chip)

    # a scalar stands in for every leaf; its shape comes from the reference
    model = SmallThinkerForCausalLM(
        family.program_config(cfg, "bfloat16"),
        param_init=lambda name, shape: jnp.zeros((), jnp.bfloat16)).eval()
    shapes = ref.param_shapes(cfg)
    for name, leaf in model.named_parameters():
        leaf._value = jax.ShapeDtypeStruct(shapes[name], leaf._value.dtype)
    slots = 32
    adapter = StatedCacheAdapter(model, 16, slots)
    assert adapter.plan_window(slots, 1024, 256) == (slots * 273, 273)
    params, bufs = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), adapter.params_and_buffers())
    assert sum(math.prod(v.shape) for v in params.values()) == 3_966_937_600
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pools = tuple(sds(p.shape, p.dtype) for p in jax.eval_shape(
        lambda: adapter.init_pools(slots * 1024 + 1)))
    assert [p.shape for p in pools] == [(2, 32769, 16, 4, 128)] * 2 \
        + [(6, 8737, 16, 4, 128)] * 2
    B, width = (slots, 1) if closure == "step" else (1, 256)
    lead = (sds((B, width), jnp.int64),) + (
        (sds((B,), jnp.int32),) if closure == "prefill_chunk" else ())
    first = 2 + len(lead)
    compiled = jax.jit(
        getattr(adapter, closure),
        donate_argnums=tuple(range(first, first + 4))).lower(
        params, bufs, *lead, *pools, sds((B, 2048), jnp.int32),
        sds((B,), jnp.int32)).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    assert chip_smoke.pool_sized_instructions(
        text, [p.shape for p in pools]) == []
    assert text.count("tpu_custom_call") == 2 * 8 + 24
    assert "ragged-dot" not in text
    assert len(re.findall(r'custom-call\([^\n]*op_name="[^"]*swa_attention/'
                          r'[^"]*paged_write', text)) == 6
    assert mem.temp_size_in_bytes < 100e6
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes) < 12.5e9
