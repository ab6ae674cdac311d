"""The chunk attention kernel at the widths the chip runs it, compiled by
the TPU's own compiler for a described v5e (no chip attached): what Mosaic
refuses — a misaligned slice, too much VMEM, a product it cannot lower —
fails here and costs no chip time.  Nothing runs, so nothing here is a
number.  Keep every such compile in THIS file: one process may load the
TPU's library at a time, and pytest-xdist hands a file to one worker."""

import importlib
import math

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

pa = importlib.import_module("paddle_tpu.ops.paged_attention")


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

    pool = sds((P, ps, HKV, D), jnp.int8 if quantized else jnp.bfloat16)
    scales = (sds((P, ps, HKV), jnp.float32),) * 2 if quantized else ()
    fn = pa._paged_chunk_q_flash_pallas if quantized \
        else pa._paged_chunk_flash_pallas
    compiled = jax.jit(
        lambda *a: fn(*a, 1.0 / math.sqrt(D), False)).lower(
        sds((B, C, H, D), jnp.bfloat16), pool, pool, *scales,
        sds((B, NP), jnp.int32), sds((B,), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") == 1


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


def test_grouped_products_compile_at_published_widths(one_chip):
    """``jax.lax.ragged_dot`` over 16 held experts of 2,048 x 768 and a
    buffer as long as all 8,192 x 6 assignments, forward and both
    gradients: the TPU's compiler gives each a grouped kernel of its own
    (no dense product per group)."""
    M, K, N, G = 8192 * 6, 2048, 768, 16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(x, w, sizes):
        return jnp.sum(jax.lax.ragged_dot(x, w, sizes).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        sds((M, K), jnp.bfloat16), sds((G, K, N), jnp.bfloat16),
        sds((G,), jnp.int32)).compile()
    text = compiled.as_text()
    assert text.count('op_name="ragged-dot') + text.count("%ragged-dot") >= 2
    assert compiled.cost_analysis()["flops"] < 4 * 2 * M * K * N
