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
