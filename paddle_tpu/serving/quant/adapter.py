"""QuantizedGPTAdapter — int8 paged KV pools for the serving engine.

Same closure contract as :class:`~paddle_tpu.serving.adapter.GPTAdapter`
(the engine donates/rebinds the pool tuple opaquely), but the KV state is
four arrays instead of two:

- ``kp, vp``: int8 page pools ``[L, P, ps, h, d]`` — half the bf16 bytes,
  a quarter of f32;
- ``k_scales, v_scales``: float32 scale pools ``[L, P, ps, h]`` — one
  absmax scale per (page slot, kv head), addressed by the SAME page table.

Quantization happens inside the compiled programs: handed a pool tuple
with scale pools, the cache seam the decoder layers call
(``ops.paged_attention.paged_cache_attend``) rounds K/V onto the int8
grid on the way into every pool write and the paged attention consumers
dequantize in-kernel, so no full-precision copy of the cache ever
materializes in HBM.  Rollback,
prefix pages, scratch-page masking and the chunk-write drop semantics are
all untouched — the scale pool rides the exact same table addressing.

Chunked prefill (``ServingEngine(prefill_chunk_tokens=N)``) rides the
inherited :meth:`GPTAdapter.prefill_chunk` unchanged: each chunk
quantizes on the way into the pools and the engine's ``prefill_chunk/<c>@int8`` program family stays
byte-identical to the monolithic int8 prefill.  On TPU the decode side of
the same batch runs the int8 decode kernel (``decode@int8``).

The hierarchical KV cache (``prefix_cache="radix"`` + ``kv_spill=True``)
needs no int8-specific code: the engine's spill snapshot/restore hooks
walk the WHOLE pool tuple, so an evicted page's int8 payload rows and
their float32 absmax scale rows spill to host DRAM — and resurrect into a
device slot — together as one unit.  A re-paged page is byte-identical to
the one evicted (payload and scales both round-trip losslessly), so
partial-prefix reuse stays exact under quantized pools too.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..adapter import GPTAdapter


class QuantizedGPTAdapter(GPTAdapter):
    """``ServingEngine(kv_dtype="int8")`` builds one of these (see module
    docstring).  Drives the same cache variants as its base with a 4-array
    pool tuple."""

    n_pools = 4
    kv_dtype = "int8"

    def init_pools(self, num_pages):
        """Zeroed ``(kp, vp, k_scales, v_scales)``: int8 payload pools
        [L, P, ps, h, d] + f32 scale pools [L, P, ps, h]."""
        from ...ops.paged_attention import pool_lane_dim

        P = int(num_pages)
        shape = (self.num_layers, P, self.page_size, self.num_kv_heads,
                 pool_lane_dim(self.head_dim))
        kp = jnp.zeros(shape, jnp.int8)
        ks = jnp.zeros(shape[:-1], jnp.float32)
        return kp, jnp.zeros_like(kp), ks, jnp.zeros_like(ks)

    def page_bytes(self):
        """One page across all layers, K and V: int8 payload (d bytes per
        position per head) + f32 scale (4 bytes per position per head) —
        (d + 4) / (2 d) of the bf16 cost, so ~1.9x pages per HBM byte at
        d=64 and ~1.94x at d=128."""
        from ...ops.paged_attention import pool_lane_dim

        # int8 payload (whole lanes of it) + f32 scale
        per_pos_head = pool_lane_dim(self.head_dim) * 1 + 4
        return (2 * self.num_layers * self.page_size * self.num_kv_heads
                * per_pos_head)

    def pool_owners(self):
        """int8 payload pools and f32 scale pools get separate ledger
        owners — the scale pools are real device residency that the
        payload-only view used to hide (ISSUE 12 satellite fix)."""
        return (("kv.pages", (0, 1)), ("kv.scales", (2, 3)))

    def pool_pspecs(self, axis="model"):
        """Payload pools [L, P, ps, h, d] AND scale pools [L, P, ps, h]
        both shard the KV-head dim — a shard dequantizes its heads with
        its own scale columns, no cross-shard traffic."""
        from jax.sharding import PartitionSpec as P

        payload = P(None, None, None, axis, None)
        scales = P(None, None, None, axis)
        return (payload, payload, scales, scales)
