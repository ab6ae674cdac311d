"""Shared int8 quantization numerics (pure jnp, no framework imports).

ONE implementation of absmax scale selection / int-grid rounding /
dequantization, used by three layers that previously could have drifted:

- the serving engine's quantized paged KV pools
  (``ops.paged_attention.quantize_kv`` on the way into the pool writes),
- :class:`paddle_tpu.quantization.Int8Linear`'s weight/activation grids,
- the calibration harness (``serving.quant.calibrate``).

``paddle_tpu.quantization`` re-exports :func:`quantize_absmax` /
:func:`dequantize` as its public deploy-grid API; this module stays
import-light (jax only) so the low-level ops can use it without pulling
the Layer machinery in.

Convention: symmetric signed grids — ``qmax = 2**(bits-1) - 1`` (127 for
int8, so -128 is never produced and the grid is symmetric), scales are
float32, and quantized payloads are int8 regardless of ``bits <= 8``
(sub-8-bit grids still store one value per byte).
"""

from __future__ import annotations

import jax.numpy as jnp


def qmax_for(bits=8):
    """Largest magnitude on the symmetric signed grid for ``bits``."""
    return float(2.0 ** (int(bits) - 1) - 1)


def absmax_scale(x, axis=None, bits=8, eps=1e-8):
    """Absmax scale for ``x``: ``max|x| / qmax`` reduced over ``axis``
    (``keepdims=True`` so the result broadcasts straight back against
    ``x``; ``axis=None`` reduces everything to a scalar array).  ``eps``
    floors the absmax so all-zero inputs quantize to zeros instead of
    dividing by zero."""
    a = jnp.abs(x.astype(jnp.float32))
    m = jnp.max(a) if axis is None else jnp.max(a, axis=axis, keepdims=True)
    return jnp.maximum(m, jnp.float32(eps)) / jnp.float32(qmax_for(bits))


def quantize(x, scale, bits=8):
    """Round ``x`` onto the symmetric grid defined by ``scale`` (any shape
    broadcastable against ``x``); returns int8."""
    qmax = qmax_for(bits)
    q = jnp.round(x.astype(jnp.float32) / scale)
    return jnp.clip(q, -qmax, qmax).astype(jnp.int8)


def quantize_absmax(x, axis=None, bits=8, eps=1e-8):
    """Absmax quantization in one step: ``(q int8, scale f32)`` with the
    scale shaped per :func:`absmax_scale` (keepdims — ``q * scale``
    broadcasts with no reshaping)."""
    scale = absmax_scale(x, axis=axis, bits=bits, eps=eps)
    return quantize(x, scale, bits=bits), scale


def dequantize(q, scale, dtype=jnp.float32):
    """``q * scale`` in float32, cast to ``dtype``."""
    return (q.astype(jnp.float32) * scale).astype(dtype)
