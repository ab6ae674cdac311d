"""Matrix products in the precision below the one a configuration states:
what a control puts in the place of the reference's ``dense``.

For a configuration that states bfloat16 the step below is int8, the chip's
own lower precision: both operands of a product are rounded to 8-bit
integers, symmetric, with one scale per row or column along the contracted
axis (the finest scaling that int8 products allow), and the product is
taken exactly (the float32 product of integers up to 127 at ``highest`` is
the integer product).  A training control takes its backward products in
int8 too, as int8 training does: ``dX = q(dY) q(W)^T`` and ``dW = q(X)^T
q(dY)``, each operand rounded along the axis it is contracted over.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _fake_quant(x, axis, bits):
    """``x`` rounded to ``bits``-bit integers times one scale along ``axis``."""
    top = F32(2 ** (bits - 1) - 1)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, F32(1.0))
    return jnp.clip(jnp.round(x / scale), -top, top) * scale


@jax.custom_vjp
def dense_int8(x, w):
    return jnp.matmul(_fake_quant(x, -1, 8), _fake_quant(w, -2, 8))


def _int8_forward(x, w):
    return dense_int8(x, w), (x, w)


def _int8_backward(saved, dy):
    x, w = saved
    dx = jnp.matmul(_fake_quant(dy, -1, 8), _fake_quant(w, -1, 8).T)
    rows, cols = x.reshape(-1, x.shape[-1]), dy.reshape(-1, dy.shape[-1])
    dw = jnp.matmul(_fake_quant(rows, 0, 8).T, _fake_quant(cols, 0, 8))
    return dx, dw


dense_int8.defvjp(_int8_forward, _int8_backward)


def dense_bf16(x, w):
    """The step below float32: operands rounded to bfloat16, float32 sums."""
    bf = jnp.bfloat16
    return jnp.matmul(x.astype(bf), w.astype(bf),
                      preferred_element_type=F32)


BELOW = {"bfloat16": dense_int8, "float16": dense_int8,
         "float32": dense_bf16}
