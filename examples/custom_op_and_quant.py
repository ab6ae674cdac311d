"""Custom Pallas op + quantization-aware training, end to end.

Demonstrates the round-4 extension surfaces:
1. paddle.register_op — install a user Pallas kernel as a first-class op
   (SURVEY.md §2.1 custom-operator row: the PD_BUILD_OP equivalent),
2. paddle.quantization.QAT — fake-quant fine-tuning with straight-through
   gradients,
3. both running inside ONE fused TrainStep XLA program.

Run (CPU): JAX_PLATFORMS=cpu python examples/custom_op_and_quant.py
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.optimizer as opt
from paddle_tpu.quantization import QAT, QuantConfig

INTERPRET = jax.default_backend() != "tpu"


# ---- 1. a user Pallas kernel: fused bias+gelu ----
def _bias_gelu_kernel(x_ref, b_ref, o_ref):
    x = x_ref[...] + b_ref[...]
    o_ref[...] = (x * 0.5 * (1.0 + jax.lax.erf(x * 0.70710678))).astype(o_ref.dtype)


def bias_gelu(x, b):
    from jax.experimental import pallas as pl

    return pl.pallas_call(
        _bias_gelu_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=INTERPRET,
    )(x, jnp.broadcast_to(b, x.shape))


def bias_gelu_bwd(res, g):
    x, b = res
    z = x + b
    cdf = 0.5 * (1.0 + jax.lax.erf(z * 0.70710678))
    pdf = jnp.exp(-0.5 * z * z) * 0.3989422804
    dz = g * (cdf + z * pdf)
    return dz, dz.sum(tuple(range(dz.ndim - 1)))


paddle.register_op("fused_bias_gelu", bias_gelu, vjp=bias_gelu_bwd,
                   override=True)


class Net(nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = nn.Linear(32, 64, bias_attr=False)
        self.b1 = self.create_parameter([64], is_bias=True)
        self.fc2 = nn.Linear(64, 10)

    def forward(self, x):
        h = paddle.ops.fused_bias_gelu(self.fc1(x), self.b1)
        return self.fc2(h)


def main():
    paddle.seed(0)
    model = Net()
    # ---- 2. quantize for QAT (wraps Linear layers with fake-quanters) ----
    model = QAT(QuantConfig()).quantize(model)
    o = opt.AdamW(learning_rate=3e-3, parameters=model.parameters())
    # ---- 3. one fused step: fwd (pallas + fake-quant) + bwd + update ----
    step = paddle.jit.TrainStep(model, o, loss_fn=nn.CrossEntropyLoss())

    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(128, 32).astype("float32"))
    y = paddle.to_tensor(rs.randint(0, 10, (128,)).astype("int64"))
    for i in range(30):
        loss = step(x, y)
        if i % 10 == 0:
            print(f"step {i:3d} loss {float(loss):.4f}")
    print(f"final loss {float(loss):.4f}")

    from paddle_tpu.quantization import extract_scales

    scales = extract_scales(model)
    print(f"{len(scales)} calibrated quant scales, e.g.",
          dict(list(scales.items())[:2]))


if __name__ == "__main__":
    main()
