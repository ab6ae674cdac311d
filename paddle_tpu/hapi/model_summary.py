"""paddle.summary (reference: python/paddle/hapi/model_summary.py):
layer-by-layer table of output shapes and parameter counts via forward
hooks, run on zero inputs."""

from __future__ import annotations

import numpy as np

import jax.numpy as jnp

from ..nn.layer import Layer
from ..tensor.tensor import Tensor


def summary(net, input_size=None, dtypes=None, input=None):
    if input is None:
        if input_size is None:
            raise ValueError("summary needs input_size or input")
        sizes = input_size if isinstance(input_size, list) else [input_size]
        sizes = [tuple(s) if isinstance(s, (tuple, list)) else (s,) for s in sizes]
        dts = dtypes if isinstance(dtypes, (list, tuple)) else [dtypes] * len(sizes)
        input = [Tensor(jnp.zeros([d if (d and d > 0) else 1 for d in s],
                                  dtype=jnp.dtype(dt or "float32")))
                 for s, dt in zip(sizes, dts)]
    else:
        input = input if isinstance(input, (list, tuple)) else [input]

    rows = []
    hooks = []

    def register(layer, name):
        def hook(lay, args, out):
            shapes = [list(o.shape) for o in
                      (out if isinstance(out, (tuple, list)) else (out,))
                      if isinstance(o, Tensor)]
            n_params = sum(int(np.prod(p.shape)) for p in lay._parameters.values()
                           if p is not None)
            rows.append((name, type(lay).__name__, shapes, n_params))
        hooks.append(layer.register_forward_post_hook(hook))

    for name, sub in net.named_sublayers(include_self=False):
        register(sub, name)

    was = net.training
    net.eval()
    try:
        net(*input)
    finally:
        net.training = was
        for h in hooks:
            h.remove()

    total = sum(int(np.prod(p.shape)) for p in net.parameters())
    trainable = sum(int(np.prod(p.shape)) for p in net.parameters()
                    if not p.stop_gradient)

    width = 76
    print("-" * width)
    print(f"{'Layer (type)':<38}{'Output Shape':<24}{'Param #':<12}")
    print("=" * width)
    for name, cls, shapes, n in rows:
        shape_s = str(shapes[0]) if shapes else "-"
        print(f"{name + ' (' + cls + ')':<38}{shape_s:<24}{n:<12,}")
    print("=" * width)
    print(f"Total params: {total:,}")
    print(f"Trainable params: {trainable:,}")
    print(f"Non-trainable params: {total - trainable:,}")
    print("-" * width)
    return {"total_params": total, "trainable_params": trainable}


def flops(net, input_size, custom_ops=None, print_detail=False):
    """Analytic FLOPs of a Layer (reference: paddle.flops) — counted from
    XLA's own cost model: trace the forward at ``input_size``, compile, and
    read the 'flops' cost analysis (exact for the program that will run,
    and free of per-layer bookkeeping).  Falls back to 0 if the backend
    reports no analysis."""
    import numpy as np
    import jax

    from ..framework import random as _rng
    from ..framework.state import no_grad_ctx
    from ..tensor.tensor import Tensor

    params = {k: p._value for k, p in net.named_parameters()}
    bufs = {k: b._value for k, b in net.named_buffers()}
    x = np.zeros(tuple(input_size), np.float32)

    def fwd(params, bufs, xv):
        with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                net.bind(params, bufs):
            return net(Tensor(xv))._value

    try:
        compiled = jax.jit(fwd).lower(params, bufs, x).compile()
        val = int(compiled.cost_analysis().get("flops", 0))
    except Exception:
        val = 0
    if print_detail:
        print(f"Total Flops: {val}")
    return val
