"""AdamW in plain ``jax.numpy`` and float32, from its published rule
(Loshchilov & Hutter 2019: decoupled decay, bias-corrected); imports nothing
of the program.  A reference finds its optimizer by the cell's
``optimizer.name``: another one is another file beside this.
"""

from __future__ import annotations

import jax.numpy as jnp

F32 = jnp.float32


def init_state(params, hyper):
    return {"m": {k: jnp.zeros_like(v, F32) for k, v in params.items()},
            "v": {k: jnp.zeros_like(v, F32) for k, v in params.items()}}


def seen_gradient(g, p, hyper):
    """The gradient as the optimizer's state takes it in: AdamW decays
    apart, so the first moment sees the gradient alone."""
    return g


def update(p, g, state, t, hyper):
    """One step on a dict of leaves; ``t`` counts from 1."""
    lr = F32(hyper["learning_rate"])
    b1, b2, eps, wd = (hyper["beta1"], hyper["beta2"], hyper["epsilon"],
                       hyper["weight_decay"])
    m = {k: b1 * state["m"][k] + (1 - b1) * g[k] for k in p}
    v = {k: b2 * state["v"][k] + (1 - b2) * jnp.square(g[k]) for k in p}
    new = {k: p[k] * (1 - lr * wd)
           - lr * (m[k] / (1 - b1 ** t)) / (jnp.sqrt(v[k] / (1 - b2 ** t)) + eps)
           for k in p}
    return new, {"m": m, "v": v}
