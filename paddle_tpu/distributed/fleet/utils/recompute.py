"""Activation recomputation (reference:
distributed/fleet/recompute/recompute.py — a PyLayer that stashes RNG state
and replays forward during backward).

TPU-native: ``jax.checkpoint`` (remat) IS this feature, compiler-integrated:
the traced segment's activations are dropped and recomputed in the backward
pass, with RNG replay free because keys are values.  The wrapper keeps the
reference call shape ``recompute(fn, *args)`` and works both eagerly (tape
node wrapping the remat'd function) and under to_static/TrainStep traces.

Kept by default: a flash attention kernel's output and log-sum-exp
(``ops.flash_attention.RESIDUAL_NAMES``), because a region that drops them
runs the forward kernel a second time for two small tensors; a region that
holds no such kernel keeps nothing but its arguments.
"""

from __future__ import annotations

import functools

import jax

from ....ops.flash_attention import RESIDUAL_NAMES
from ....profiler import metrics as _metrics
from ....tensor.dispatch import apply as _apply
from ....tensor.tensor import Tensor

# the default policy: what only a flash forward kernel can make again
_KEEP_FLASH_RESIDUALS = jax.checkpoint_policies.save_only_these_names(
    *RESIDUAL_NAMES)

_m_regions = _metrics.counter(
    "recompute.regions_traced",
    "regions traced under fleet.utils.recompute, by what they keep (policy = "
    "flash_residuals: the default; caller: an explicit checkpoint_policy; "
    "none: checkpoint_policy=None spelled out); counted when a region is "
    "traced, not when it runs")


def recompute(function, *args, **kwargs):
    """Run ``function(*args)`` with activation checkpointing.

    preserve_rng_state / use_reentrant kwargs are accepted for parity; RNG
    correctness is structural (keys thread through the trace).

    Without ``checkpoint_policy=`` the region keeps the flash kernels'
    named residuals and recomputes the rest: 68 MB a layer at B=2, S=4,096
    against a 4.6 ms kernel run again.  A ``checkpoint_policy=`` given is
    ``jax.checkpoint``'s ``policy`` as it stands (``None``: keep nothing).
    """
    kwargs.pop("preserve_rng_state", None)
    kwargs.pop("use_reentrant", None)
    if "checkpoint_policy" in kwargs:
        policy = kwargs.pop("checkpoint_policy")
        kept = "none" if policy is None else "caller"
    else:
        policy, kept = _KEEP_FLASH_RESIDUALS, "flash_residuals"

    tensor_idx = [i for i, a in enumerate(args) if isinstance(a, Tensor)]
    consts = {i: a for i, a in enumerate(args) if i not in set(tensor_idx)}

    @functools.partial(jax.checkpoint, policy=policy)
    def inner(*tvals):
        _m_regions.inc(policy=kept)
        call = []
        it = iter(tvals)
        for i in range(len(args)):
            call.append(Tensor(next(it)) if i in set(tensor_idx) else consts[i])
        out = function(*call, **kwargs)
        if isinstance(out, Tensor):
            return out._value
        if isinstance(out, (tuple, list)):
            return tuple(o._value if isinstance(o, Tensor) else o for o in out)
        return out

    return _apply(inner, *[args[i] for i in tensor_idx], op_name="recompute",
                  n_outs=None)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """reference: recompute_sequential — checkpoint a Sequential span-wise."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    layers = list(functions)
    n = len(layers)
    per = max(1, n // max(segments, 1))
    x = args[0] if len(args) == 1 else args
    i = 0
    while i < n:
        span = layers[i:i + per]

        def run(h, _span=span):
            for l in _span:
                h = l(h)
            return h

        x = recompute(run, x)
        i += per
    return x
