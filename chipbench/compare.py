"""The comparison that decides ``correct``.

Each number compared has a limit of its own, kept in the cell's file under
``limits`` with the readings it was set from in ``PERF.md``.  A number is
a gap between what the timed path produced and what the plain reference
gives for the same inputs; an exact comparison has the limit 0.
"""

from __future__ import annotations

import math
import statistics


def exact(name, value):
    return {"name": name, "value": value, "limit": 0, "ok": value == 0}


def bounded(name, value, limit):
    ok = value is not None and math.isfinite(value) and value <= limit
    return {"name": name, "value": value, "limit": limit, "ok": bool(ok)}


def judge(numbers, limits):
    """Every number that has a limit, held to it: the program's numbers in a
    run, a control's or a fault's in ``tools/readings.py``, the same way."""
    return [bounded(name, numbers.get(name), limit)
            for name, limit in limits.items()]


def rel_gap(got, want):
    return abs(got - want) / max(abs(want), 1e-30)


def _flat(norms):
    return [(k, i, v) for k in sorted(norms) for i, v in enumerate(norms[k])]


def worst_leaf_gap(got, want, leave_out=()):
    """The widest gap, over the leaves, between the program's norm and the
    reference's (not the norm of their difference), measured against the
    reference's norm of that leaf or of the median leaf, whichever is larger:
    some gradients are all but zero.  Returns ``(gap, leaf)``."""
    ref = _flat(want)
    median = statistics.median(v for _, _, v in ref)
    worst, where = 0.0, None
    for k, i, w in ref:
        if (k, i) in leave_out:
            continue
        g = got[k][i]
        gap = abs(g - w) / max(w, median, 1e-30)
        if not math.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, where = gap, f"{k}[{i}]"
    return worst, where


def near_zero_leaves(grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's): under Adam they move by round-off
    alone, so they are left out of the parameter change.  By a rule on the
    reference's gradient, not by name."""
    ref = _flat(grad_norms)
    median = statistics.median(v for _, _, v in ref)
    return {(k, i) for k, i, v in ref if v < share * median}


def tree_rel_err(got, want):
    """The norm of the difference of two trees of like leaves over the norm
    of ``want``, all leaves together: first-order in rounding noise, where a
    gap between norms is second-order."""
    import jax.numpy as jnp

    f32 = jnp.float32
    num = den = 0.0
    for k in sorted(want):
        w = jnp.asarray(want[k]).astype(f32)
        num += float(jnp.sum(jnp.square(jnp.asarray(got[k]).astype(f32) - w)))
        den += float(jnp.sum(jnp.square(w)))
    return math.sqrt(num / max(den, 1e-300))


def train_numbers(got, want):
    """The numbers of a training cell.  ``got`` and ``want`` are
    ``(losses, grad_norms, change_norms, first gradient)`` of the program
    (or of a control or a fault put in its place) and of the reference."""
    g_loss, g_grad, g_change, g_tree = got
    w_loss, w_grad, w_change, w_tree = want
    out = {"grad_rel_err": tree_rel_err(g_tree, w_tree)}
    for i, (a, b) in enumerate(zip(g_loss, w_loss)):
        out[f"loss_step{i + 1}"] = rel_gap(a, b)
    out["grad_norm_gap"], out["grad_norm_leaf"] = worst_leaf_gap(g_grad, w_grad)
    skip = near_zero_leaves(w_grad)
    out["update_norm_gap"], out["update_norm_leaf"] = worst_leaf_gap(
        g_change, w_change, leave_out=skip)
    out["leaves_left_out"] = len(skip)
    return out


def sibling(ref, name):
    """A module beside the reference (``reference/adamw.py``)."""
    import importlib

    return importlib.import_module(
        ref.__name__.rsplit(".", 1)[0] + "." + name)


def reference_training(ref, cfg, hyper, seed, batches, mm=None):
    """The reference's ``(losses, grad_norms, change_norms, first gradient
    as the optimizer takes it in)`` over the first steps, from the seed
    alone: its own weights, its own optimizer."""
    import jax.numpy as jnp

    p0 = ref.init_params(seed, cfg)
    kw = {} if mm is None else {"mm": mm}
    losses, g1, p = ref.train_steps(
        p0, [tuple(jnp.asarray(a) for a in b) for b in batches], cfg, hyper,
        **kw)
    optim = sibling(ref, hyper["name"])
    seen = optim.seen_gradient(
        g1, {k: v.astype(g1[k].dtype) for k, v in p0.items()}, hyper)
    change = {k: p[k] - p0[k].astype(p[k].dtype) for k in p}
    return (losses, ref.leaf_norms(seen, cfg), ref.leaf_norms(change, cfg),
            seen)


def train_checks(ref, cfg, hyper, seed, batches, got, limits, kept=None):
    want = reference_training(ref, cfg, hyper, seed, batches)
    numbers = train_numbers(got, want)
    if kept is not None:
        kept.update(got=got, want=want, numbers=numbers, batches=batches)
    return judge(numbers, limits), numbers


def serve_numbers(ref, cfg, seed, dtype, served, mm=None, shape=None):
    """The gap by which a served token's logit lies below the reference's
    best, over every served token of the sampled requests: the widest
    (``logit_gap_max``) and the mean (``logit_gap_mean``, which grows with
    the square of the rounding where the widest grows with its first power).

    ``served`` is a list of ``(prompt ids, served ids)``.  The reference
    makes its own weights from the seed (``shape``: the cell's ``weights``),
    in the type they are served in, and runs once over each prompt with its
    served tokens, padded to the configuration's context so that one program
    serves every length (the pad lies after the last token, where causal
    attention never looks).  With ``mm`` (a control's matrix product) nothing
    is decoded: at the same positions of the same prompts and tokens, those
    the program is judged on and no others, the gap is read of the token
    that the lower precision puts first.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    width = ref.sizes(cfg)["P"]
    params = ref.init_params(seed, cfg, dtype=jnp.dtype(dtype), shape=shape)

    @jax.jit
    def gaps(params, ids, picked):
        lg = ref.logits(params, ids[None], cfg)[0]
        return jnp.max(lg, -1) - jnp.take_along_axis(
            lg, picked[:, None], -1)[:, 0]

    @jax.jit
    def control_pick(params, ids):
        return jnp.argmax(ref.logits(params, ids[None], cfg, mm=mm)[0], -1)

    worst, total, n, repeats, where = 0.0, 0.0, 0, 0, None
    with jax.default_matmul_precision("highest"):
        for r, (prompt, out) in enumerate(served):
            if not len(out):
                continue
            ids = np.concatenate([prompt, out]).astype(np.int64)
            pad = np.zeros(width + 1, np.int64)
            pad[:len(ids)] = ids
            # position t predicts token t+1; the served ones start at P
            inputs, picked = jnp.asarray(pad[:-1]), jnp.asarray(pad[1:])
            if mm is not None:
                picked = control_pick(params, inputs)
            g = np.asarray(gaps(params, inputs, picked))
            g = g[len(prompt) - 1:len(ids) - 1]
            n += len(g)
            total += float(g.sum())
            repeats += int((out[1:] == out[:-1]).sum())
            if float(g.max()) >= worst:
                worst = float(g.max())
                where = f"request {r} token {int(g.argmax())}"
    return {"logit_gap_max": worst,
            "logit_gap_mean": total / n if n else None,
            "logit_gap_where": where, "tokens_compared": n,
            "tokens_repeating_the_last": repeats}
