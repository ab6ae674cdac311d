"""Entry driver ``train``: ``paddle.jit.TrainStep`` fed by
``paddle.io.DataLoader``.

Set-up builds ONE step object with its state, drives it from the seed through
its first steps by the window's own call and feed (those steps compile, and
they are what ``correct`` compares), and hands that same object to the
window.  A traced run goes on for a few more seconds under the profiler once
the window has closed, so the window's numbers are the same in both kinds of
run.  Then the program's state is freed and the plain reference follows the
first steps on the same batches.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import compare, program_counters, spans, stats


def _first_gradient(step, optimizer, model, h, how):
    """The first gradient as the optimizer got it, from its state after one
    step (``step.sync()`` then ``optimizer.state_dict()``), read leaf by leaf
    as the optimizer's own file says (``optimizers/<name>.py``)."""
    step.sync()
    states = optimizer.state_dict()["states"]
    return {name: how.first_gradient(states[str(i)], h)
            for i, (name, _) in enumerate(model.named_parameters())}


class _Feed:
    """The loader, iterated without end; the time spent waiting in
    ``next()`` is the input pipeline's share."""

    def __init__(self, loader):
        self.loader = loader
        self.it = iter(loader)
        self.wait_s = 0.0

    def next(self):
        t0 = time.perf_counter()
        with spans.span("bench.next_loader"):
            try:
                batch = next(self.it)
            except StopIteration:
                self.it = iter(self.loader)
                batch = next(self.it)
        self.wait_s += time.perf_counter() - t0
        return batch


def _drive(call, feed, until, inflight):
    """Steps until the host clock passes ``until``, at most ``inflight``
    ahead of the device; returns the time each step's loss was ready.  The
    last step's ``block_until_ready`` closes the stretch."""
    import jax

    pending, ends = [], []
    while time.time() < until:
        pending.append(call(feed.next()))
        if len(pending) > inflight:
            with spans.span("bench.wait_step"):
                jax.block_until_ready(pending.pop(0)._value)
            ends.append(time.time())
    with spans.span("bench.wait_step"):
        for p in pending:
            jax.block_until_ready(p._value)
            ends.append(time.time())
    return ends, (float(pending[-1]._value) if pending else None)


def run(ctx):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader

    cfg, wl, seed = ctx.config, ctx.workload, ctx.seed
    family = ctx.module("models", cfg["family"])
    ref = ctx.module("reference", cfg["family"])
    hyper = wl["optimizer"]
    paddle.seed(seed % (1 << 31))

    params = ref.init_params(seed, cfg)
    model, loss_fn, step_args = family.build_train(cfg, wl, params, ref)
    del params
    how = ctx.module("optimizers", hyper["name"])
    optimizer = how.build(hyper, model)
    step = paddle.jit.TrainStep(model, optimizer, loss_fn=loss_fn,
                                amp_level=wl["amp_level"],
                                amp_dtype=wl["amp_dtype"])
    feed_facts = ctx.module("traffic", wl["kind"]).describe(wl)
    batch_size = feed_facts["batch_size"]
    data = family.make_dataset(seed, cfg, wl)
    loader = DataLoader(data, batch_size=batch_size, drop_last=True,
                        num_workers=feed_facts["num_workers"],
                        worker_mode=feed_facts["worker_mode"], timeout=300)
    feed = _Feed(loader)

    def call(batch):
        with spans.span("bench.step_call"):
            return step(*step_args(batch))

    # ---- first steps: they compile, and they are what `correct` compares
    first_batches, losses, grad_norms, grad_tree = [], [], None, None
    for i in range(int(wl["compared_steps"])):
        batch = feed.next()
        first_batches.append(family.reference_batch(batch))
        losses.append(float(call(batch)._value))
        if i == 0:
            first = _first_gradient(step, optimizer, model, hyper, how)
            grad_norms = family.gradient_norms(first, ref, cfg)
            grad_tree = family.gradient_tree(first)    # to the host
            del first
    change_norms = family.change_norms(model, ref, seed, cfg)
    for _ in range(int(wl.get("extra_warm_steps", 0))):
        jax.block_until_ready(call(feed.next())._value)

    # ---- the window ----------------------------------------------------
    inflight = feed_facts["steps_in_flight"]
    feed.wait_s = 0.0
    compiles0 = ctx.compiles.n
    counters0 = program_counters.snapshot()
    t_open = ctx.open_window()
    step_ends, last_loss = _drive(call, feed, t_open + ctx.seconds, inflight)
    window_s = step_ends[-1] - t_open
    e2e = stats.train_metrics(step_ends, batch_size, t_open)
    ctx.host.update(
        input_wait_s=feed.wait_s, window_s=window_s, steps=len(step_ends),
        traffic=feed_facts,
        samples_per_step=batch_size,
        tokens_per_sample=family.tokens_per_sample(cfg, wl),
        train_ips=e2e.get("train_ips"),
        compiles_in_window=ctx.compiles.n - compiles0,
        counters=program_counters.delta(counters0,
                                        program_counters.snapshot()))
    if ctx.start_trace():
        with spans.span("bench.window"):
            traced, _ = _drive(call, feed, time.time() + ctx.trace_seconds,
                               inflight)
        ctx.stop_trace()
        ctx.host["traced_steps"] = len(traced)
    ctx.read_memory_peak()

    # ---- free the program, then the reference --------------------------
    del step, optimizer, model, loader, feed, data, call
    gc.collect()
    finite = np.isfinite(losses + [last_loss if last_loss is not None
                                   else 0.0]).all()
    checks = [compare.exact("compiles_in_window",
                            ctx.host["compiles_in_window"]),
              compare.exact("loss_not_finite", int(not finite))]
    got = (losses, grad_norms, change_norms, grad_tree)
    more, numbers = compare.train_checks(
        ref, cfg, hyper, seed, first_batches, got, wl["limits"],
        kept=ctx.kept)
    ctx.host["compared"] = dict(numbers)
    return {"end_to_end": e2e, "attempted": len(step_ends),
            "failed": 0, "checks": checks + more}


# ---- what `tools/readings.py` puts in the program's place ----------------
def stand_ins(ctx, kept):
    """Numbers of the control and of the faults this cell can have, each the
    reference put in the program's place on the batches of the run that
    ``kept`` comes from: ``{name: numbers}``.

    - ``control``: the forward's matrix products in the precision below the
      one the cell states (``reference/lower_precision.py``).
    - ``half_batch``: the second half of every batch left out, the mean
      taken over the rest.
    A step that returns its state unchanged needs no run: the change of
    every leaf is nought, which reads 1 by ``worst_leaf_gap``."""
    cfg, wl = ctx.config, ctx.workload
    ref = ctx.module("reference", cfg["family"])
    below = compare.sibling(ref, "lower_precision").BELOW[wl["amp_dtype"]]
    want, batches = kept["want"], kept["batches"]
    half = [tuple(a[:len(a) // 2] for a in b) for b in batches]
    return {
        "control": compare.train_numbers(compare.reference_training(
            ref, cfg, wl["optimizer"], ctx.seed, batches, mm=below), want),
        "half_batch": compare.train_numbers(compare.reference_training(
            ref, cfg, wl["optimizer"], ctx.seed, half), want)}
