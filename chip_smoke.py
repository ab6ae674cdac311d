"""chip_smoke.py — the quickest proof that paddle_tpu still starts on the chip.

    python chip_smoke.py [phase ...]      # no arguments: every phase

One process, the public entry points, full width, random weights from a seed:

- kernels: every Pallas kernel a public entry dispatches to on TPU, compiled
  (not interpreted), against its ``*_ref`` at GPT-base shapes and at D=128
  with GQA; flash attention also at latent attention's two head sizes (192
  and 128), and one dropless expert layer at Kanana-2's widths against a
  dense mask over its experts;
- train_resnet: ResNet-50 through ``paddle.jit.TrainStep`` (B=128, 224x224,
  bf16 O2, Momentum), fed by a ``DataLoader`` with two process workers;
- train_gpt: GPT-base LM step at S=1024, so attention runs the Pallas flash
  forward and both backward kernels;
- serve_bf16 / serve_int8: ``ServingEngine`` at gpt2-medium's widths and
  GPT-base's depth answering mixed
  requests through ``submit()/result()`` and ``stream()``, with the lowered
  decode, prefill-chunk and prefill programs checked for their Mosaic calls
  per layer and their compiled text for any instruction over a whole pool;
- serve_lfm2: a small hybrid of the LFM2-MoE family (hidden 2048, 32 query
  heads in groups of 4 over 8 KV heads of 64, 10 layers, 16 experts top-4)
  through the same engine with per-slot convolution state beside its pages,
  its tokens held to the model's own dense forward, and the decode, chunk and
  write kernels at those heads over pools 128 lanes wide and tables 256
  pages wide (4,096 positions), and the grouped-product kernel at a chunk's
  1,024 assignments over 64 experts of 2,048 x 1,536;
- multichip: with >= 4 chips, data-parallel ResNet-50, the all-reduce probe,
  ring attention and tensor-parallel serving, each with its arrays checked
  to sit on four distinct devices.  On fewer chips: ``skipped: N device``.

It refuses to start unless ``jax.default_backend() == "tpu"``, wraps no phase
in ``try/except`` (a failure is a traceback and a non-zero exit, with no result
line), and ends stdout with two lines: ``[chip_smoke] report {...}`` (versions,
per-phase facts, compile-cache hits, wall seconds) and then the result the
driver reads, exactly ``{"ok": true, "device": {"platform", "kind", "count"}}``.
Per-phase seconds are set-up facts of a correctness run, not throughput
measurements.
"""

from __future__ import annotations

import gc
import json
import re
import sys
import time

import numpy as np

SEED = 0
MOSAIC_CALL = "tpu_custom_call"

#: max |got - want| / max |want| allowed between a compiled kernel and its
#: float32 reference.  Inputs are bf16 (or int8 pools) and every kernel
#: rounds its float32 accumulator to the input dtype on the way out, so the
#: bound is bf16-grade; the measured errors are printed per check.
KERNEL_TOL = 2e-2

#: the run's sizes.  tests/test_smoke_rehearsal.py rehearses the same phase
#: functions on the CPU with a toy copy of this table.
FULL = {
    # (H, HKV, D): GPT-base heads (rows of 64 lanes, and 12 heads are no
    # whole tile: decoded a page a grid step), GQA at head_dim 128, the
    # served pools' 16 rows of 128
    "kernels": {"heads": [(12, 12, 64), (16, 4, 128), (16, 16, 128)],
                "page_size": 16,
                "table_pages": 64, "rows": 8, "chunk": 8,
                # (B, S, H, D) or (B, S, H, D_qk, D_v): GPT heads, and
                # latent attention's 192 / 128 at the trained length
                "flash": [(2, 1024, 12, 64), (1, 2048, 4, 128),
                          (1, 4096, 32, 192, 128)],
                # one expert layer at Kanana-2's widths, one chip's share:
                # (tokens, hidden, width, router experts, held, top-k)
                "experts": (8192, 2048, 768, 128, 16, 6)},
    "resnet": {"arch": "resnet50", "classes": 1000, "batch": 128,
               "image": 224, "steps": 6},
    # GPTForCausalLM() defaults are GPT-base: 12 x 768, 12 heads, vocab 50304
    "gpt": {"model": {}, "batch": 4, "seq": 1024, "steps": 4},
    # served at gpt2-medium's widths (16 heads of 64: they fill the sublane
    # tiles, so the device lays the pools out as the kernels take them and
    # the programs must leave them alone), GPT-base's depth
    "serve": {"model": {"hidden_size": 1024, "num_attention_heads": 16},
              "num_slots": 4, "page_size": 16, "chunk": 64,
              # (prompt tokens, new tokens): two monolithic prefill buckets
              # (16, 48) and prompts past `chunk` that ingest by chunks
              "requests": [(9, 24), (40, 12), (150, 16), (14, 40), (45, 8),
                           (300, 20), (16, 32), (90, 10)],
              "stream": (12, 16)},
    "multichip": {"chips": 4, "ring": (1, 2048, 4, 64),
                  "allreduce_mb": 64},
    # a small hybrid at LFM2-24B-A2B's hidden size and heads (32 query
    # heads in groups of 4 over 8 KV heads of 64, stored 128 lanes wide),
    # served at 4,096 positions: tables 256 pages wide
    "lfm2": {"model": {"hidden_size": 2048, "num_attention_heads": 32,
                       "num_key_value_heads": 8, "intermediate_size": 4096,
                       "moe_intermediate_size": 512, "num_experts": 16,
                       "num_experts_per_tok": 4, "num_hidden_layers": 10,
                       "vocab_size": 8192, "dtype": "bfloat16"},
             # 8 slots: pools of 134 MB, past what the compiler stages whole
             # in VMEM around the monolithic prefill's writer (a 34 MB pool
             # was: call 2 of PR 30; the cell's 1.07 GB pools are not:
             # tests/test_paged_chunk_compiles.py compiles that program)
             "num_slots": 8, "page_size": 16, "chunk": 64,
             "max_model_len": 4096,
             "requests": [(9, 24), (40, 12), (150, 16), (14, 40), (45, 8),
                          (300, 20), (16, 32), (90, 10)],
             "stream": (12, 16),
             # a chunk's 64 x top-4 = 256 assignments over 16 experts take
             # the repo's grouped kernel: 3 products in 8 expert layers
             "chunk_grouped_kernels": 24,
             # the cell's chunk: (rows, K, N, groups) of a gate product
             "kernels": {"heads": (32, 8, 64), "rows": 8, "chunk": 8,
                         "grouped": (1024, 2048, 1536, 64)}},
}


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.all(np.isfinite(got)):
        raise AssertionError("non-finite values in a kernel output")
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-6))


def expect_mosaic(what, jitted, args, want):
    """The program ``jitted`` lowers to for ``args`` must carry ``want``
    Mosaic custom calls — a silent reference path has none.  Only shapes are
    used: nothing runs and nothing is donated."""
    import jax

    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        if hasattr(a, "shape") and hasattr(a, "dtype") else a, args)
    text = jitted.trace(*shapes).lower(lowering_platforms=("tpu",)).as_text()
    n = text.count(MOSAIC_CALL)
    if n != want:
        raise AssertionError(f"{what}: lowered program carries {n} Mosaic "
                             f"custom calls, expected {want}")
    return n


def distinct_devices(x):
    return len({s.device for s in x.addressable_shards})


class CacheCounter:
    """Counts JAX's persistent-compile-cache hits and misses."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ------------------------------------------------------------------ kernels
def _check(name, jitted, ref, args, want_calls, out):
    """Run a compiled kernel entry and its reference on the same inputs."""
    import jax

    n = expect_mosaic(name, jitted, args, want_calls)
    got = jax.block_until_ready(jitted(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(ref(*args))
    errs = [rel_err(g, w) for g, w in zip(jax.tree_util.tree_leaves(got),
                                          jax.tree_util.tree_leaves(want))]
    err = max(errs)
    log(f"  {name}: rel_err {err:.2e} ({n} Mosaic call(s))")
    if err > KERNEL_TOL:
        raise AssertionError(f"{name}: rel_err {err:.3e} > {KERNEL_TOL}")
    out[name] = round(err, 6)


def phase_kernels(cfg):
    import importlib

    import jax
    import jax.numpy as jnp

    # paddle_tpu.ops re-exports functions of the same names as the modules
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")

    t0 = time.time()
    out = {}
    rs = np.random.RandomState(SEED)
    ps, NP, B, C = (cfg["page_size"], cfg["table_pages"], cfg["rows"],
                    cfg["chunk"])

    # flash forward + both backward kernels through the public [B,S,H,D]
    # entry, against flash_attention._ref_attention in float32
    for (b, s, h, d, *rest) in cfg["flash"]:
        dv = rest[0] if rest else d
        q, k = (jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16)
                for _ in range(2))
        v, w = (jnp.asarray(rs.randn(b, s, h, dv), jnp.bfloat16)
                for _ in range(2))

        def flash_out(q, k, v):
            return fa.flash_attention_fn(q, k, v, causal=True)

        def ref_out(q, k, v):
            o = fa._ref_attention(
                *(jnp.moveaxis(x, 2, 1).reshape(b * h, s, x.shape[3])
                  .astype(jnp.float32) for x in (q, k, v)), d ** -0.5, True)
            return jnp.moveaxis(o.reshape(b, h, s, dv), 1, 2)

        def grads(attend):
            def loss(q, k, v, w):
                return jnp.sum(attend(q, k, v).astype(jnp.float32)
                               * w.astype(jnp.float32))
            return jax.grad(loss, argnums=(0, 1, 2))

        tag = f"S{s}_H{h}_D{d}" + (f"_DV{dv}" if rest else "")
        _check(f"flash_fwd/{tag}", jax.jit(flash_out), ref_out, (q, k, v), 1,
               out)
        # grad = forward (saving lse) + dk/dv kernel + dq kernel
        _check(f"flash_bwd/{tag}", jax.jit(grads(flash_out)), grads(ref_out),
               (q, k, v, w), 3, out)

    for (H, HKV, D) in cfg["heads"]:
        P = B * NP + 1
        q = jnp.asarray(rs.randn(B, H, D), jnp.bfloat16)
        kf = rs.randn(P, ps, HKV, D).astype(np.float32)
        vf = rs.randn(P, ps, HKV, D).astype(np.float32)
        kp, vp = jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)
        table = jnp.asarray(rs.permutation(P - 1)[:B * NP].reshape(B, NP),
                            jnp.int32)
        # lengths: 1, page edges, the full table, a block's edge (128
        # keys) and one key past it, one that overruns the table, and
        # random in between
        lens = rs.randint(1, NP * ps + 1, (B,))
        edges = [1, ps, ps + 1, NP * ps, 128, 129, NP * ps + 37]
        lens[:len(edges)] = edges[:B]
        lens = jnp.asarray(lens, jnp.int32)
        tag = f"H{H}_HKV{HKV}_D{D}"
        # a table that is no multiple of a block, narrower than one at
        # the rehearsal's size: its first columns, the lengths as they are
        # (most now overrun it)
        narrow = table[:, :max(NP // 4 - 3, 1)]

        # the kernels read their pools as the engine holds them: stacked
        # over layers, the layer an index (here the second of two); the
        # references read that layer sliced out
        def stacked(entry, *tail):
            return jax.jit(lambda x, *pools: entry(
                x, *(jnp.stack([jnp.zeros_like(p), p]) for p in pools),
                *tail, layer=1))

        kq, ks = pa.quantize_kv(jnp.asarray(kf))
        vq, vs = pa.quantize_kv(jnp.asarray(vf))
        for tb, width in ((table, ""), (narrow, f"_NP{narrow.shape[1]}")):
            _check(f"paged_flash/{tag}{width}",
                   stacked(pa.paged_attention, tb, lens),
                   lambda q, kp, vp, tb=tb: pa.paged_attention_ref(
                       q, kp, vp, tb, lens),
                   (q, kp, vp), 1, out)
            _check(f"paged_q_flash/{tag}{width}",
                   stacked(pa.paged_attention_quantized, tb, lens),
                   lambda q, *a, tb=tb: pa.paged_attention_quantized_ref(
                       q, *a, tb, lens),
                   (q, kq, vq, ks, vs), 1, out)

        # the chunk path: C positions per slot, each with its own length —
        # the reference attends the [B*C]-row expansion densely
        qc = jnp.asarray(rs.randn(B, C, H, D), jnp.bfloat16)
        base = jnp.asarray(rs.randint(0, NP * ps - C, (B,)), jnp.int32)

        def expand(base):
            lens2 = base[:, None] + 1 + jnp.arange(C, dtype=jnp.int32)[None]
            table2 = jnp.broadcast_to(table[:, None], (B, C, NP))
            return table2.reshape(B * C, NP), lens2.reshape(-1)

        def chunk_ref(qc, kp, vp, base):
            t2, l2 = expand(base)
            return pa.paged_attention_ref(
                qc.reshape(B * C, H, D), kp, vp, t2, l2).reshape(B, C, H, D)

        def chunk_q_ref(qc, kq, vq, ks, vs, base):
            t2, l2 = expand(base)
            return pa.paged_attention_quantized_ref(
                qc.reshape(B * C, H, D), kq, vq, ks, vs, t2,
                l2).reshape(B, C, H, D)

        _check(f"paged_chunk/{tag}",
               jax.jit(lambda qc, kp, vp, base: stacked(
                   pa.paged_chunk_attend, table, base)(qc, kp, vp)),
               chunk_ref, (qc, kp, vp, base), 1, out)
        _check(f"paged_chunk_quant/{tag}",
               jax.jit(lambda qc, kq, vq, ks, vs, base: stacked(
                   pa.paged_chunk_attend_quant, table, base)(
                       qc, kq, vq, ks, vs)),
               chunk_q_ref, (qc, kq, vq, ks, vs, base), 1, out)

        # the pool writer against the plain scatter, bit for bit (0.0): a
        # decode token, and a chunk that enters its first page part of the
        # way down; slot 0 runs out of table and the last slot's table is
        # one page under every entry, so merges meet in the output block
        kn, vn = (jnp.asarray(rs.randn(B, C, HKV, D), jnp.bfloat16)
                  for _ in range(2))
        wlens = base.at[0].set(NP * ps - 2)
        wtable = table.at[B - 1].set(P - 1)
        for name, pools in (("paged_write", (kp, vp)),
                            ("paged_write_quant", (kq, vq, ks, vs))):
            pools = tuple(jnp.stack([p, p]) for p in pools)
            for width in (1, C):
                _check(f"{name}/C{width}_{tag}",
                       jax.jit(lambda kn, vn, *pl: pa.paged_pool_write(
                           pl, kn[:, :width], vn[:, :width], wtable, wlens,
                           1)),
                       lambda kn, vn, *pl: tuple(
                           pa.paged_table_chunk_write(p, x, wtable, wlens, 1)
                           for p, x in zip(pl, pa._pool_rows(
                               pl, kn[:, :width], vn[:, :width]))),
                       (kn, vn, *pools), 1, out)
    if "experts" in cfg:
        _check_experts(cfg["experts"], rs, out)
    return {"checks": len(out), "max_rel_err": max(out.values()),
            "tolerance": KERNEL_TOL, "rel_err": out,
            "seconds": round(time.time() - t0, 1)}


def _check_experts(sizes, rs, out):
    """One dropless expert layer's routed part (sigmoid top-k over all the
    router's experts, the held ones' grouped products), forward and
    gradients, against a dense mask over the held experts in float32.  The
    reference takes the program's own choice of experts: a bf16 score can
    swap a token's last two."""
    import importlib

    import jax
    import jax.numpy as jnp

    moe = importlib.import_module(
        "paddle_tpu.distributed.fleet.meta_parallel.moe")
    T, H, F, E, held, k = sizes
    bf = jnp.bfloat16
    x = jnp.asarray(rs.randn(T, H), bf)
    rw = jnp.asarray(rs.randn(H, E) * 0.02, bf)
    bias = jnp.zeros((E,), jnp.float32)
    wg, wu = (jnp.asarray(rs.randn(held, H, F) * 0.02, bf) for _ in range(2))
    wd = jnp.asarray(rs.randn(held, F, H) * 0.02, bf)
    cot = jnp.asarray(rs.randn(T, H), bf)

    def routed(x, rw, wg, wu, wd):
        return moe.routed_experts(x, rw, bias, wg, wu, wd, top_k=k,
                                  scale=2.448)[0]

    def dense(x, rw, wg, wu, wd):
        f32 = jnp.float32
        x, rw, wg, wu, wd = (a.astype(f32) for a in (x, rw, wg, wu, wd))
        idx, w = moe.sigmoid_topk(x, rw, bias, k, 2.448)
        y = jnp.zeros_like(x)
        for e in range(held):
            mine = jnp.sum(jnp.where(idx == e, w, 0.0), -1)[:, None]
            y = y + mine * ((jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
        return y

    def grads(f):
        def loss(*a):
            return jnp.sum(f(*a).astype(jnp.float32) * cot.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 2, 3, 4))

    tag = f"T{T}_H{H}_F{F}_E{held}of{E}_top{k}"
    args = (x, rw, wg, wu, wd)
    # the grouped products become kernels in the TPU's compiler, after the
    # lowering that ``_check`` counts Mosaic calls in: none there
    _check(f"experts_fwd/{tag}", jax.jit(routed), dense, args, 0, out)
    _check(f"experts_bwd/{tag}", jax.jit(grads(routed)), grads(dense), args,
           0, out)


# ------------------------------------------------------------------ trainer
class _SeededImages:
    """A map-style dataset of seeded random images; pure numpy, so the
    DataLoader's forked workers never touch jax."""

    def __init__(self, n, image, classes):
        self.n, self.image, self.classes = n, image, classes

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        rs = np.random.RandomState(SEED + i)
        return (rs.randn(3, self.image, self.image).astype("float32"),
                np.int64(rs.randint(0, self.classes)))


def _train(step, batch, steps):
    """First call (trace + compile), then ``steps`` more on the same batch:
    the loss must stay finite and end lower, from ONE trace."""
    t0 = time.time()
    first = float(step(*batch))
    t1 = time.time()
    losses = [float(step(*batch)) for _ in range(steps)]
    t2 = time.time()
    if not all(np.isfinite(l) for l in [first] + losses):
        raise AssertionError(f"non-finite loss: {[first] + losses}")
    if not losses[-1] < first:
        raise AssertionError(f"loss did not fall: {[first] + losses}")
    if len(step._compiled) != 1:
        raise AssertionError(f"{len(step._compiled)} traces for one batch "
                             "shape, expected 1")
    return {"compile_s": round(t1 - t0, 1), "steady_s": round(t2 - t1, 2),
            "steps": steps, "loss_first": round(first, 4),
            "loss_last": round(losses[-1], 4), "traces": 1}


def _resnet_step(cfg, dp=False):
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as opt

    paddle.seed(SEED)
    net = getattr(paddle.vision.models, cfg["arch"])(
        num_classes=cfg["classes"])
    # bench.py's configuration except the learning rate (a run-time input of
    # the same program): at its 0.1 the loss on ONE fixed batch oscillates
    # over the first steps, and the check here is that it falls
    o = opt.Momentum(learning_rate=0.01, momentum=0.9,
                     parameters=net.parameters(), weight_decay=1e-4)
    model = None
    if dp:
        import paddle_tpu.distributed.fleet as fleet

        model = fleet.distributed_model(net)
        o = fleet.distributed_optimizer(o)
    step = paddle.jit.TrainStep(net, o, loss_fn=nn.CrossEntropyLoss(),
                                amp_level="O2", amp_dtype="bfloat16")
    return net, model, step


def _resnet_batch(cfg):
    """One batch assembled by two forked DataLoader workers."""
    from paddle_tpu.io import DataLoader

    data = _SeededImages(2 * cfg["batch"], cfg["image"], cfg["classes"])
    loader = DataLoader(data, batch_size=cfg["batch"], num_workers=2,
                        worker_mode="process", timeout=300)
    batches = list(loader)      # runs the workers to the end and joins them
    if len(batches) != 2:
        raise AssertionError(f"expected 2 batches, got {len(batches)}")
    return batches[0]


def phase_train_resnet(cfg):
    net, _, step = _resnet_step(cfg)
    x, y = _resnet_batch(cfg)
    facts = _train(step, (x, y), cfg["steps"])
    facts.update(model=cfg["arch"], batch=cfg["batch"], image=cfg["image"],
                 dataloader="2 process workers")
    return facts


def phase_train_gpt(cfg):
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as opt
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(SEED)
    m = GPTForCausalLM(**cfg["model"])
    layers = len(m.gpt.layers)
    vocab = m.gpt.word_embeddings.weight.shape[0]
    o = opt.AdamW(learning_rate=1e-3, parameters=m.parameters(),
                  weight_decay=0.01)
    step = paddle.jit.TrainStep(m, o, amp_level="O2", amp_dtype="bfloat16")
    ids = paddle.to_tensor(np.random.RandomState(SEED).randint(
        0, vocab, (cfg["batch"], cfg["seq"])).astype("int64"))
    batch = ({"input_ids": ids, "labels": ids},)
    facts = _train(step, batch, cfg["steps"])
    # the step must contain flash forward + dk/dv + dq per layer
    n = expect_mosaic(
        "GPT train step", step._last_fn._jitted,
        (step._diff_params, step._opt_state, step._buffers,
         step._frozen_params, step._lr_dev, step._rng_carry, ids._value,
         ids._value), 3 * layers)
    facts.update(layers=layers, batch=cfg["batch"], seq=cfg["seq"],
                 mosaic_calls=n)
    return facts


# ------------------------------------------------------------------- server
def _prompts(cfg, vocab):
    rs = np.random.RandomState(SEED)
    reqs = list(cfg["requests"]) + [cfg["stream"]]
    return [(rs.randint(1, vocab, (n,)).astype("int64"), new)
            for n, new in reqs]


def _serve_pass(engine, prompts):
    """All but the last request through submit()/result(), the last through
    stream().  Returns the generated ids per request."""
    handles = [engine.submit(p, max_new_tokens=n) for p, n in prompts[:-1]]
    sp, sn = prompts[-1]
    streamed = list(engine.stream(sp, max_new_tokens=sn))
    outs = [h.result(timeout=900) for h in handles]
    for h, (_, n), ids in zip(handles, prompts, outs):
        if h.status != "completed" or len(ids) != n:
            raise AssertionError(
                f"request {h.request_id}: status {h.status}, "
                f"{len(ids)}/{n} tokens")
    if len(streamed) != sn:
        raise AssertionError(f"stream: {len(streamed)}/{sn} tokens")
    return outs + [streamed]


def _engine_programs(engine, chunk):
    """The engine's decode, prefill-chunk and (smallest bucket) prefill
    programs, each with the arguments it is dispatched with (the layouts of
    ServingEngine._warm_step / _warm_prefill_chunk / _warm_prefill)."""
    def tail(b):
        return (engine._numeric_inject(b),) if engine._numeric_guard else ()

    def row(width, dtype):
        return np.zeros((1, width), dtype) if width else np.zeros((1,), dtype)

    one = (np.full((1, engine.table_width), engine._scratch, np.int32),
           row(0, np.int32), row(0, np.float32), engine._base_key,
           *engine._prefill_extra(None), *tail(1))
    s_pad = engine._prefill_bucket(1)
    return {
        "decode": (engine._step_program()[0], (
            engine._params, engine._bufs, engine._h_last, *engine._pools,
            engine._h_table, engine._h_lens, engine._h_temps,
            engine._base_key, *tail(None))),
        "prefill_chunk": (engine._prefill_chunk_program(chunk)[0], (
            engine._params, engine._bufs, row(chunk, np.int64),
            row(0, np.int32), *engine._pools, *one)),
        "prefill": (engine._prefill_program(s_pad)[0], (
            engine._params, engine._bufs, row(s_pad, np.int64),
            *engine._pools, *one)),
    }


def _decode_sweep(engine):
    """How the decode kernel walks one device's share of the engine's
    pools: the kernel that attends, the pages of one block of its sweep,
    and its grid steps a layer."""
    import importlib

    import jax

    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    local = engine._pools[0].addressable_shards[0].data
    slots = engine._h_lens.shape[0]
    blocking = pa._decode_blocking(
        jax.ShapeDtypeStruct((slots,) + local.shape[-2:], local.dtype),
        local, engine.table_width)
    if blocking is None:        # no DMA takes a page of these pools
        return {"kernel": "page", "pages_a_step": 1,
                "grid_steps_a_layer": slots * engine.table_width}
    return {"kernel": "decode", "pages_a_step": blocking[0],
            "grid_steps_a_layer": slots}


def _expect_engine_mosaic(engine, chunk, layers, chunk_grouped=0):
    """Mosaic calls in the lowered serving programs: the pool writer
    (``paged_write``) ONCE, a function of its shapes that every layer
    calls, and in every layer the decode kernel in the decode program and
    the chunk kernel in the prefill-chunk program; a whole-prompt prefill
    attends densely and only writes.  ``chunk_grouped``: the expert
    layers' grouped products in the prefill-chunk program, where its rows
    take the repo's kernel (``ops/grouped_matmul.py``; XLA's own for
    ``ragged_dot`` come after this lowering)."""
    want = {"decode": layers + 1,
            "prefill_chunk": layers + 1 + chunk_grouped, "prefill": 1}
    for name, (prog, args) in _engine_programs(engine, chunk).items():
        expect_mosaic(f"serving {name} program", prog, args, want[name])
    return want


_HLO_RESULT = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ([\w\-]+)\(")
_HLO_ARRAY = re.compile(r"\b[a-z]+[0-9]+\[([0-9,]*)\]")
#: instructions that name a buffer and move nothing
_HLO_NO_DATA = ("parameter", "get-tuple-element", "tuple", "bitcast")


def pool_sized_instructions(text, pools):
    """Instructions of a compiled program's text whose result holds as many
    elements as one of ``pools`` (shapes) or as one layer of it, Mosaic
    calls left out: a copy, slice, update, concatenation, transpose or
    fusion over the stacked pool.  A program that serves from the pool in
    place has none: only its kernels touch the pool, a page at a time."""
    sizes = {int(np.prod(shape[at:])) for shape in pools for at in (0, 1)}
    found = []
    for line in text.splitlines():
        m = _HLO_RESULT.match(line)
        if m is None or m.group(2) in _HLO_NO_DATA \
                or MOSAIC_CALL in line:
            continue
        for dims in _HLO_ARRAY.findall(m.group(1)):
            if int(np.prod([int(d) for d in dims.split(",") if d])) in sizes:
                found.append(line.strip()[:160])
                break
    return found


def compiled_pool_facts(jitted, args, pools):
    """Compile ``jitted`` for ``args`` as it is dispatched (committed
    arrays keep their sharding; nothing runs, nothing is donated) and
    return what it does to ``pools`` outside its kernels: the pool-sized
    instructions of its text, and its temporary bytes."""
    import jax

    def spec(a):
        if not (hasattr(a, "shape") and hasattr(a, "dtype")):
            return a
        placed = a.sharding if getattr(a, "committed", False) else None
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=placed)

    compiled = jitted.lower(*jax.tree_util.tree_map(spec, args)).compile()
    shapes = [p.addressable_shards[0].data.shape if hasattr(
        p, "addressable_shards") else p.shape for p in pools]
    return {"pool_sized": pool_sized_instructions(compiled.as_text(), shapes),
            "temp_bytes": int(compiled.memory_analysis().temp_size_in_bytes)}


def _expect_pools_in_place(engine, chunk):
    """What the real compiler's output for the engine's programs does to
    the pools outside the kernels, printed per program: instructions over a
    whole pool or a whole layer of it, and temporary bytes.  Where the
    device's own layout of every pool is row-major, the kernels', there
    must be none, and no temporaries of a pool's size (at this engine's
    small pools the activations' are a quarter of one).  Elsewhere — heads
    that do not fill the device's sublane tiles (a shard of an mp engine),
    the int8 engine's 16-lane scale pools — XLA converts a pool on the way
    in and out, and the print says how often."""
    # a per-slot state that rides in the tuple (``state.slots``) is no
    # page pool: a step scatters its slots' rows, a few KB a slot
    pools = [engine._pools[i] for owner, idx in engine._adapter.pool_owners()
             if owner != "state.slots" for i in idx]
    shard_bytes = pools[0].addressable_shards[0].data.nbytes
    row_major = all(
        p.format.layout.major_to_minor == tuple(range(p.ndim)) for p in pools)
    out = {"pools_row_major": row_major}
    for name, (prog, args) in _engine_programs(engine, chunk).items():
        facts = compiled_pool_facts(prog, args, pools)
        log(f"  {name}: {len(facts['pool_sized'])} pool-sized instructions "
            f"outside the kernels, {facts['temp_bytes']} temporary bytes "
            f"(one pool: {shard_bytes}; pools row-major: {row_major})")
        if row_major and (facts["pool_sized"]
                          or facts["temp_bytes"] >= shard_bytes):
            raise AssertionError(
                f"serving {name} program moves the pool: {facts}")
        out[name] = {"pool_sized": len(facts["pool_sized"]),
                     "temp_bytes": facts["temp_bytes"]}
    return out


def _counter(name, **labels):
    from paddle_tpu.profiler import metrics

    m = metrics.get_registry().get(name)
    return 0 if m is None else (m.get(**labels) or 0)


def phase_serve(cfg, kv_dtype, bf16=True, mesh=None, replica=None,
                build=None):
    """One engine: a cold pass (compiles inside), then the same requests
    again (steady: no new trace).  Returns (facts, generated ids).
    ``build()`` gives ``(model, layers with a paged cache, vocabulary)`` of
    another family than GPT."""
    import paddle_tpu as paddle
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(SEED)
    if build is None:
        m = GPTForCausalLM(**cfg["model"]).eval()
        if bf16:
            m = m.bfloat16()
        layers = len(m.gpt.layers)
        vocab = m.gpt.word_embeddings.weight.shape[0]
    else:
        m, layers, vocab = build()
    replica = replica or f"smoke-{kv_dtype or 'native'}"
    engine = ServingEngine(m, num_slots=cfg["num_slots"],
                           page_size=cfg["page_size"], kv_dtype=kv_dtype,
                           max_model_len=cfg.get("max_model_len"),
                           prefill_chunk_tokens=cfg["chunk"],
                           numeric_guard=True, mesh=mesh, replica=replica)
    prompts = _prompts(cfg, vocab)
    with engine:
        t0 = time.time()
        ids = _serve_pass(engine, prompts)
        t1 = time.time()
        traces = engine.program_traces()
        ids2 = _serve_pass(engine, prompts)
        t2 = time.time()
        if engine.program_traces() != traces:
            raise AssertionError("the steady pass traced a new program")
        if ids2 != ids:
            raise AssertionError("greedy output changed between passes")
        if engine.step_traces != 1:
            raise AssertionError(f"step_traces == {engine.step_traces}")
        restarts = _counter("serving.engine_restarts", replica=replica)
        faults = _counter("serving.numeric_faults", replica=replica)
        if restarts or faults:
            raise AssertionError(f"{restarts} engine restarts, {faults} "
                                 "numeric faults")
        mosaic = _expect_engine_mosaic(
            engine, cfg["chunk"], layers,
            cfg.get("chunk_grouped_kernels", 0))
        sweep = _decode_sweep(engine)
        log(f"  decode sweep: {sweep}")
        in_place = _expect_pools_in_place(engine, cfg["chunk"])
        pool_devices = distinct_devices(engine._pools[0])
        param_devices = max(distinct_devices(v)
                            for v in engine._params.values())
    facts = {"requests": len(prompts), "tokens": sum(n for _, n in prompts),
             "pool_dtype": engine.stats()["pool_dtype"],
             "cold_pass_s": round(t1 - t0, 1),
             "steady_pass_s": round(t2 - t1, 2), "programs": traces,
             "step_traces": 1, "engine_restarts": 0, "numeric_faults": 0,
             "mosaic_calls": mosaic, "decode_sweep": sweep,
             "programs_outside_kernels": in_place,
             "pool_devices": pool_devices, "param_devices": param_devices}
    return facts, ids


def _check_grouped_lanes(cfg, page_size, table_pages, out):
    """The decode, chunk and write kernels over 16-bit pools whose rows are
    whole lanes behind a narrower head (``d`` 64 stored 128 wide, as the
    engine holds them), grouped query heads, a table ``table_pages`` wide:
    each against the dense reference over the pool's real lanes."""
    import importlib

    import jax
    import jax.numpy as jnp

    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    rs = np.random.RandomState(SEED)
    (H, HKV, D), B, C = cfg["heads"], cfg["rows"], cfg["chunk"]
    ps, NP = page_size, table_pages
    P, lanes = B * NP + 1, pa.pool_lane_dim(D)
    kf, vf = (jnp.asarray(rs.randn(P, ps, HKV, D), jnp.bfloat16)
              for _ in range(2))
    table = jnp.asarray(rs.permutation(P - 1)[:B * NP].reshape(B, NP),
                        jnp.int32)
    lens = rs.randint(1, NP * ps + 1, (B,))
    edges = [1, ps, NP * ps, 128, 129, NP * ps + 37, NP * ps // 2]
    lens[:len(edges)] = edges[:B]
    lens = jnp.asarray(lens, jnp.int32)
    tag = f"H{H}_HKV{HKV}_D{D}in{lanes}_NP{NP}"

    def stacked(entry, *tail):
        # layer 1 of two, rows padded to whole lanes as the engine's are
        return jax.jit(lambda x, *pools: entry(
            x, *(jnp.stack([jnp.zeros_like(p), p])
                 for p in (pa._to_lanes(p, lanes) for p in pools)),
            *tail, layer=1))

    q = jnp.asarray(rs.randn(B, H, D), jnp.bfloat16)
    _check(f"paged_flash/{tag}", stacked(pa.paged_attention, table, lens),
           lambda q, kp, vp: pa.paged_attention_ref(q, kp, vp, table, lens),
           (q, kf, vf), 1, out)
    qc = jnp.asarray(rs.randn(B, C, H, D), jnp.bfloat16)
    base = jnp.asarray(rs.randint(0, NP * ps - C, (B,)), jnp.int32)

    def chunk_ref(qc, kp, vp, base):
        lens2 = base[:, None] + 1 + jnp.arange(C, dtype=jnp.int32)[None]
        table2 = jnp.broadcast_to(table[:, None], (B, C, NP))
        return pa.paged_attention_ref(
            qc.reshape(B * C, H, D), kp, vp, table2.reshape(B * C, NP),
            lens2.reshape(-1)).reshape(B, C, H, D)

    _check(f"paged_chunk/{tag}",
           jax.jit(lambda qc, kp, vp, base: stacked(
               pa.paged_chunk_attend, table, base)(qc, kp, vp)),
           chunk_ref, (qc, kf, vf, base), 1, out)
    kn, vn = (jnp.asarray(rs.randn(B, C, HKV, D), jnp.bfloat16)
              for _ in range(2))
    pools = tuple(jnp.stack([p, p]) for p in (pa._to_lanes(kf, lanes),
                                              pa._to_lanes(vf, lanes)))
    for width in (1, C):
        _check(f"paged_write/C{width}_{tag}",
               jax.jit(lambda kn, vn, *pl: pa.paged_pool_write(
                   pl, kn[:, :width], vn[:, :width], table, base, 1)),
               lambda kn, vn, *pl: tuple(
                   pa.paged_table_chunk_write(p, x, table, base, 1)
                   for p, x in zip(pl, pa._pool_rows(
                       pl, kn[:, :width], vn[:, :width]))),
               (kn, vn, *pools), 1, out)


def _check_grouped_product(sizes, out):
    """The grouped product through its seam at a chunk's rows (the shapes
    choose the repo's kernel there) against a per-group loop in float32:
    ragged groups, empty ones among them, and rows past the last group,
    which the kernel zeroes."""
    import importlib

    import jax
    import jax.numpy as jnp

    gm = importlib.import_module("paddle_tpu.ops.grouped_matmul")
    rs = np.random.RandomState(SEED)
    M, K, N, G = sizes
    counts = rs.multinomial(M - M // 8, rs.dirichlet(np.full(G, 0.5)))
    x = jnp.asarray(rs.randn(M, K), jnp.bfloat16)
    w = jnp.asarray(rs.randn(G, K, N) * 0.02, jnp.bfloat16)
    ends = np.cumsum(counts)

    def loop(x, w, counts):
        x, rows = x.astype(jnp.float32), jnp.arange(M)[:, None]
        y = jnp.zeros((M, N), jnp.float32)
        for g in range(G):
            mine = (rows >= ends[g] - counts[g]) & (rows < ends[g])
            y = y + jnp.where(mine, x, 0.0) @ w[g].astype(jnp.float32)
        return y

    _check(f"grouped_matmul/M{M}_K{K}_N{N}_G{G}", jax.jit(gm.grouped_matmul),
           loop, (x, w, jnp.asarray(counts, jnp.int32)), 1, out)


def _greedy_gap(model, prompt, tokens):
    """How far below the model's own best logit the served tokens lie, over
    the spread of the logits, in the model's dense forward (no cache) of
    prompt + tokens: a lost state or a wrong page moves it to order one."""
    import jax.numpy as jnp

    import paddle_tpu as paddle

    ids = np.concatenate([prompt, np.asarray(tokens, np.int64)])
    logits = np.asarray(model(paddle.to_tensor(ids[None]))._value.astype(
        jnp.float32))[0, len(prompt) - 1:len(ids) - 1]
    picked = logits[np.arange(len(tokens)), np.asarray(tokens)]
    return float(np.max((logits.max(-1) - picked)
                        / (logits.max(-1) - logits.mean(-1))))


def phase_serve_lfm2(cfg):
    """A small hybrid of the LFM2-MoE family through the engine's normal
    path (per-slot state beside the pages of its one attention layer), and
    the paged kernels at its heads over tables as wide as its context."""
    from paddle_tpu.text.models import Lfm2MoeForCausalLM

    t0 = time.time()
    kernels = {}
    _check_grouped_lanes(cfg["kernels"], cfg["page_size"],
                         -(-cfg["max_model_len"] // cfg["page_size"]),
                         kernels)
    _check_grouped_product(cfg["kernels"]["grouped"], kernels)
    built = []

    def build():
        m = Lfm2MoeForCausalLM(**cfg["model"]).eval()
        built.append(m)
        return m, m.model.num_attention_layers, m.config.vocab_size

    facts, ids = phase_serve(cfg, None, replica="smoke-lfm2", build=build)
    prompts = _prompts(cfg, built[0].config.vocab_size)
    gaps = [_greedy_gap(built[0], p, out)
            for (p, _), out in list(zip(prompts, ids))[:3]]
    log(f"  served tokens below the dense forward's best by {gaps} of the "
        "logits' spread")
    if max(gaps) > 0.1:
        raise AssertionError(f"served tokens are not the model's: {gaps}")
    return dict(facts, kernels=kernels, greedy_gap=round(max(gaps), 5),
                seconds=round(time.time() - t0, 1))


# ---------------------------------------------------------------- four chips
def phase_multichip(cfg, resnet_cfg, serve_cfg):
    import jax
    import jax.numpy as jnp

    n = cfg["chips"]
    if jax.device_count() < n:
        return f"skipped: {jax.device_count()} device"
    import importlib

    import paddle_tpu.distributed as dist
    import paddle_tpu.distributed.fleet as fleet
    from benchmarks import micro
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    ring = importlib.import_module("paddle_tpu.ops.ring_attention")
    devs = jax.devices()[:n]
    out = {}

    # tensor-parallel serving first (before fleet installs its global mesh):
    # greedy output of the mp engine must equal the one-chip engine's
    t0 = time.time()
    one, ids1 = phase_serve(serve_cfg, None, bf16=False, replica="smoke-mp1")
    mp, ids_mp = phase_serve(serve_cfg, None, bf16=False, mesh=devs,
                             replica=f"smoke-mp{n}")
    if mp["pool_devices"] != n or mp["param_devices"] != n:
        raise AssertionError(f"mp engine: pools on {mp['pool_devices']} "
                             f"devices, params on {mp['param_devices']}")
    if ids_mp != ids1:
        raise AssertionError("mp greedy output differs from mp=1")
    out["serve_mp"] = {"mp": n, "greedy_equal_mp1": True,
                       "pool_devices": n, "param_devices": n,
                       "mosaic_calls": mp["mosaic_calls"],
                       "seconds": round(time.time() - t0, 1)}
    log(f"  serve_mp: {out['serve_mp']}")

    # ring attention over a 4-way sequence mesh against the dense reference
    t0 = time.time()
    b, s, h, d = cfg["ring"]
    mesh = Mesh(np.asarray(devs), ("sep",))
    rs = np.random.RandomState(SEED)
    sh = NamedSharding(mesh, P(None, "sep"))
    q, k, v = (jax.device_put(jnp.asarray(rs.randn(b, s, h, d), jnp.bfloat16),
                              sh) for _ in range(3))
    fn = jax.jit(lambda q, k, v: ring.ring_attention_fn(
        q, k, v, mesh, axis="sep", causal=True))
    got = jax.block_until_ready(fn(q, k, v))
    with jax.default_matmul_precision("highest"):
        want = fa._ref_attention(
            *(jnp.moveaxis(x, 2, 1).reshape(b * h, s, d).astype(jnp.float32)
              for x in (q, k, v)), d ** -0.5, True)
    want = jnp.moveaxis(want.reshape(b, h, s, d), 1, 2)
    err = rel_err(got, want)
    if err > KERNEL_TOL or distinct_devices(got) != n:
        raise AssertionError(f"ring attention: rel_err {err:.3e}, output on "
                             f"{distinct_devices(got)} devices")
    # per device: one flash call for the diagonal block, one for past blocks
    # in each of the n ring steps' lax.switch
    out["ring_attention"] = {
        "rel_err": round(err, 6), "devices": n,
        "mosaic_calls": expect_mosaic("ring attention", fn, (q, k, v), 2 * n),
        "seconds": round(time.time() - t0, 1)}
    log(f"  ring_attention: {out['ring_attention']}")

    bw, n_ar = micro.allreduce_bus_bw(mb=cfg["allreduce_mb"], devices=devs)
    if n_ar != n or not (bw and np.isfinite(bw) and bw > 0):
        raise AssertionError(f"allreduce probe returned {bw} over {n_ar}")
    out["allreduce"] = {"devices": n_ar, "bus_gbs": round(bw, 1)}
    log(f"  allreduce: {out['allreduce']}")

    # Fleet data-parallel ResNet-50 over every chip
    dist.init_parallel_env()
    fleet.init(is_collective=True)
    net, model, step = _resnet_step(resnet_cfg, dp=True)
    x, y = _resnet_batch(resnet_cfg)
    model.shard_input(x)
    model.shard_input(y)
    facts = _train(step, (x, y), resnet_cfg["steps"])
    placed = {"batch": distinct_devices(x._value),
              "params": min(distinct_devices(p._value)
                            for p in net.parameters())}
    if set(placed.values()) != {jax.device_count()}:
        raise AssertionError(f"data-parallel placement: {placed}")
    facts.update(devices=placed)
    out["resnet_dp"] = facts
    log(f"  resnet_dp: {facts}")
    return out


# --------------------------------------------------------------------- main
def result_line(device):
    """The last line of stdout, printed only when every phase passed: the
    keys the driver's check expects and no others (the rest is the report
    line before it)."""
    return json.dumps({"ok": True, "device": {
        k: device[k] for k in ("platform", "kind", "count")}})


PHASES = {
    "kernels": lambda c: phase_kernels(c["kernels"]),
    "train_resnet": lambda c: phase_train_resnet(c["resnet"]),
    "train_gpt": lambda c: phase_train_gpt(c["gpt"]),
    "serve_bf16": lambda c: phase_serve(c["serve"], None)[0],
    "serve_int8": lambda c: phase_serve(c["serve"], "int8")[0],
    "serve_lfm2": lambda c: phase_serve_lfm2(c["lfm2"]),
    "multichip": lambda c: phase_multichip(c["multichip"], c["resnet"],
                                           c["serve"]),
}


def run_phase(name, cfg):
    return PHASES[name](cfg)


def main(argv):
    import jax

    unknown = [name for name in argv if name not in PHASES]
    if unknown:
        raise SystemExit(f"chip_smoke: unknown phase(s) {unknown}; "
                         f"phases are {', '.join(PHASES)}")
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU; jax.default_backend() is "
            f"{jax.default_backend()!r} — nothing was run")
    import jaxlib

    import paddle_tpu as paddle
    from paddle_tpu.io import native
    from paddle_tpu.observability import perf

    t_start = time.time()
    cache = CacheCounter()
    paddle.set_device("tpu")
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices())}
    from benchmarks import micro

    if None in (perf.peak_flops(), perf.hbm_ceiling(),
                micro.device_peak_flops()[1]):
        raise SystemExit(f"chip_smoke: device_kind {d0.device_kind!r} is "
                         "missing from a peak table (observability.perf, "
                         "benchmarks.micro)")
    from importlib import metadata

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    versions = {"python": sys.version.split()[0], "jax": jax.__version__,
                "jaxlib": jaxlib.__version__, "libtpu": libtpu}
    cache_dir = jax.config.jax_compilation_cache_dir
    log(f"device {device}")
    log(f"versions {versions}")
    log(f"compile cache {cache_dir}")
    log(f"native input kernels available: {native.available()}")

    phases = {}
    for name in (argv or PHASES):
        log(f"phase {name} ...")
        t0 = time.time()
        phases[name] = run_phase(name, FULL)
        gc.collect()    # drop the phase's model and pools before the next
        log(f"phase {name} done in {time.time() - t0:.1f}s: "
            f"{json.dumps(phases[name])}")

    log("report " + json.dumps({
        "device": device, "versions": versions,
        "phases": phases, "native_input_kernels": native.available(),
        "compile_cache": {"dir": cache_dir, "hits": cache.hits,
                          "misses": cache.misses},
        "wall_s": round(time.time() - t_start, 1), "claim": None,
    }, separators=(",", ":")))
    print(result_line(device), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
