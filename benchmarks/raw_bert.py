"""Hand-written raw-JAX BERT-base fine-tune step — bench.py's transformer
calibration baseline (BASELINE.md config #2: ERNIE-3.0-base / BERT via
to_static).

Same philosophy as raw_resnet50.py: this is the program a JAX expert would
hand-write for the exact job the framework runs — BERT-base encoder
(L=12, H=768, heads=12, FFN=3072), sequence classification on the [CLS]
pooler, bf16 compute with f32 master params, AdamW with bias-correction,
everything in ONE donated jit.  Measured in the same process/run as the
framework step so `vs_baseline` compares identical hardware and method.
"""

import functools

import numpy as np
import jax
import jax.numpy as jnp

L, H, HEADS, FFN, VOCAB, TYPES, MAXPOS = 12, 768, 12, 3072, 30522, 2, 512
DH = H // HEADS


def train_flops_per_token(seq_len):
    """Analytic train-step FLOPs/token (fwd×3), matmuls only.

    Per layer fwd: QKVO projections 4·2·H² + FFN 2·2·H·FFN, plus attention
    score/value matmuls 2·2·T·H per token.  Embedding lookups and norms are
    bandwidth, not FLOPs.  bwd ≈ 2× fwd.
    """
    per_layer = 8 * H * H + 4 * H * FFN + 4 * seq_len * H
    return 3 * (L * per_layer + 2 * H * H)  # + pooler


def build_params(key):
    keys = iter(jax.random.split(key, 32 + 16 * L))

    def dense(cin, cout):
        return (jax.random.normal(next(keys), (cin, cout), jnp.float32)
                * np.float32(0.02), jnp.zeros(cout, jnp.float32))

    def ln():
        return jnp.ones(H, jnp.float32), jnp.zeros(H, jnp.float32)

    p = {
        "tok": jax.random.normal(next(keys), (VOCAB, H), jnp.float32) * 0.02,
        "pos": jax.random.normal(next(keys), (MAXPOS, H), jnp.float32) * 0.02,
        "typ": jax.random.normal(next(keys), (TYPES, H), jnp.float32) * 0.02,
        "emb_ln": ln(),
        "layers": [{
            "qkv": dense(H, 3 * H),
            "out": dense(H, H),
            "ln1": ln(),
            "fc1": dense(H, FFN),
            "fc2": dense(FFN, H),
            "ln2": ln(),
        } for _ in range(L)],
        "pool": dense(H, H),
        "cls": dense(H, 2),
    }
    return p


DROPOUT = 0.1  # the reference fine-tune config trains WITH dropout — the
# expert baseline must do the same job (hidden + attention-prob dropout)


def _ln(x, g, b):
    m = jnp.mean(x, axis=-1, keepdims=True)
    v = jnp.var(x, axis=-1, keepdims=True)
    return ((x - m) * jax.lax.rsqrt(v + 1e-12)) * g + b


def _dropout(x, key):
    keep = jax.random.bernoulli(key, 1.0 - DROPOUT, x.shape)
    # python-float scale: weak-typed, keeps bf16 bf16 (a np.float32 scalar
    # would silently promote the whole mask-multiply to f32)
    return jnp.where(keep, x / (1.0 - DROPOUT), 0.0).astype(x.dtype)


def forward(p, ids, type_ids, key):
    B, T = ids.shape
    keys = jax.random.split(key, 1 + 3 * L)
    ki = iter(range(len(keys)))
    # additive padding mask, [B,1,1,T] — part of the BERT job (the
    # framework computes it from input_ids; the baseline must too)
    mask = ((ids == 0).astype(jnp.float32) * -1e4)[:, None, None, :]
    x = p["tok"][ids] + p["pos"][jnp.arange(T)][None] + p["typ"][type_ids]
    x = _dropout(_ln(x, *p["emb_ln"]), keys[next(ki)]).astype(jnp.bfloat16)
    for lyr in p["layers"]:
        w, b = lyr["qkv"]
        qkv = x @ w.astype(jnp.bfloat16) + b.astype(jnp.bfloat16)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(t):
            return t.reshape(B, T, HEADS, DH).transpose(0, 2, 1, 3)

        q, k, v = heads(q), heads(k), heads(v)
        s = (q @ k.transpose(0, 1, 3, 2)) * np.float32(1.0 / np.sqrt(DH))
        a = jax.nn.softmax(s.astype(jnp.float32) + mask, axis=-1).astype(jnp.bfloat16)
        a = _dropout(a, keys[next(ki)])
        o = (a @ v).transpose(0, 2, 1, 3).reshape(B, T, H)
        w, b = lyr["out"]
        o = _dropout(o @ w.astype(jnp.bfloat16) + b.astype(jnp.bfloat16),
                     keys[next(ki)])
        x = _ln((x + o).astype(jnp.float32), *lyr["ln1"]).astype(jnp.bfloat16)
        w, b = lyr["fc1"]
        h = jax.nn.gelu(x @ w.astype(jnp.bfloat16) + b.astype(jnp.bfloat16),
                        approximate=False)
        w, b = lyr["fc2"]
        h = _dropout(h @ w.astype(jnp.bfloat16) + b.astype(jnp.bfloat16),
                     keys[next(ki)])
        x = _ln((x + h).astype(jnp.float32), *lyr["ln2"]).astype(jnp.bfloat16)
    w, b = p["pool"]
    pooled = jnp.tanh(x[:, 0].astype(jnp.float32) @ w + b)
    w, b = p["cls"]
    return pooled @ w + b


def loss_fn(p, ids, type_ids, y, key):
    logits = forward(p, ids, type_ids, key)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return (lse - jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]).mean()


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def train_step(p, m, v, t, ids, type_ids, y, key):
    # per-step dropout keys derive from the TRACED step counter: the host
    # passes one constant base key (an eager fold_in per step would add a
    # serializing host dispatch)
    key = jax.random.fold_in(key, t)
    loss, g = jax.value_and_grad(loss_fn)(p, ids, type_ids, y, key)
    t = t + 1
    b1, b2, lr, eps, wd = 0.9, 0.999, 2e-5, 1e-8, 0.01

    def adamw(pp, mm, vv, gg):
        mm = b1 * mm + (1 - b1) * gg
        vv = b2 * vv + (1 - b2) * gg * gg
        mhat = mm / (1 - b1 ** t)
        vhat = vv / (1 - b2 ** t)
        pp = pp - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * pp)
        return pp, mm, vv

    flat_p, td = jax.tree_util.tree_flatten(p)
    flat_m = jax.tree_util.tree_flatten(m)[0]
    flat_v = jax.tree_util.tree_flatten(v)[0]
    flat_g = jax.tree_util.tree_flatten(g)[0]
    out = [adamw(pp, mm, vv, gg)
           for pp, mm, vv, gg in zip(flat_p, flat_m, flat_v, flat_g)]
    new_p = jax.tree_util.tree_unflatten(td, [o[0] for o in out])
    new_m = jax.tree_util.tree_unflatten(td, [o[1] for o in out])
    new_v = jax.tree_util.tree_unflatten(td, [o[2] for o in out])
    return loss, new_p, new_m, new_v, t


def measure(batch_size=64, seq_len=128, iters=15, cost=False):
    """samples/sec of the raw fine-tune step (same timing as bench.py)."""
    import time

    p = build_params(jax.random.key(0))
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    t = jnp.zeros((), jnp.int32)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, VOCAB, (batch_size, seq_len)).astype("int32"))
    typ = jnp.zeros((batch_size, seq_len), jnp.int32)
    y = jnp.asarray(rs.randint(0, 2, (batch_size,)).astype("int32"))
    key = jax.random.key(0)
    comp = train_step.lower(p, m, v, t, ids, typ, y, key).compile() if cost else None
    loss, p, m, v, t = train_step(p, m, v, t, ids, typ, y, key)
    float(loss)
    t0 = time.time()
    for _ in range(iters):
        loss, p, m, v, t = train_step(p, m, v, t, ids, typ, y, key)
    float(loss)
    dt = (time.time() - t0) / iters
    ips = batch_size / dt
    if not cost:
        return ips
    from benchmarks.micro import cost_fields

    return ips, cost_fields(comp)
