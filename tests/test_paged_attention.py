"""Paged attention kernels and the cache seam the models call (SURVEY.md
§2.1 inference engine row adjacency: the serving-side decode attention
primitive)."""

import math

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops.paged_attention import (
    paged_attention, paged_attention_ref, _paged_flash_pallas,
)


def _setup(B=3, H=4, D=16, page=8, np_pages=4, seed=0):
    rs = np.random.RandomState(seed)
    total = B * np_pages
    q = jnp.asarray(rs.randn(B, H, D).astype("float32") * 0.5)
    k_pages = jnp.asarray(rs.randn(total, page, H, D).astype("float32") * 0.5)
    v_pages = jnp.asarray(rs.randn(total, page, H, D).astype("float32") * 0.5)
    table = jnp.asarray(
        rs.permutation(total).reshape(B, np_pages).astype("int32"))
    lens = jnp.asarray(np.array([5, 17, 32 - 1], "int32")[:B])
    return q, k_pages, v_pages, table, lens


def _dense_oracle(q, k_pages, v_pages, table, lens):
    """Independent numpy oracle (not the module's own ref)."""
    B, H, D = q.shape
    page = k_pages.shape[1]
    out = np.zeros((B, H, D), "float32")
    for b in range(B):
        ks = np.concatenate([np.asarray(k_pages[p]) for p in np.asarray(table[b])], 0)
        vs = np.concatenate([np.asarray(v_pages[p]) for p in np.asarray(table[b])], 0)
        L = int(lens[b])
        for h in range(H):
            s = ks[:L, h] @ np.asarray(q[b, h]) / math.sqrt(D)
            p_ = np.exp(s - s.max())
            p_ /= p_.sum()
            out[b, h] = p_ @ vs[:L, h]
    return out


def test_ref_matches_dense_oracle():
    q, kp, vp, table, lens = _setup()
    got = np.asarray(paged_attention_ref(q, kp, vp, table, lens))
    want = _dense_oracle(q, kp, vp, table, lens)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("page,np_pages", [(8, 4), (16, 9), (32, 5)])
def test_pallas_kernel_matches_ref_interpret(page, np_pages):
    q, kp, vp, table, lens = _setup(page=page, np_pages=np_pages)
    # the length-bounded kernel takes stacked pools and a layer: layer 1
    # of two, the other poisoned
    kp2, vp2 = (jnp.stack([jnp.full_like(p, 9.0), p]) for p in (kp, vp))
    got = np.asarray(_paged_flash_pallas(q, kp2, vp2, table, lens,
                                         1.0 / math.sqrt(q.shape[-1]),
                                         True, 1))
    want = np.asarray(paged_attention_ref(q, kp, vp, table, lens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_public_entry_dispatches_and_jits():
    q, kp, vp, table, lens = _setup(seed=1)
    f = jax.jit(lambda *a: paged_attention(*a))
    got = np.asarray(f(q, kp, vp, table, lens))
    want = np.asarray(paged_attention_ref(q, kp, vp, table, lens))
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_padded_pages_are_masked():
    # identical prefixes, different padding in the tail pages -> same output
    q, kp, vp, table, lens = _setup(seed=3)
    vp2 = vp.at[np.asarray(table[0, -1])].set(999.0)  # poison a padded page
    a = np.asarray(paged_attention_ref(q, kp, vp, table, lens))
    b = np.asarray(paged_attention_ref(q, kp, vp2, table, lens))
    np.testing.assert_allclose(a[0], b[0], rtol=1e-6)


# ------------------------------------------------- serving-loop integration


def test_gpt_generate_paged_matches_dense():
    """generate(cache_impl='paged') produces IDENTICAL tokens to the dense
    static-cache decode (greedy), including prompts that straddle page
    boundaries (r4 missing #2: the kernel existed but nothing decoded
    through it)."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(0)
    m = GPTForCausalLM(vocab_size=160, hidden_size=64, num_hidden_layers=2,
                       num_attention_heads=4, max_position_embeddings=128)
    rs = np.random.RandomState(42)
    for s0 in (3, 8):  # below / at a page_size=8 boundary
        ids = paddle.to_tensor(rs.randint(0, 160, (2, s0)).astype("int64"))
        dense = m.generate(ids, max_new_tokens=18, temperature=0.0)
        paged = m.generate(ids, max_new_tokens=18, temperature=0.0,
                           cache_impl="paged", page_size=8)
        np.testing.assert_array_equal(dense.numpy(), paged.numpy())


def test_llama_generate_paged_matches_dense_gqa():
    """Llama GQA: paged pools stay at hkv heads; grouped attention against
    the pools matches the dense repeated-KV decode token-for-token."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models import LlamaForCausalLM

    paddle.seed(1)
    m = LlamaForCausalLM(vocab_size=160, hidden_size=64, num_hidden_layers=2,
                         num_attention_heads=4, num_key_value_heads=2,
                         intermediate_size=128, max_position_embeddings=128)
    rs = np.random.RandomState(7)
    ids = paddle.to_tensor(rs.randint(0, 160, (2, 5)).astype("int64"))
    dense = m.generate(ids, max_new_tokens=16, temperature=0.0)
    paged = m.generate(ids, max_new_tokens=16, temperature=0.0,
                       cache_impl="paged", page_size=4)
    np.testing.assert_array_equal(dense.numpy(), paged.numpy())


def test_paged_pool_hbm_bound_by_pages():
    """The paged cache allocates ceil(T/ps) pages — for short decodes with
    a large model max length, orders less HBM than the dense rectangle."""
    from paddle_tpu.text.models._decode import paged_pool_shape

    B, hkv, hd, ps = 4, 8, 64, 16
    T_actual = 96
    shape = paged_pool_shape(B, T_actual, hkv, hd, ps)
    paged_elems = int(np.prod(shape))
    dense_max_len = 2048  # a server sized for the model's max context
    dense_elems = B * dense_max_len * hkv * hd
    assert paged_elems == B * 6 * ps * hkv * hd
    assert paged_elems * 20 < dense_elems


# ------------------------------------------ one contract, no pool copies
def _whole_pool_ops(jaxpr, pool_shape):
    """Names of the slice / concatenate / dynamic_update_slice equations,
    at any depth of ``jaxpr``, that read or produce a whole pool (or the
    pool less its layer dim): what slicing a layer out of the stacked pool
    and stacking layers back leaves in a traced program."""
    sizes = {int(np.prod(pool_shape)), int(np.prod(pool_shape[1:]))}
    found = []

    def walk(j):
        for e in j.eqns:
            if e.primitive.name in ("slice", "dynamic_slice", "concatenate",
                                    "dynamic_update_slice"):
                avals = [v.aval for v in (*e.invars, *e.outvars)
                         if hasattr(v, "aval")]
                if any(int(np.prod(a.shape)) in sizes for a in avals
                       if getattr(a, "shape", None)):
                    found.append(e.primitive.name)
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr)
    return found


def _tiny_lm(family):
    import paddle_tpu as paddle
    from paddle_tpu.text.models import GPTForCausalLM, LlamaForCausalLM

    paddle.seed(0)
    kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=3,
              num_attention_heads=4, max_position_embeddings=64)
    if family == "gpt":
        return GPTForCausalLM(**kw)
    return LlamaForCausalLM(num_key_value_heads=2, intermediate_size=64, **kw)


@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_generate_paged_step_holds_no_whole_pool_copy(family):
    """generate(cache_impl="paged") threads ONE stacked pool tuple through
    the layers on the served contract: its traced step neither slices a
    layer out of a pool nor stacks layers back nor updates a whole pool."""
    import paddle_tpu as paddle
    from paddle_tpu.text.models._decode import program_store

    m = _tiny_lm(family)
    ids = paddle.to_tensor(np.arange(10, dtype="int64").reshape(2, 5))
    m.generate(ids, max_new_tokens=3, temperature=0.0, cache_impl="paged",
               page_size=4)
    (key, (_, step)), = [kv for kv in program_store(m).items()
                         if kv[0][0] == "paged"]
    params = {k: p._value for k, p in m.named_parameters()}
    bufs = {k: b._value for k, b in m.named_buffers()}
    L, B, PP = 3, 2, 2                      # T = 8 tokens: 2 pages of 4
    hkv = 4 if family == "gpt" else 2
    pool = jnp.zeros((L, B * PP, 4, hkv, 8), jnp.float32)
    jaxpr = jax.make_jaxpr(step)(
        params, bufs, jnp.zeros((B, 1), jnp.int64), (pool, pool),
        np.int32(5), jax.random.key(0)).jaxpr
    assert _whole_pool_ops(jaxpr, pool.shape) == []
    # and the pool really is that one: every layer in it, written in place
    text = str(jaxpr)
    assert "scatter" in text and f"f32[{L},{B * PP},4,{hkv},8]" in text


@pytest.mark.parametrize("chunk", [1, 4])
def test_llama_layer_on_served_contract_matches_dense(chunk):
    """LlamaModel on the serving engine's cache, every slot at its OWN
    length (GQA, rotary positions per slot): a decode token (chunk 1) and
    a chunk of 4 give the hidden states the dense forward gives at those
    positions, through a scrambled page table."""
    import paddle_tpu as paddle
    from paddle_tpu.tensor.tensor import Tensor

    m = _tiny_lm("llama").eval()
    llama = m.llama
    rs = np.random.RandomState(3)
    B, ps, NP, L, hkv, hd = 3, 4, 5, 3, 2, 8
    lens = np.array([2, 9, 5], "int32")          # tokens already cached
    ids = rs.randint(0, 96, (B, int(lens.max()) + chunk)).astype("int64")
    dense = [llama(Tensor(jnp.asarray(ids[b:b + 1, :lens[b] + chunk])))
             .numpy()[0, lens[b]:] for b in range(B)]

    table = rs.permutation(B * NP).reshape(B, NP).astype("int32")
    pools = tuple(Tensor(jnp.zeros((L, B * NP + 1, ps, hkv, hd), jnp.float32))
                  for _ in range(2))
    # the cached prefix of each slot: right-padded prompts at position 0
    S0 = int(lens.max())
    prompt = np.where(np.arange(S0)[None, :] < lens[:, None], ids[:, :S0], 0)
    _, pools = llama(Tensor(jnp.asarray(prompt)),
                     cache=("served", pools, Tensor(jnp.asarray(table)),
                            Tensor(jnp.asarray(lens))))
    new = np.stack([ids[b, lens[b]:lens[b] + chunk] for b in range(B)])
    pos = lens[:, None] + np.arange(chunk, dtype="int32")[None, :]
    tag = "served" if chunk == 1 else "served_chunk"
    got, _ = llama(Tensor(jnp.asarray(new)),
                   position_ids=Tensor(jnp.asarray(pos)),
                   cache=(tag, pools, Tensor(jnp.asarray(table)),
                          Tensor(jnp.asarray(lens))))
    for b in range(B):
        np.testing.assert_allclose(got.numpy()[b], dense[b], rtol=2e-4,
                                   atol=2e-4)


def test_cache_seam_refuses_a_tag_it_does_not_know():
    """The per-sequence ``"paged"`` cache tuple is gone: the seam serves
    ``"served"`` and ``"served_chunk"`` and says so of anything else."""
    from paddle_tpu.ops.paged_attention import paged_cache_attend
    from paddle_tpu.tensor.tensor import Tensor

    x = Tensor(jnp.zeros((1, 1, 2, 8), jnp.float32))
    pool = Tensor(jnp.zeros((1, 2, 4, 2, 8), jnp.float32))
    cache = ("paged", 0, (pool, pool), Tensor(jnp.zeros((1, 2), jnp.int32)),
             Tensor(jnp.zeros((1,), jnp.int32)))
    with pytest.raises(ValueError, match="unknown paged cache tag 'paged'"):
        paged_cache_attend(x, x, x, cache, None)
