"""The paged attention kernels traced once a signature
(``ops.paged_attention._traced_once``): a program whose layer bodies apply
the decode or the chunk kernel at one signature traces the kernel's body
once, and the program it traces is, equation for equation, the one the
undecorated kernel application traces -- the same primitives, shapes and
name stacks, no call of a jitted function left in it.  Traced with the
TPU's kernels on the CPU (``jax.default_backend`` patched to ``"tpu"``,
``interpret=False``): nothing is lowered, nothing runs."""

import importlib

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.profiler import metrics as prof_metrics

pa = importlib.import_module("paddle_tpu.ops.paged_attention")

CACHED = ("_paged_decode_call", "_paged_chunk_call")


def _counts(kernel):
    """``(calls, bodies)`` traced so far for ``kernel`` (decode | chunk)."""
    return tuple(prof_metrics.counter(name).get(kernel=kernel) or 0
                 for name in ("paged.kernel_calls_traced",
                              "paged.kernel_bodies_traced"))


def _moved(kernel, before):
    return tuple(a - b for a, b in zip(_counts(kernel), before))


def _sub_jaxprs(value):
    if isinstance(value, jax.extend.core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jax.extend.core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for v in value:
            yield from _sub_jaxprs(v)


def _flat(jaxpr, out=None):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (loop bodies,
    kernel bodies), in order: primitive, operand and result types, name
    stack, and the name a Pallas call or a call of a jitted function
    carries."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        named = eqn.params.get("name_and_src_info")
        out.append((eqn.primitive.name,
                    tuple(str(v.aval) for v in eqn.invars),
                    tuple(str(v.aval) for v in eqn.outvars),
                    str(eqn.source_info.name_stack),
                    eqn.params.get("name") if named is None
                    else named.name))
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                _flat(sub, out)
    return out


def _uncached(monkeypatch):
    """The kernel applications as they were before the cache: the
    undecorated functions under the names the builders call."""
    for name in CACHED:
        monkeypatch.setattr(pa, name, getattr(pa, name).__wrapped__)


# --------------------------------------------------- one builder, N calls
def _pools(layers, P, ps, HKV, D, dtype=jnp.bfloat16):
    return tuple(jax.ShapeDtypeStruct((layers, P, ps, HKV, D), dtype)
                 for _ in range(2))


def _decode(pools, scales=()):
    B, NP, H, D = 3, 5, 2 * pools[0].shape[3], pools[0].shape[-1]

    def build(q, table, lens, *pools_and_scales):
        k, v, *sc = pools_and_scales
        return [pa._paged_decode_pallas(q, (k, v), tuple(sc), table, lens,
                                        0.125, False, layer)
                for layer in range(pools[0].shape[0])]
    return build, (jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, NP), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32), *pools, *scales)


def _chunk(pools):
    B, C, NP, H, D = 2, 24, 5, 2 * pools[0].shape[3], pools[0].shape[-1]

    def build(q, table, lens, k, v):
        return [pa._paged_chunk_pallas(q, (k, v), (), table, lens, 0.125,
                                       False, layer)
                for layer in range(pools[0].shape[0])]
    return build, (jax.ShapeDtypeStruct((B, C, H, D), jnp.bfloat16),
                   jax.ShapeDtypeStruct((B, NP), jnp.int32),
                   jax.ShapeDtypeStruct((B,), jnp.int32), *pools)


@pytest.mark.parametrize("kernel,make", [
    ("decode", lambda p: _decode(p)),
    ("chunk", lambda p: _chunk(p))])
def test_n_calls_at_one_signature_trace_the_body_once(kernel, make):
    """Seven layers at one signature: seven calls, one body; the same
    program again: seven calls, no body; another page size, another head
    count: a body each."""
    def moved(pools):
        # a program of its own each time (``make_jaxpr`` keeps what it
        # traced for a function it has seen)
        build, args = make(pools)
        before = _counts(kernel)
        jax.make_jaxpr(build)(*args)
        return _moved(kernel, before)

    # sizes no other test traces, so that the first build is a miss
    assert moved(_pools(7, 13, 16, 2, 128)) == (7, 1)
    assert moved(_pools(7, 13, 16, 2, 128)) == (7, 0)
    assert moved(_pools(7, 13, 32, 2, 128)) == (7, 1)
    assert moved(_pools(7, 13, 16, 4, 128)) == (7, 1)


def test_the_int8_pools_kernel_is_cached_as_well():
    """The decode of pools no DMA takes (int8, scale pools beside them) is
    the same cached application at its own signature."""
    pools = _pools(5, 11, 16, 2, 128, jnp.int8)
    scales = tuple(jax.ShapeDtypeStruct((5, 11, 16, 2), jnp.float32)
                   for _ in range(2))
    build, args = _decode(pools, scales)
    before = _counts("decode")
    jaxpr = jax.make_jaxpr(build)(*args)
    assert _moved("decode", before) == (5, 1)
    assert sum(e[0] == "pallas_call" for e in _flat(jaxpr.jaxpr)) == 5


@pytest.mark.parametrize("kernel,make", [
    ("decode", lambda p: _decode(p)),
    ("chunk", lambda p: _chunk(p))])
def test_n_calls_trace_what_the_undecorated_calls_trace(monkeypatch, kernel,
                                                        make):
    def flat():
        # a program of its own each time, as above
        build, args = make(_pools(4, 9, 16, 2, 128))
        return _flat(jax.make_jaxpr(build)(*args).jaxpr)

    cached = flat()
    _uncached(monkeypatch)
    before = _counts(kernel)
    plain = flat()
    assert _moved(kernel, before) == (0, 0)
    assert cached == plain
    assert sum(e[0] == "pallas_call" for e in cached) == 4


# ------------------------------------------------ served programs, whole
def _ouro_adapter():
    """A looped decoder of 48 layers run 2 times at toy widths, its heads
    the published 128 lanes, served on pages of 16 over 8 slots."""
    from paddle_tpu.serving import StatedCacheAdapter
    from paddle_tpu.text.models.ouro import OuroForCausalLM

    model = OuroForCausalLM(dict(
        vocab_size=128, hidden_size=256, intermediate_size=128,
        num_hidden_layers=48, num_attention_heads=2, num_key_value_heads=2,
        head_dim=128, total_ut_steps=2, dtype="bfloat16")).eval()
    return StatedCacheAdapter(model, 16, 8), 8, 48


def _gpt_adapter(kv_dtype=None):
    """A GPT-2 of 2 layers, 2 heads of 128, served on pages of 16: the
    layer a Python int."""
    import paddle_tpu as paddle
    from paddle_tpu.serving.adapter import GPTAdapter
    from paddle_tpu.serving.quant import QuantizedGPTAdapter
    from paddle_tpu.text.models import GPTForCausalLM

    paddle.seed(0)
    model = GPTForCausalLM(
        vocab_size=128, hidden_size=256, num_hidden_layers=2,
        num_attention_heads=2, max_position_embeddings=256).eval().bfloat16()
    adapter = (QuantizedGPTAdapter if kv_dtype == "int8"
               else GPTAdapter)(model, page_size=16)
    return adapter, 4, 2


def _served_jaxpr(adapter, slots, closure):
    params, bufs = adapter.params_and_buffers()
    pools = jax.eval_shape(lambda: adapter.init_pools(33))
    B, width = (slots, 1) if closure == "step" else (1, 32)
    lead = (jax.ShapeDtypeStruct((B, width), jnp.int64),) + (
        (jax.ShapeDtypeStruct((B,), jnp.int32),)
        if closure == "prefill_chunk" else ())
    return jax.make_jaxpr(getattr(adapter, closure))(
        params, bufs, *lead, *pools,
        jax.ShapeDtypeStruct((B, 16), jnp.int32),
        jax.ShapeDtypeStruct((B,), jnp.int32)).jaxpr


ADAPTERS = {"ouro": _ouro_adapter, "gpt2": _gpt_adapter,
            "gpt2_int8": lambda: _gpt_adapter("int8")}


@pytest.mark.parametrize("model,closure", [
    ("ouro", "step"), ("ouro", "prefill_chunk"), ("gpt2", "step"),
    ("gpt2", "prefill_chunk"), ("gpt2_int8", "step")])
def test_served_programs_trace_as_before_and_a_body_once(monkeypatch, model,
                                                         closure):
    """The served decode step and chunk program, the looped decoder's with
    its layer a traced scalar and GPT-2's with a Python int: the flattened
    equations equal those of the undecorated kernel application, and a
    build applies the kernel once a layer body while tracing its body at
    most once (not at all when it was traced before)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    adapter, slots, layers = ADAPTERS[model]()
    kernel = "decode" if closure == "step" else "chunk"
    before = _counts(kernel)
    cached = _flat(_served_jaxpr(adapter, slots, closure))
    calls, bodies = _moved(kernel, before)
    assert calls == layers and bodies <= 1
    before = _counts(kernel)
    _served_jaxpr(adapter, slots, closure)
    assert _moved(kernel, before) == (layers, 0)
    _uncached(monkeypatch)
    plain = _flat(_served_jaxpr(adapter, slots, closure))
    assert cached == plain
    kernels = [e for e in cached if e[0] == "pallas_call"]
    # a writer and an attention kernel a layer body
    assert len(kernels) == 2 * layers
    # no call of a jitted function is left where a kernel is applied, and
    # no name stack gains one
    assert not any(e[0] in ("jit", "pjit") and e[4] in CACHED
                   for e in cached)
    assert not any("jit(" in e[3] for e in cached)


def _smallthinker_adapter():
    """Two NoPE full layers and six window layers at toy widths, heads of
    the published 128 lanes, served on pages of 16 over 4 slots: the window
    layers' pages in a pool of their own."""
    from paddle_tpu.serving import StatedCacheAdapter
    from paddle_tpu.text.models.smallthinker import SmallThinkerForCausalLM

    layout = [0, 1, 1, 1, 0, 1, 1, 1]
    model = SmallThinkerForCausalLM(dict(
        vocab_size=128, hidden_size=256, num_hidden_layers=8,
        num_attention_heads=4, num_key_value_heads=2, head_dim=128,
        moe_ffn_hidden_size=64, moe_num_primary_experts=8,
        moe_num_active_primary_experts=2, rope_layout=layout,
        sliding_window_layout=layout, sliding_window_size=64,
        max_position_embeddings=256, dtype="bfloat16")).eval()
    adapter = StatedCacheAdapter(model, 16, 4)
    adapter.plan_window(4, 16, 32)
    return adapter


@pytest.mark.parametrize("closure", ["step", "prefill_chunk"])
def test_the_window_signature_is_traced_once_a_program(monkeypatch,
                                                       closure):
    """Six window layers and two full layers: eight kernel applications, a
    body for each signature (the window pool's, with its window; the full
    pool's, without) and none again on a second build; the program is the
    undecorated application's, equation for equation."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    adapter = _smallthinker_adapter()
    kernel = "decode" if closure == "step" else "chunk"

    def flat():
        params, bufs = adapter.params_and_buffers()
        pools = jax.eval_shape(lambda: adapter.init_pools(65))
        B, width = (4, 1) if closure == "step" else (1, 32)
        lead = (jax.ShapeDtypeStruct((B, width), jnp.int64),) + (
            (jax.ShapeDtypeStruct((B,), jnp.int32),)
            if closure == "prefill_chunk" else ())
        return _flat(jax.make_jaxpr(getattr(adapter, closure))(
            params, bufs, *lead, *pools,
            jax.ShapeDtypeStruct((B, 32), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32)).jaxpr)

    before = _counts(kernel)
    cached = flat()
    calls, bodies = _moved(kernel, before)
    assert calls == 8 and bodies <= 2
    before = _counts(kernel)
    flat()
    assert _moved(kernel, before) == (8, 0)
    _uncached(monkeypatch)
    assert flat() == cached
    assert sum(e[0] == "pallas_call" for e in cached) == 2 * 8
