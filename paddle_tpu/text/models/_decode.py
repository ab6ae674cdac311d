"""Shared jitted KV-cache decode loop (used by GPT and Llama heads).

The per-model piece is ONE closure: ``fwd(params, bufs, ids, ks, vs, pos)
-> (last-token logits f32, new ks, new vs)`` over stacked [L, B, T, h, d]
cache buffers.  This module owns everything else — sampling (greedy /
temperature / top-k / top-p as traced ops), the compiled prefill, the
single compiled decode step with DONATED cache buffers, and the
train-mode save/restore discipline — so decode fixes land in one place.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...tensor.tensor import Tensor


def program_store(model):
    """The per-model compiled-program cache.

    decode_loop keys it by its program_key tuples; the serving engine
    (paddle_tpu.serving) keys it by (kind, batch-shape, sampler) tuples so
    a second engine over the same model reuses the compiled prefill/step
    pair instead of re-tracing.  Stored via object.__setattr__ so Layer's
    attribute bookkeeping never sees it."""
    store = model.__dict__.get("_decode_programs")
    if store is None:
        store = {}
        object.__setattr__(model, "_decode_programs", store)
    return store


def apply_top_k_top_p(l, top_k, top_p):
    """Static top-k / top-p (nucleus) filtering on [N, V] logits.

    top_k/top_p are trace-time constants (part of every compiled program's
    key); filtered entries become -inf.  Shared by the generate() samplers,
    the serving engine's batched sampler, and the speculative-decoding
    verifier (serving/speculative.py), so the three paths can never drift
    on what distribution "temperature + top_k/top_p" means."""
    if top_k:
        kk = min(int(top_k), l.shape[-1])
        kth = jax.lax.top_k(l, kk)[0][:, -1][:, None]
        l = jnp.where(l < kth, -jnp.inf, l)
    if top_p < 1.0:  # nucleus: smallest prefix of sorted probs >= top_p
        srt = jnp.sort(l, axis=-1)[:, ::-1]
        p = jax.nn.softmax(srt, axis=-1)
        keep_n = (jnp.cumsum(p, axis=-1) - p < top_p).sum(-1)
        kth = jnp.take_along_axis(srt, (keep_n - 1)[:, None], axis=-1)
        l = jnp.where(l < kth, -jnp.inf, l)
    return l


def make_sampler(temperature, top_k, top_p):
    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1)
        l = logits / jnp.float32(max(temperature, 1e-6))
        l = apply_top_k_top_p(l, top_k, top_p)
        return jax.random.categorical(key, l, axis=-1)

    return sample


def make_batched_sampler(top_k=0, top_p=1.0):
    """Per-slot sampler for the serving engine: ONE traced program covers
    greedy and temperature rows (``temps[b] <= 0`` selects argmax), so a
    batch mixing greedy and sampled requests shares a single compiled
    decode step.  top_k/top_p stay static — they are part of the engine's
    program key, matching make_sampler's trace-time specialization."""

    def sample(logits, temps, key):
        greedy = jnp.argmax(logits, axis=-1)
        l = logits / jnp.maximum(temps, jnp.float32(1e-6))[:, None]
        l = apply_top_k_top_p(l, top_k, top_p)
        samp = jax.random.categorical(key, l, axis=-1)
        return jnp.where(temps <= jnp.float32(0.0), greedy, samp)

    return sample


def make_guarded_batched_sampler(top_k=0, top_p=1.0):
    """NaN-safe twin of :func:`make_batched_sampler` for the serving
    engine's numeric-guard program variant: returns ``(tokens, bad)``
    where ``bad [B] bool`` flags rows whose logits contain ANY non-finite
    value.  The token math is untouched — the flag is a pure extra
    reduction over the same logits, so every finite row's greedy/sampled
    token is byte-identical to the unguarded sampler's — which is what
    lets the engine fail exactly the poisoned requests while the rest of
    the batch streams on."""
    inner = make_batched_sampler(top_k, top_p)

    def sample(logits, temps, key):
        bad = ~jnp.all(jnp.isfinite(logits), axis=-1)
        return inner(logits, temps, key), bad

    return sample


def make_masked_batched_sampler(top_k=0, top_p=1.0):
    """Constrained-decoding twin of :func:`make_batched_sampler`: the
    multi-tenant engine's per-row token-FSM masks (``allowed [B, V]``
    bool, computed host-side each step — serving/multitenant/grammar.py)
    are applied BEFORE greedy/temperature sampling, so a schema-
    constrained row can only ever emit grammar-legal tokens while
    unconstrained rows (all-True mask) sample bit-identically to the
    unmasked path (``where`` with an all-True predicate is the identity).
    Disallowed entries get a large negative constant rather than -inf so
    a temperature row's softmax stays NaN-free by construction."""
    inner = make_batched_sampler(top_k, top_p)

    def sample(logits, allowed, temps, key):
        return inner(jnp.where(allowed, logits, jnp.float32(-1e30)),
                     temps, key)

    return sample


def decode_loop(model, fwd, ids0, max_new_tokens, init_cache,
                temperature=1.0, top_k=0, top_p=1.0, seed=None,
                program_key=None):
    """Generic prefill + per-token decode over an arbitrary cache PYTREE.

    fwd(params, bufs, ids, cache, pos) -> (last-token logits f32, cache).
    The cache (dense [L,B,T,h,d] buffers, paged pools, anything jax) is
    DONATED into each compiled step, so decode state updates in-place in
    HBM.  Returns the full id matrix.

    program_key: when the caller can name everything its fwd closure is
    specialized on (cache impl, shapes, sampling params — see generate()),
    the compiled prefill/step pair is CACHED on the model and reused by
    later calls.  Without it every generate() call re-traced and
    re-compiled both programs, which dominated short decodes.
    """
    import numpy as np

    S0 = ids0.shape[1]
    # snapshot under the model's bind lock: a serving replica tracing on
    # its scheduler thread holds bind() on this model, and an unlocked
    # read here would capture its tracers instead of the real arrays
    with model.bind_lock():
        params = {k: p._value for k, p in model.named_parameters()}
        bufs = {k: b._value for k, b in model.named_buffers()}
    modes = [(m, m.training) for m in model.sublayers(include_self=True)]
    model.eval()

    progs = None
    store = None
    if program_key is not None:
        store = program_store(model)
        progs = store.get(program_key)
    warm = progs is not None  # cached pair: no trace/compile in this call
    if progs is None:
        sample = make_sampler(temperature, top_k, top_p)

        @jax.jit
        def prefill(params, bufs, ids, cache, key):
            logits, cache = fwd(params, bufs, ids, cache, jnp.int32(0))
            return sample(logits, key), cache

        @functools.partial(jax.jit, donate_argnums=(3,))
        def step(params, bufs, last, cache, pos, key):
            logits, cache = fwd(params, bufs, last, cache, pos)
            return sample(logits, key), cache

        progs = (prefill, step)
        if store is not None:
            store[program_key] = progs
    prefill, step = progs

    from time import perf_counter

    from ...observability import perf as _perf
    from ...observability import programs as _programs
    from ...observability import tracing as _tracing

    if store is not None:
        # every store mint lands a ledger row; warm hits record provenance
        # only (no stall), so /statusz accounts 100% of live store keys
        _programs.ledger().record_mint(
            program_key, family="generate.decode", kind="generate",
            store=store, owner=model, replica="-", warm=warm)
    try:
        cache = init_cache()
        base = jax.random.key(seed if seed is not None else 0)
        key0 = jax.random.fold_in(base, 0)
        t_loop = perf_counter()
        # a cold key's prefill dispatch pays its trace+compile (the step
        # program builds under the same episode, at its first step): the
        # compile window puts the wall and the build's own seconds on the
        # row, under the ambient trace id
        win = _programs.ledger().compile_window(
            program_key, family="generate.decode", kind="generate",
            store=store, owner=model, replica="-",
            trace_id=_tracing.current_trace_id(),
            cold=not warm and store is not None)
        try:
            nxt, cache = prefill(params, bufs, jnp.asarray(ids0), cache, key0)
        finally:
            win.close()
        if store is not None and _perf.needs_cost("generate.decode"):
            # per-token roofline attribution for the generate() path: one
            # representative step program's cost (shapes captured here,
            # the re-lower+compile runs lazily off this path)
            _perf.register_cost_thunk("generate.decode", _perf.jit_cost_thunk(
                step, (params, bufs, nxt[:, None].astype(jnp.int64), cache,
                       np.int32(S0), key0)))
        # tokens stay ON DEVICE across the loop: async dispatch queues every
        # step without a host round-trip, and ONE transfer at the end
        # collects the whole id matrix.
        # Per-step host work is hoisted off the dispatch path too: greedy
        # decode never consumes randomness, so it reuses one key instead of
        # paying a fold_in dispatch per token, and the position scalar is a
        # host numpy int32 (same aval, no per-step device-array creation).
        greedy = temperature == 0.0
        out = [nxt[:, None]]
        for t in range(1, max_new_tokens):
            nxt, cache = step(params, bufs, nxt[:, None].astype(jnp.int64),
                              cache, np.int32(S0 + t - 1),
                              key0 if greedy else jax.random.fold_in(base, t))
            out.append(nxt[:, None])
        new = np.asarray(jnp.concatenate(out, axis=1))
        if warm:
            # whole pipelined loop (prefill + steps + the one sync),
            # attributed per emitted token; cold calls are trace+compile
            # walls, not device time, and are skipped
            _perf.record("generate.decode", perf_counter() - t_loop,
                         calls=max_new_tokens)
    finally:
        for m, tr in modes:
            m.training = tr
    return Tensor(jnp.asarray(np.concatenate([ids0, new], axis=1)))


def jitted_decode(model, fwd, ids0, max_new_tokens, cache_shape, cache_dtype,
                  temperature=1.0, top_k=0, top_p=1.0, seed=None,
                  program_key=None):
    """Dense-cache decode (the original API): zero-initialized K/V buffers
    [L, B, T, h, d]; fwd takes (params, bufs, ids, ks, vs, pos)."""

    def fwd_cache(params, bufs, ids, cache, pos):
        ks, vs = cache
        logits, ks, vs = fwd(params, bufs, ids, ks, vs, pos)
        return logits, (ks, vs)

    def init_cache():
        ks = jnp.zeros(tuple(cache_shape), cache_dtype)
        return ks, jnp.zeros_like(ks)

    return decode_loop(model, fwd_cache, ids0, max_new_tokens, init_cache,
                       temperature=temperature, top_k=top_k, top_p=top_p,
                       seed=seed, program_key=program_key)


def paged_pool_shape(batch, max_len, num_kv_heads, head_dim, page_size=16):
    """[B * PP, ps, h, d] shape of one layer's pages covering max_len
    tokens a sequence; rows as wide as the serving engine lays them out
    (``ops.paged_attention.pool_lane_dim``)."""
    from ...ops.paged_attention import pool_lane_dim

    pp = -(-max_len // page_size)
    return (batch * pp, page_size, num_kv_heads, pool_lane_dim(head_dim))


def paged_cache(pools, batch, pos):
    """generate()'s page pools as the serving engine's cache ``(tag, pools,
    table, lens)``: every sequence owns a run of pages (page i of sequence
    b is row ``b * PP + i``: an identity table) and all stand at ``pos``."""
    n = pools[0].shape[1]
    table = jnp.arange(n, dtype=jnp.int32).reshape(batch, n // batch)
    return ("served", tuple(Tensor(p) for p in pools), Tensor(table),
            Tensor(jnp.full((batch,), pos, jnp.int32)))


def beam_search(model, input_ids, max_new_tokens, num_beams=4,
                length_penalty=0.0, eos_token_id=None):
    """Reference-style beam search (PaddleNLP generate
    decode_strategy='beam_search'): maintain num_beams hypotheses per batch
    item, expand by log-prob, keep the global top beams, penalize each
    hypothesis by ITS OWN finished length at the end.  Beam bookkeeping is
    host logic; scoring runs through ONE compiled static-shape forward
    (prefixes right-padded to S0+max_new_tokens, last-position logits
    gathered by traced index), so all steps share a single trace and only
    [N, V] logits leave the device.

    model: a causal LM Layer (called as model(ids) -> [N, S, V] logits).
    Returns a Tensor [B, S0 + max_new_tokens] (best beam per item).
    """
    import numpy as np

    ids0 = np.asarray(input_ids.numpy()).astype("int64")
    if max_new_tokens <= 0:
        return input_ids
    B, S0 = ids0.shape
    modes = [(m, m.training) for m in model.sublayers(include_self=True)]
    model.eval()

    # Static-shape scoring (ADVICE r3): every pass feeds [N, S_max] ids
    # right-padded to the final length, and gathers the logits of the
    # current last position with a traced index.  Causality makes padding
    # after position pos-1 invisible to it, so one compiled program serves
    # every step — no per-length retrace, no O(S^2) growth in traced work.
    from ... import jit as _jit

    S_max = S0 + max_new_tokens

    @_jit.to_static
    def _score(ids, pos):
        out = model(ids)                       # [N, S_max, V]
        from ...tensor.manipulation import index_select

        return index_select(out, pos - 1, axis=1)[:, 0]  # [N, V]

    _fallback = [False]  # model does host logic / can't trace -> eager path
    # ONLY trace-incompatibility flips to the eager path (r4 weak #5: a bare
    # `except Exception` turned shape bugs in user models into a silent 100x
    # slower decode).  Real model errors propagate; the fallback itself is
    # announced with a warning.
    _TRACE_ERRS = (jax.errors.ConcretizationTypeError,
                   jax.errors.TracerArrayConversionError,
                   jax.errors.TracerBoolConversionError,
                   jax.errors.TracerIntegerConversionError,
                   jax.errors.UnexpectedTracerError,
                   NotImplementedError)

    def last_logits(arr, cur_len):
        if not _fallback[0]:
            try:
                n = arr.shape[0]
                padded = np.zeros((n, S_max), np.int64)
                padded[:, :cur_len] = arr
                pos = Tensor(jnp.asarray([cur_len], jnp.int64))
                out = _score(Tensor(jnp.asarray(padded)), pos)
                # only [N, V] crosses to host, not [N, S, V]
                return np.asarray(out._value).astype(np.float64)
            except _TRACE_ERRS as e:
                import warnings

                warnings.warn(
                    "beam_search: model is not jax-traceable "
                    f"({type(e).__name__}); falling back to the EAGER "
                    "per-step decode path, which is much slower",
                    RuntimeWarning, stacklevel=2)
                _fallback[0] = True
        out = model(Tensor(jnp.asarray(arr[:, :cur_len])))
        return np.asarray(out._value[:, -1]).astype(np.float64)

    def log_softmax(l):
        m = l.max(-1, keepdims=True)
        return l - (np.log(np.exp(l - m).sum(-1, keepdims=True)) + m)

    try:
        # first expansion: top num_beams continuations of each prompt
        logp = log_softmax(last_logits(ids0, S0))
        V = logp.shape[-1]
        top = np.argsort(-logp, axis=-1)[:, :num_beams]        # [B, beams]
        scores = np.take_along_axis(logp, top, -1)             # [B, beams]
        seqs = np.concatenate(
            [np.repeat(ids0[:, None], num_beams, 1), top[..., None]], -1)
        done = np.zeros((B, num_beams), bool)
        fin_len = np.full((B, num_beams), max_new_tokens, np.int64)
        # finished-hypothesis POOL per item: a completed beam is recorded
        # the moment it hits EOS, so later eviction from the active set
        # cannot lose it (reference BeamHypotheses semantics)
        pool = [[] for _ in range(B)]  # (penalized score, seq list)

        def penalize(sc, ln):
            return sc / (max(ln, 1) ** length_penalty) if length_penalty \
                else sc

        def record(b, k, t):
            pool[b].append((penalize(scores[b, k], t), seqs[b, k].copy()))

        if eos_token_id is not None:
            done |= top == eos_token_id
            fin_len = np.where(done, 1, fin_len)
            for b, k in zip(*np.nonzero(done)):
                record(b, k, 1)

        for t in range(1, max_new_tokens):
            if done.all():
                break
            logp = log_softmax(last_logits(seqs.reshape(B * num_beams, -1),
                                           seqs.shape[-1]))
            logp = logp.reshape(B, num_beams, V)
            if eos_token_id is not None:
                # finished beams only extend with EOS at no cost
                frozen = np.full((V,), -np.inf)
                frozen[eos_token_id] = 0.0
                logp = np.where(done[..., None], frozen, logp)
            cand = scores[..., None] + logp                    # [B, beams, V]
            pick = np.argsort(-cand.reshape(B, num_beams * V),
                              axis=-1)[:, :num_beams]
            beam_idx, tok = pick // V, pick % V
            scores = np.take_along_axis(cand.reshape(B, num_beams * V),
                                        pick, -1)
            seqs = np.concatenate(
                [np.take_along_axis(seqs, beam_idx[..., None], 1),
                 tok[..., None]], -1)
            done = np.take_along_axis(done, beam_idx, 1)
            fin_len = np.take_along_axis(fin_len, beam_idx, 1)
            if eos_token_id is not None:
                just = (~done) & (tok == eos_token_id)
                fin_len = np.where(just, t + 1, fin_len)
                done |= just
                for b, k in zip(*np.nonzero(just)):
                    record(b, k, t + 1)
    finally:
        for m, tr in modes:
            m.training = tr

    # best hypothesis = max over the finished pool and the live beams
    out_rows = []
    gen_total = seqs.shape[1] - S0
    for b in range(B):
        cands = list(pool[b])
        for k in range(num_beams):
            if not done[b, k]:  # live beam: penalize by full current length
                cands.append((penalize(scores[b, k], gen_total),
                              seqs[b, k]))
        best_seq = max(cands, key=lambda x: x[0])[1]
        if len(best_seq) < seqs.shape[1]:  # pool snapshot from an early step
            padv = eos_token_id if eos_token_id is not None else 0
            best_seq = np.concatenate(
                [best_seq, np.full(seqs.shape[1] - len(best_seq), padv,
                                   best_seq.dtype)])
        out_rows.append(best_seq)
    out = np.stack(out_rows)
    if out.shape[1] < S0 + max_new_tokens:  # early-EOS: pad with EOS
        pad = np.full((B, S0 + max_new_tokens - out.shape[1]),
                      eos_token_id if eos_token_id is not None else 0,
                      out.dtype)
        out = np.concatenate([out, pad], 1)
    return Tensor(jnp.asarray(out))
