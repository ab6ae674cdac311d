"""MultiTenantEngine — one continuous-batching engine, many tenants.

Extends :class:`~paddle_tpu.serving.engine.ServingEngine` with the three
multi-tenant workload classes (ROADMAP item 4, README "Multi-tenant
serving"), all riding the SAME iteration-level scheduler and compiled
program families:

- **paged multi-LoRA** (``lora_store=``): each batch row gathers its
  tenant's low-rank pairs by slot id inside the compiled
  prefill/decode/verify programs (:mod:`.lora`); program families are
  keyed by the store's RANK BUCKETS (``decode@lora-r<r>``), so adapter
  register/evict/hot-swap at runtime never re-traces;
- **grammar-constrained decoding** (``submit(grammar=...)``): per-row
  token-FSM masks (:mod:`.grammar`) computed host-side each step and
  applied in the batched sampler before greedy/temperature sampling;
  composes with speculative verification — drafts are pre-trimmed at the
  first grammar-illegal token and the verifier's distribution is masked
  per position, so a draft that exits the grammar is rejected and the
  bonus/resample token is always legal;
- **embed / score requests** (``submit(mode="embed"|"score")``): the
  prompt runs one prefill-family dispatch against the scratch page —
  no decode slot, no KV pages allocated — returning the pooled hidden
  state (``pooling="mean"|"last"``) or per-token prompt logprobs via
  ``handle.result()``.

Per-tenant observability: ``serving.tenant.requests{adapter=}`` /
``serving.tenant.tokens{adapter=}`` counters (label ``base`` = no
adapter) and a ``tenants`` section on /statusz; the new program families
attribute in the perf table as ``decode@lora-r<r>``,
``prefill/<bucket>@embed`` etc. and ``perf.candidate_hint`` recognizes
them.
"""

from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from ...observability import perf as _perf
from ...observability import tracing as _tracing
from ..engine import ServingEngine
from .lora import LoRAGPTAdapter, LoRAQuantizedGPTAdapter, LoRAStore


class MultiTenantEngine(ServingEngine):
    """See module docstring.  Typical use::

        store = LoRAStore(model, capacity=8, ranks=(8,))
        store.register(LoRAAdapter.random(model, "tenant-a", rank=4))
        engine = MultiTenantEngine(model, lora_store=store, num_slots=4)
        with engine:
            ha = engine.submit(p, adapter="tenant-a")     # LoRA row
            hb = engine.submit(p, grammar=g)              # schema row
            hc = engine.submit(p, mode="embed")           # embedding row
    """

    def __init__(self, model, lora_store: LoRAStore | None = None, **kw):
        if lora_store is not None and kw.get("adapter") is None:
            kvd = str(kw.get("kv_dtype") or "native").lower()
            cls = LoRAQuantizedGPTAdapter if kvd == "int8" \
                else LoRAGPTAdapter
            kw["adapter"] = cls(model, kw.get("page_size", 16), lora_store)
        self._lora = lora_store
        super().__init__(model, **kw)
        from ...profiler import metrics as _metrics
        from ...text.models._decode import make_masked_batched_sampler

        self._vsize = int(model.gpt.word_embeddings.weight.shape[0])
        self._nb = len(lora_store.ranks) if lora_store is not None else 0
        self._lora_fam = lora_store.family_suffix() \
            if lora_store is not None else ""
        self._mt_sig = ("mt", lora_store.signature()
                        if lora_store is not None else None)
        self._masked_sampler = make_masked_batched_sampler(*self._top)
        self._masked_verifier = None
        if self._spec_k:
            from ..speculative import make_masked_verifier

            self._masked_verifier = make_masked_verifier(*self._top)
        # persistent per-lane host buffers, extending the base set: the
        # grammar masks (all-True = unconstrained — bit-identical to the
        # unmasked sampler) and the per-bucket adapter slot ids (0 = null)
        self._h_allowed = np.ones((self.num_slots, self._vsize), np.bool_)
        self._h_aid = np.zeros((max(self._nb, 1), self.num_slots), np.int32)
        # device-RESIDENT all-True twins: with zero constrained rows live
        # (the common pure-LoRA batch) the dispatch passes these instead
        # of re-uploading num_slots x V host bytes every step — same aval,
        # so the program never re-traces when a grammar row arrives
        self._dev_allowed = jnp.ones((self.num_slots, self._vsize),
                                     jnp.bool_)
        self._n_constrained = 0      # live slots carrying a grammar
        if self._spec_k:
            self._h_allowed3 = np.ones(
                (self.num_slots, self._spec_k + 1, self._vsize), np.bool_)
            self._dev_allowed3 = jnp.ones(
                (self.num_slots, self._spec_k + 1, self._vsize), jnp.bool_)
        self._tenant_live = {}       # adapter name -> live request count
        # score-value memo for prefix-cached scoring: value[j] (the
        # logprob of prompt[j+1] given prompt[:j+1]) is a pure function
        # of prompt[:j+2], so entries up to a page boundary c are reusable
        # by ANY prompt sharing those c tokens — keyed by the boundary
        # prefix, populated at every boundary a score dispatch covers
        self._score_memo = collections.OrderedDict()
        self._score_memo_cap = 128
        self._m_tenant_req = _metrics.bind(_metrics.counter(
            "serving.tenant.requests",
            "submitted requests by tenant (adapter name, or 'base')"),
            replica=self.replica)
        self._m_tenant_tok = _metrics.bind(_metrics.counter(
            "serving.tenant.tokens",
            "tokens emitted by tenant (adapter name, or 'base')"),
            replica=self.replica)
        self._m_lora_blocked = _metrics.bind(_metrics.counter(
            "serving.lora_blocked",
            "admissions deferred: every adapter slot pinned by live "
            "requests"), replica=self.replica)

    # ------------------------------------------------------------ tenancy
    @property
    def lora_store(self):
        return self._lora

    def register_adapter(self, adapter):
        """Hot-swap path: host-registers a LoRA adapter on the live
        engine; it is paged into the device pools at first use.  No
        restart, no re-trace (asserted by the trace counters)."""
        if self._lora is None:
            raise ValueError("engine built without a lora_store")
        return self._lora.register(adapter)

    def _validate_tenant(self, adapter, grammar, mode, pooling,
                         eos_token_id):
        if mode not in ("generate", "embed", "score"):
            raise ValueError(f"mode must be generate|embed|score, "
                             f"got {mode!r}")
        if pooling not in ("mean", "last"):
            raise ValueError(f"pooling must be mean|last, got {pooling!r}")
        if adapter is not None:
            if self._lora is None:
                raise ValueError(f"adapter {adapter!r}: engine built "
                                 "without a lora_store")
            if not self._lora.registered(adapter):
                raise KeyError(f"adapter {adapter!r} is not registered "
                               f"(have {self._lora.names})")
        if grammar is not None:
            if mode != "generate":
                raise ValueError("grammar= only applies to mode='generate'")
            if grammar.vocab_size != self._vsize:
                raise ValueError(
                    f"grammar compiled over {grammar.vocab_size} tokens, "
                    f"model vocabulary is {self._vsize}")
            if eos_token_id is None:
                eos_token_id = grammar.eos_token_id
            elif int(eos_token_id) != grammar.eos_token_id:
                raise ValueError(
                    f"eos_token_id {eos_token_id} != the grammar's "
                    f"{grammar.eos_token_id}")
        return eos_token_id

    def submit(self, prompt_ids, *args, **kw):
        h = super().submit(prompt_ids, *args, **kw)
        # counted AFTER a successful enqueue: rejected/shed submissions
        # must not inflate the per-tenant request series (the base
        # serving.requests counter carries their status=rejected)
        self._m_tenant_req.inc(adapter=h.adapter or "base")
        return h

    def _acquire_tenant(self, req):
        if req.adapter is None or req.lease is not None:
            return True
        lease = self._lora.acquire(req.adapter)
        if lease is None:
            self._m_lora_blocked.inc()
            return False
        req.lease = lease
        self._tenant_live[req.adapter] = \
            self._tenant_live.get(req.adapter, 0) + 1
        return True

    def _release_tenant(self, req):
        if req.lease is not None:
            self._lora.release(req.lease)
            req.lease = None
            n = self._tenant_live.get(req.adapter, 0) - 1
            if n > 0:
                self._tenant_live[req.adapter] = n
            else:
                self._tenant_live.pop(req.adapter, None)

    # --------------------------------------------------- dispatch plumbing
    def _mt_args(self, aid):
        """The trailing (aid, *adapter_pools) the adapter closures take —
        empty without a store (the plain adapter takes no LoRA args)."""
        if self._lora is None:
            return ()
        return (aid,) + self._lora.device_args()

    def _aid_row(self, req):
        aid = np.zeros((max(self._nb, 1), 1), np.int32)
        if req.lease is not None:
            aid[req.lease.bucket, 0] = req.lease.row
        return aid

    def _prefill_family(self, s_pad):
        return f"prefill/{s_pad}{self._fam_suffix}{self._lora_fam}"

    def _decode_family(self):
        return f"decode{self._fam_suffix}{self._lora_fam}"

    def _prefill_chunk_family(self, c):
        return f"prefill_chunk/{c}{self._fam_suffix}{self._lora_fam}"

    def _verify_family(self):
        return f"verify/k{self._spec_k}{self._fam_suffix}{self._lora_fam}"

    def _mask_or_fail(self, handle, g, state):
        """One row's grammar mask, containing pathological failures (a
        mid-document state no vocab token can tile, or a state-count
        blowup) to THE REQUEST: the handle records the error and cancels,
        retiring at the next scheduler check, and the returned all-True
        mask only feeds the dying row's final dispatch — one bad
        (grammar, vocab) pairing must not abort every tenant's work."""
        try:
            return g.allowed(state)
        except ValueError as e:
            if handle._error is None:
                handle._error = e
            handle.cancel()
            return np.ones((self._vsize,), np.bool_)

    def _prefill_extra(self, req, slot_idx=None):
        allowed = np.ones((1, self._vsize), np.bool_)
        if req.grammar is not None:
            allowed[0] = self._mask_or_fail(req.handle, req.grammar,
                                            req.handle._fsm_state)
        return (allowed,) + self._mt_args(self._aid_row(req))

    def _step_extra(self):
        allowed = self._h_allowed if self._n_constrained \
            else self._dev_allowed
        return (allowed,) + self._mt_args(self._h_aid)

    def _host_makes_step_inputs(self):
        """A constrained row's next mask is its FSM advanced through the
        token just emitted (``_emit_token``): every step is read back
        before the next is built."""
        return True

    def _verify_extra(self, active):
        if not self._n_constrained:
            return (self._dev_allowed3,) + self._mt_args(self._h_aid)
        for i in active:
            s = self._slots[i]
            g = s.req.grammar
            if g is None:
                continue
            # per-position masks along the (grammar-filtered) draft chain:
            # position t's mask is the state after accepting drafts < t,
            # so an accepted prefix is legal by construction and the
            # bonus/resample at the first rejection samples a legal token
            st = s.handle._fsm_state
            try:
                self._h_allowed3[i, 0] = g.allowed(st)
                dlen = int(self._h_dlen[i])
                for t in range(dlen):
                    tok = int(self._h_ids[i, 1 + t])
                    if tok == g.eos_token_id:
                        # an accepted EOS draft retires the row
                        # mid-chain; later positions (and their bonus
                        # sample) are discarded, so their masks are
                        # unconstrained — advancing the FSM through EOS
                        # has no next state
                        self._h_allowed3[i, t + 1:] = True
                        break
                    st = g.advance(st, tok)
                    self._h_allowed3[i, t + 1] = g.allowed(st)
                else:
                    self._h_allowed3[i, dlen + 1:] = True
            except ValueError as e:     # same containment as _mask_or_fail
                if s.handle._error is None:
                    s.handle._error = e
                s.handle.cancel()
                self._h_allowed3[i] = True
        return (self._h_allowed3,) + self._mt_args(self._h_aid)

    def _filter_draft(self, i, draft):
        s = self._slots[i]
        g = s.req.grammar
        if g is None or not draft:
            return draft
        st = s.handle._fsm_state
        out = []
        for t in draft:
            if not self._mask_or_fail(s.handle, g, st)[int(t)]:
                break
            if s.handle.cancelled:      # grammar failure: row is dying
                return []
            out.append(t)
            if int(t) == g.eos_token_id:
                break
            st = g.advance(st, t)
        return out

    def _budget_status(self, slot):
        """A constrained row whose token budget ran out mid-document (its
        FSM is not in an accepting state) finishes as ``truncated``, not
        ``completed`` — the schema-validity guarantee only covers rows
        that actually reached a complete document, and the caller must be
        able to tell the difference (size ``max_new_tokens`` to the
        grammar's longest document to avoid it)."""
        g = slot.req.grammar
        if g is not None:
            st = slot.handle._fsm_state
            if st is None or not g.is_final(st):
                return "truncated"
        return "completed"

    def _on_admitted(self, slot, i):
        self._h_aid[:, i] = 0
        if slot.req.lease is not None:
            self._h_aid[slot.req.lease.bucket, i] = slot.req.lease.row
        g = slot.req.grammar
        if g is not None:
            self._n_constrained += 1
            self._h_allowed[i] = self._mask_or_fail(
                slot.handle, g, slot.handle._fsm_state)
        else:
            self._h_allowed[i] = True

    def _emit_token(self, slot, tok):
        super()._emit_token(slot, tok)
        g = slot.req.grammar
        h = slot.handle
        if g is not None and int(tok) != g.eos_token_id \
                and not h.cancelled:
            try:
                h._fsm_state = g.advance(h._fsm_state, tok)
                if h._fsm_state is None:  # unreachable under masking
                    raise RuntimeError(
                        f"constrained request {h.request_id} emitted "
                        f"token {int(tok)} outside its grammar")
                self._h_allowed[slot.idx] = self._mask_or_fail(
                    h, g, h._fsm_state)
            except ValueError as e:     # state blowup: contain to the row
                if h._error is None:
                    h._error = e
                h.cancel()
                self._h_allowed[slot.idx] = True
        self._m_tenant_tok.inc(adapter=slot.req.adapter or "base")

    def _clear_slot_row(self, i, slot):
        super()._clear_slot_row(i, slot)
        self._h_allowed[i] = True
        self._h_aid[:, i] = 0
        if slot.req.grammar is not None:
            self._n_constrained -= 1
        if self._spec_k:
            self._h_allowed3[i] = True

    def _reset_host_buffers(self):
        super()._reset_host_buffers()
        self._h_allowed[:] = True
        self._h_aid[:] = 0
        self._n_constrained = 0
        if self._spec_k:
            self._h_allowed3[:] = True

    # ------------------------------------------------------------ programs
    def _step_program(self):
        key = ("mt_step", self.num_slots, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype), self._top,
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._masked_sampler

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def step(params, bufs, last, *rest):
                traces[0] += 1
                pools = rest[:n]
                table, lens, temps, rkey, allowed = rest[n:n + 5]
                mt = rest[n + 5:]       # (aid, *adapter_pools) or ()
                out = adapter.step(params, bufs, last, *pools, table, lens,
                                   *mt)
                return (sampler(out[0], allowed, temps, rkey),) \
                    + tuple(out[1:])

            return step, traces

        return self._program(key, build)

    def _prefill_program(self, s_pad):
        key = ("mt_prefill", s_pad, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype), self._top,
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._masked_sampler

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def prefill(params, bufs, ids, *rest):
                traces[0] += 1
                pools = rest[:n]
                table, lens, temps, rkey, allowed = rest[n:n + 5]
                mt = rest[n + 5:]
                out = adapter.prefill(params, bufs, ids, *pools, table,
                                      lens, *mt)
                return (sampler(out[0], allowed, temps, rkey),) \
                    + tuple(out[1:])

            return prefill, traces

        return self._program(key, build)

    def _prefill_chunk_program(self, c_pad):
        key = ("mt_prefill_chunk", c_pad, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype), self._top,
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._masked_sampler

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(4, 4 + n)))
            def chunk(params, bufs, ids, nvalid, *rest):
                traces[0] += 1
                pools = rest[:n]
                table, lens, temps, rkey, allowed = rest[n:n + 5]
                mt = rest[n + 5:]
                out = adapter.prefill_chunk(params, bufs, ids, nvalid,
                                            *pools, table, lens, *mt)
                return (sampler(out[0], allowed, temps, rkey),) \
                    + tuple(out[1:])

            return chunk, traces

        return self._program(key, build)

    def _verify_program(self):
        key = ("mt_verify", self._spec_k, self.num_slots, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype), self._top,
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, verifier = self._adapter, self._masked_verifier

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def verify(params, bufs, ids, *rest):
                traces[0] += 1
                pools = rest[:n]
                table, lens, dlen, temps, rkey, allowed3 = rest[n:n + 6]
                mt = rest[n + 6:]
                out = adapter.verify(params, bufs, ids, *pools, table, lens,
                                     *mt)
                targets, accept = verifier(out[0], allowed3, ids[:, 1:],
                                           dlen, temps, rkey)
                return (targets, accept) + tuple(out[1:])

            return verify, traces

        return self._program(key, build)

    def _embed_program(self, s_pad, mode, pooling):
        key = ("mt_encode", mode, pooling, s_pad, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype),
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter = self._adapter

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def run(params, bufs, ids, *rest):
                import jax.numpy as jnp

                traces[0] += 1
                pools = rest[:n]
                table, lens = rest[n:n + 2]
                mt = rest[n + 2:]
                x, w, *pools2 = adapter.encode(params, bufs, ids, *pools,
                                               table, lens, *mt)
                S = x.shape[1]
                if mode == "embed":
                    if pooling == "last":
                        idx = (lens.astype(jnp.int32) - 1)[:, None, None]
                        out = jnp.take_along_axis(x, idx, axis=1)[:, 0]
                    else:
                        pos = jnp.arange(S, dtype=jnp.int32)[None, :]
                        m = (pos < lens[:, None]).astype(jnp.float32)
                        out = (x * m[..., None]).sum(axis=1) \
                            / jnp.maximum(
                                lens[:, None].astype(jnp.float32), 1.0)
                else:                   # score: logprob of each prompt
                    logits = x @ w.T                     # token given its
                    lp = jax.nn.log_softmax(logits, -1)  # prefix
                    tgt = ids[:, 1:].astype(jnp.int32)
                    out = jnp.take_along_axis(
                        lp[:, :-1], tgt[..., None], axis=-1)[..., 0]
                return (out,) + tuple(pools2)

            return run, traces

        return self._program(key, build)

    def _embed_chunk_program(self, c_pad, mode, pooling):
        """Prefix-cached encode: :meth:`GPTAdapter.encode_chunk` over the
        UNSHARED tail of an embed/score prompt, attending the resident
        shared-run pages the table addresses.  ``nvalid`` carries the real
        tail length (embed/last selects that lane in-program; score's
        host-side slice uses it)."""
        key = ("mt_encode_chunk", mode, pooling, c_pad, self.table_width,
               self._pools[0].shape, str(self._pools[0].dtype),
               self._mt_sig)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter = self._adapter

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(4, 4 + n)))
            def run(params, bufs, ids, nvalid, *rest):
                traces[0] += 1
                pools = rest[:n]
                table, lens = rest[n:n + 2]
                mt = rest[n + 2:]
                x, w, *pools2 = adapter.encode_chunk(
                    params, bufs, ids, *pools, table, lens, *mt)
                if mode == "embed":     # pooling == "last" by construction
                    idx = jnp.maximum(
                        nvalid.astype(jnp.int32) - 1, 0)[:, None, None]
                    out = jnp.take_along_axis(x, idx, axis=1)[:, 0]
                else:                   # score: logprob of each tail token
                    logits = x @ w.T    # given its full (cached) prefix
                    lp = jax.nn.log_softmax(logits, -1)
                    tgt = ids[:, 1:].astype(jnp.int32)
                    out = jnp.take_along_axis(
                        lp[:, :-1], tgt[..., None], axis=-1)[..., 0]
                return (out,) + tuple(pools2)

            return run, traces

        return self._program(key, build)

    # --------------------------------------------------------- passthrough
    def _run_passthrough(self, req):
        """One embed/score request: a single prefill-family dispatch with
        every table row pointed at the scratch page — the BlockManager is
        never touched (asserted by the page-accounting test) and no
        decode slot is occupied; the request retires immediately.

        Under ``prefix_cache="radix"``, embed (``pooling="last"``) and
        score requests first pin the longest resident shared run
        (``BlockManager.acquire_run``) and dispatch only the unshared
        tail through :meth:`_embed_chunk_program` — a system-prompt-heavy
        embed flood skips recomputing the cached pages entirely.  The
        scratch-page invariant survives: the table addresses only the
        refcounted shared run plus the scratch page (the sub-page tail's
        K/V lands at distinct in-page scratch offsets), and the run is
        released — parked idle, resident for the next sharer — the moment
        the dispatch returns.  ``pooling="mean"`` stays on the monolithic
        path: mean-pooling reduces over every position, so a cached run
        saves nothing and the full dispatch keeps reduction-order parity
        with the uncached engine."""
        h = req.handle
        S0 = len(req.prompt)
        if self._radix and (req.mode == "score" or (
                req.mode == "embed" and req.pooling == "last")):
            run = self._bm.acquire_run(req.prompt)
            if run is not None and run[0]:
                pages, cached = run
                try:
                    return self._run_passthrough_cached(req, pages, cached)
                finally:
                    self._bm.release_run(req.prompt, len(pages))
        s_pad = self._prefill_bucket(S0)
        ids = np.zeros((1, s_pad), np.int64)
        ids[0, :S0] = req.prompt
        table = np.full((1, self.table_width), self._scratch, np.int32)
        lens = np.asarray([S0], np.int32)
        mt = self._mt_args(self._aid_row(req))
        prog, traces = self._embed_program(s_pad, req.mode, req.pooling)
        n0 = traces[0]
        fam = (f"prefill/{s_pad}@{req.mode}"
               f"{self._fam_suffix}{self._lora_fam}")
        if _perf.needs_cost(fam):
            _perf.register_cost_thunk(fam, _perf.jit_cost_thunk(
                prog, (self._params, self._bufs, ids, *self._pools,
                       table, lens, *mt)))
        self._compiling = n0 == 0
        t0 = time.perf_counter()
        try:
            with _tracing.span(f"serving.{req.mode}", trace_id=h.trace_id,
                               request_id=h.request_id, prompt_len=S0):
                val, *pools = prog(self._params, self._bufs, ids,
                                   *self._pools, table, lens, *mt)
                self._pools = tuple(pools)
                val = np.asarray(val)
        finally:
            self._compiling = False
            self._progress_t = time.monotonic()
        if traces[0] > n0:
            self._m_prefill_traces.inc(traces[0] - n0)
        else:
            _perf.record(fam, time.perf_counter() - t0)
        self._m_prefill_seconds.observe(time.perf_counter() - t0)
        if req.mode == "embed":
            h.value = val[0]                        # [H] f32
        else:
            h.value = [float(v) for v in val[0][:max(S0 - 1, 0)]]
        self._release_tenant(req)
        self._admitting = None
        self._finish(h, "cancelled" if h.cancelled else "completed")

    def _run_passthrough_cached(self, req, pages, cached):
        """The prefix-cached half of :meth:`_run_passthrough`: dispatch
        the tail from offset ``l0`` against the pinned run.

        - embed/last: ``l0 = min(cached * ps, S0 - 1)`` — only the lanes
          needed to reach the last real position are computed (at least
          one, so a fully-covered prompt still recomputes its final
          position against cached K/V).
        - score: value entry j needs logits at position j, so a cached
          boundary ``c`` alone cannot produce entry ``c - 1`` — the
          dispatch starts at ``l0 = c' - 1`` where ``c'`` is the deepest
          page boundary with a score-memo hit (entries ``[:c' - 1]`` come
          from the memo; position ``c' - 1`` is recomputed against cached
          K/V for its logits).  No memo hit means a full-tail dispatch
          (``l0 = 0``) that self-warms both the memo and any freshly
          registered run pages.

        Fresh pages ``acquire_run`` registered start at ``cached * ps``
        >= every possible ``l0``, so the dispatch's pool writes always
        cover them with real K/V before the run is released."""
        h = req.handle
        S0 = len(req.prompt)
        ps = self.page_size
        prefix_vals = None
        if req.mode == "score":
            l0 = 0
            for k in range(min(cached, S0 // ps), 0, -1):
                mkey = tuple(int(t) for t in req.prompt[:k * ps])
                got = self._score_memo.get(mkey)
                if got is not None:
                    self._score_memo.move_to_end(mkey)
                    prefix_vals = list(got)
                    l0 = k * ps - 1
                    break
        else:
            l0 = min(cached * ps, S0 - 1)
        tail = S0 - l0
        c_pad = self._prefill_bucket(tail)
        ids = np.zeros((1, c_pad), np.int64)
        ids[0, :tail] = req.prompt[l0:]
        table = np.full((1, self.table_width), self._scratch, np.int32)
        table[0, :len(pages)] = pages
        lens = np.asarray([l0], np.int32)
        nvalid = np.asarray([tail], np.int32)
        mt = self._mt_args(self._aid_row(req))
        prog, traces = self._embed_chunk_program(c_pad, req.mode,
                                                 req.pooling)
        n0 = traces[0]
        fam = (f"prefill/{c_pad}@{req.mode}@cached{cached}"
               f"{self._fam_suffix}{self._lora_fam}")
        if _perf.needs_cost(fam):
            _perf.register_cost_thunk(fam, _perf.jit_cost_thunk(
                prog, (self._params, self._bufs, ids, nvalid, *self._pools,
                       table, lens, *mt)))
        self._compiling = n0 == 0
        t0 = time.perf_counter()
        try:
            with _tracing.span(f"serving.{req.mode}_cached",
                               trace_id=h.trace_id,
                               request_id=h.request_id, prompt_len=S0,
                               cached_tokens=l0):
                val, *pools = prog(self._params, self._bufs, ids, nvalid,
                                   *self._pools, table, lens, *mt)
                self._pools = tuple(pools)
                val = np.asarray(val)
        finally:
            self._compiling = False
            self._progress_t = time.monotonic()
        if traces[0] > n0:
            self._m_prefill_traces.inc(traces[0] - n0)
        else:
            _perf.record(fam, time.perf_counter() - t0)
        self._m_prefill_seconds.observe(time.perf_counter() - t0)
        if req.mode == "embed":
            h.value = val[0]                    # [H] f32, last-position row
        else:
            vals = [float(v) for v in val[0][:max(tail - 1, 0)]]
            if prefix_vals is not None:
                vals = prefix_vals + vals       # memo covers [:l0]
            h.value = vals
            for k in range(1, S0 // ps + 1):    # warm every boundary
                mkey = tuple(int(t) for t in req.prompt[:k * ps])
                self._score_memo[mkey] = tuple(vals[:k * ps - 1])
                self._score_memo.move_to_end(mkey)
            while len(self._score_memo) > self._score_memo_cap:
                self._score_memo.popitem(last=False)
        self._release_tenant(req)
        self._admitting = None
        self._finish(h, "cancelled" if h.cancelled else "completed")

    # -------------------------------------------------------------- insight
    def stats(self):
        st = super().stats()
        st["multitenant"] = {
            "vocab_size": self._vsize,
            "lora": self._lora.stats() if self._lora is not None else None,
        }
        return st

    def _statusz(self):
        st = super()._statusz()
        tenants = {}
        if self._lora is not None:
            lstats = self._lora.stats()
            for name, info in lstats["adapters"].items():
                tenants[name] = dict(info,
                                     live_requests=self._tenant_live.get(
                                         name, 0))
            st["lora_pools"] = {k: lstats[k] for k in
                                ("ranks", "capacity", "targets", "dtype",
                                 "pool_bytes")}
        st["tenants"] = tenants
        return st
