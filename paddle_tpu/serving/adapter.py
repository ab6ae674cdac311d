"""Model adapters: the pure functions the ServingEngine jit-compiles.

An adapter reduces a causal LM to closures over explicit jax state (the
engine wraps them in ``jax.jit`` with DONATED pools, once per
(batch-shape, sampler) tuple — the ``_decode.py`` discipline).  The KV
state is an adapter-defined POOL TUPLE of ``n_pools`` arrays: the base
:class:`GPTAdapter` carries ``(kp, vp)`` global page pools, every layer
stacked in each; the quantized
:class:`~paddle_tpu.serving.quant.QuantizedGPTAdapter` carries
``(kp, vp, k_scales, v_scales)`` — int8 payloads plus parallel scale
pools.  The engine treats the tuple opaquely (build, donate, rebind), so
one scheduler serves every pool layout.

The pools are served IN PLACE.  A closure hands the model ONE cache,
``(tag, pools, table, lens)``, and every decoder layer reads and writes
the stacked pools at its own index (``ops.paged_attention``: the kernels'
block specs take the layer as a leading block dimension of one whose block
index is a prefetched scalar, so the index may be a Python int or a traced
one: a decoder that runs its layers several times reaches row ``step *
layers + layer`` from inside its traced loop; the writer merges a chunk's
rows into the pages it touches through aliased operands) and hands the
tuple to the next: no program slices a layer out of a pool or stacks layers
back.  On the TPU a payload pool's rows are as wide as
the chip's lanes (``ops.paged_attention.pool_lane_dim``: a ``d`` of 64 is
stored 128 wide, zeros behind it), which is how the device holds a
``[ps, h, d]`` page for the kernels anyway; said in the SHAPE, the
device's own layout of the pool is the one the kernels take, and with the
pools donated a compiled program holds no operation over a whole pool,
only its kernels' pages (where the heads of one device fill its sublane
tiles — 16 rows of bf16, 8 of f32; otherwise XLA still converts the pool
on the way in and out: ROADMAP, Speed).

- ``prefill(params, bufs, ids, *pools, table, lens)`` — run the
  (right-padded) prompts ``ids [B, S]`` densely, write their K/V into the
  global page pools through ``table [B, NP]``, and return the next-token
  logits gathered at each row's true last position ``lens[b] - 1``.
- ``step(params, bufs, last, *pools, table, lens)`` — one decode token per
  slot at each slot's OWN position ``lens[b]`` (iteration-level batching:
  no lock-step scalar pos), attention through the paged kernel.
- ``verify(params, bufs, ids, *pools, table, lens)`` — speculative
  decoding's multi-token step: C tokens per slot at positions
  ``lens[b]..lens[b]+C-1`` through the chunk cache variant, returning
  logits at EVERY position so the engine can accept/reject the drafted
  suffix (serving/speculative.py).
- ``prefill_chunk(params, bufs, ids, nvalid, *pools, table, lens)`` —
  chunked prefill's ingestion step: the next C prompt tokens per slot
  through the same chunk cache variant, logits at each row's last real
  chunk lane (``nvalid[b] - 1``) so the final chunk seeds decode exactly
  like a monolithic prefill.

prefill/step return ``(logits [B, V] f32, *pools)``, verify
``(logits [B, C, V] f32, *pools)``, with each pool a per-layer-stacked
``[L, P, ...]`` array.

A decoder that is not ``.gpt`` STATES ITS CACHES (``model.serving_caches()``)
and is served by :class:`StatedCacheAdapter`: pages for as many cache rows
as it names (the attention layers of a hybrid; ``steps x layers`` of a
decoder that runs its layers several times over one set of weights), and,
where it names a ``state_shape``, A SECOND KIND OF CACHE in the pool tuple:
``(kp, vp, state)`` with ``state [L_state, slots + 1, ...]``, one row a
slot, that lives and dies with the slot and not with pages.  With a state
the decode rows are the slots and the one-request programs (``prefill``,
``prefill_chunk``) take the slot's index as one more int32 operand behind
``lens``, which the engine appends (``ServingEngine._prefill_extra``) for an
adapter that says ``slot_state``; without one the tuple is ``(kp, vp)`` and
the programs take what :class:`GPTAdapter`'s take.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class PagedAdapter:
    """What every adapter shares: the paged-cache tags, the geometry of the
    K/V page pools and their arithmetic, the model's arrays taken under its
    bind lock, the signature.  A subclass states the geometry
    (:meth:`_set_geometry`), builds its pool tuple (``init_pools``) and
    brings the closures."""

    #: paged-cache tags an adapter drives (``paged_cache_attend``): one
    #: token or a whole prompt per slot, and a chunk at the slot's own
    #: position
    tag = "served"
    chunk_tag = "served_chunk"
    #: number of arrays in the pool tuple (the engine donates all of them)
    n_pools = 2
    #: storage format label ("native" = the model dtype)
    kv_dtype = "native"
    #: True where the pool tuple holds a per-slot state beside the pages:
    #: the engine then hands the one-request programs the slot's index
    slot_state = False
    #: set by ServingEngine(mesh=...) — the jax Mesh whose "model" axis the
    #: pools/weights are sharded over (None = single-device serving).  The
    #: TPU flash kernels consult it at trace time (mp_shard_scope) so each
    #: shard's Pallas page sweep covers only its local KV heads.
    mp_mesh = None
    mp_axis = "model"

    def _set_geometry(self, model, page_size, num_layers, num_kv_heads,
                      head_dim, dtype, max_model_len):
        """``num_layers`` counts the cache rows, the layers that HOLD pages
        (all of a ``.gpt`` decoder, the attention layers of a hybrid, steps
        x layers of a looped decoder): not the layers that hold weights."""
        self.model = model
        self.page_size = int(page_size)
        self.num_layers = int(num_layers)
        self.num_kv_heads = int(num_kv_heads)
        self.head_dim = int(head_dim)
        self.dtype = dtype
        self.max_model_len = int(max_model_len)

    def params_and_buffers(self):
        # under the bind lock: another replica of this model may be inside
        # a trace-time bind() on its scheduler thread right now
        with self.model.bind_lock():
            params = {k: p._value for k, p in self.model.named_parameters()}
            bufs = {k: b._value for k, b in self.model.named_buffers()}
        return params, bufs

    def signature(self):
        """Static geometry a compiled program is specialized on, as a
        JSON-plain dict.  Stamped into :class:`~paddle_tpu.observability
        .programs.WarmupManifest` metadata so a manifest captured against
        one model is refused by an engine whose replay would only mint
        useless programs."""
        return {"adapter": type(self).__name__,
                "kv_dtype": self.kv_dtype,
                "n_pools": int(self.n_pools),
                "num_layers": int(self.num_layers),
                "num_kv_heads": int(self.num_kv_heads),
                "head_dim": int(self.head_dim),
                "page_size": int(self.page_size),
                "max_model_len": int(self.max_model_len),
                "dtype": str(self.dtype)}

    # ----------------------------------------------------------- pool hooks
    def _page_pool(self, num_pages):
        """One zeroed payload pool [L, P, ps, h, d]: every page-holding
        layer's pages in one array, ``d`` the head size in whole lanes."""
        from ..ops.paged_attention import pool_lane_dim

        return jnp.zeros((self.num_layers, int(num_pages), self.page_size,
                          self.num_kv_heads, pool_lane_dim(self.head_dim)),
                         self.dtype)

    def page_bytes(self):
        """HBM bytes ONE page costs across the layers that hold pages, K
        and V (the unit BlockManager capacity math and the
        serving.kv_bytes_per_token gauge are denominated in)."""
        from ..ops.paged_attention import pool_lane_dim

        return (2 * self.num_layers * self.page_size * self.num_kv_heads
                * pool_lane_dim(self.head_dim)
                * jnp.dtype(self.dtype).itemsize)

    def pool_owners(self):
        """Memory-ledger owner labels over the pool tuple: ``(owner,
        pool-index tuple)`` pairs covering EVERY pool array, so the
        engine's ledger registration attributes payload and scale pools
        separately (observability.memory owner taxonomy)."""
        return (("kv.pages", tuple(range(self.n_pools))),)

    def state_bytes_per_slot(self):
        """HBM bytes of per-slot state ONE resident sequence costs beside
        its pages (the ``serving.state_bytes_per_slot`` gauge): none for a
        decoder whose only cache is paged."""
        return 0


class GPTAdapter(PagedAdapter):
    """Adapter for :class:`paddle_tpu.text.models.GPTForCausalLM` (and any
    model exposing the same ``.gpt`` decoder structure over the paged
    cache).  Subclasses override the pool hooks (``init_pools``,
    ``page_bytes``, ``pool_owners``, ``pool_pspecs``) to change the KV
    storage format without touching the closure shapes: the cache seam
    (``ops.paged_attention.paged_cache_attend``) tells the formats apart
    by the pool tuple it is handed."""

    def __init__(self, model, page_size=16):
        self.gpt = model.gpt
        blk = self.gpt.layers[0]
        # local head count from the actual projection width (TP-safe); an
        # int8-weight model (serving.quant.quantize_model_weights) stores
        # the projection as an Int8Linear whose weight lives in the
        # ``weight_int8`` buffer — same shape, different attribute
        qkv_w = getattr(blk.qkv, "weight", None)
        if qkv_w is None:
            qkv_w = blk.qkv.weight_int8
        self._set_geometry(
            model, page_size, num_layers=len(self.gpt.layers),
            num_kv_heads=qkv_w.shape[-1] // (3 * blk.head_dim),
            head_dim=blk.head_dim,
            dtype=self.gpt.word_embeddings.weight._value.dtype,
            max_model_len=self.gpt.position_embeddings.weight.shape[0])

    # --------------------------------------------------------- mp sharding
    def validate_mp(self, mp):
        """Divisibility check for ``ServingEngine(mesh=...)``: the pools
        shard on the KV-head dim and the qkv split is head-granular, so
        every shard must own a whole number of heads."""
        mp = int(mp)
        if self.num_kv_heads % mp:
            raise ValueError(
                f"tensor-parallel serving needs num_kv_heads divisible by "
                f"the mesh's model axis: {self.num_kv_heads} heads % "
                f"mp={mp} != 0")

    def pool_pspecs(self, axis="model"):
        """PartitionSpec per pool array: payload pools [L, P, ps, h, d]
        shard the KV-head dim (page table stays replicated — every shard
        addresses the same page slots, each holding its own heads)."""
        from jax.sharding import PartitionSpec as P

        return (P(None, None, None, axis, None),) * self.n_pools

    def param_pspec(self, name, axis="model"):
        """PartitionSpec for one named parameter/buffer under mp serving:
        the Megatron column/row split from gpt.mp_param_specs, replicated
        for everything outside the decoder matmuls."""
        from jax.sharding import PartitionSpec as P
        from ..text.models.gpt import mp_param_specs

        for suf, spec in mp_param_specs(axis).items():
            if name.endswith(suf):
                return spec
        return P()

    # ----------------------------------------------------------- pool hooks
    def init_pools(self, num_pages):
        """Zeroed K/V pools ``(kp, vp)``, each [L, P, ps, h, d]."""
        kp = self._page_pool(num_pages)
        return kp, jnp.zeros_like(kp)

    # ------------------------------------------------------------- closures
    def _run(self, params, bufs, ids, pools, table, lens, pos_ids, tag,
             lora=None):
        from ..framework import random as _rng
        from ..framework.state import no_grad_ctx
        from ..ops.paged_attention import mp_shard_scope
        from ..tensor.tensor import Tensor

        gpt = self.gpt
        with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                self.model.bind(params, bufs), \
                mp_shard_scope(self.mp_mesh, self.mp_axis):
            # ONE cache for all layers: each reads and writes the stacked
            # pools in place at its own index and hands the tuple on
            x, pools = gpt(Tensor(ids), position_ids=Tensor(pos_ids),
                           cache=(tag, tuple(Tensor(p) for p in pools),
                                  Tensor(table), Tensor(lens)), lora=lora)
            w = gpt.word_embeddings.weight._value
            return x._value, w, tuple(p._value for p in pools)

    def _split(self, args):
        """``(*pools, table, lens)`` -> (pools tuple, table, lens)."""
        if len(args) != self.n_pools + 2:
            raise TypeError(
                f"{type(self).__name__} closures take {self.n_pools} pool "
                f"arrays + table + lens; got {len(args)} trailing args")
        return tuple(args[:self.n_pools]), args[-2], args[-1]

    def _split_extra(self, args):
        """``(pools, table, lens, lora)`` — THE extension hook: an
        adapter carrying extra trailing dispatch args (multi-tenant LoRA:
        per-row adapter ids + the rank-bucketed pools) overrides this one
        method; the prefill/step/verify/encode closure bodies below stay
        single-copy."""
        pools, table, lens = self._split(args)
        return pools, table, lens, None

    def prefill(self, params, bufs, ids, *args):
        pools, table, lens, lora = self._split_extra(args)
        S = ids.shape[1]
        pos_ids = jnp.arange(S, dtype=jnp.int64)[None, :]
        x, w, pools = self._run(params, bufs, ids, pools, table, lens,
                                pos_ids, self.tag, lora=lora)
        # logits at each row's LAST REAL position (rows are right-padded)
        idx = (lens.astype(jnp.int32) - 1)[:, None, None]
        h = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        logits = h.astype(jnp.float32) @ w.T.astype(jnp.float32)
        return (logits,) + pools

    def encode(self, params, bufs, ids, *args):
        """Embedding/scoring forward (multi-tenant serving's
        ``mode="embed"|"score"`` requests): run the (right-padded) prompts
        like :meth:`prefill` but return the FULL hidden states and the
        tied LM-head weights instead of last-position logits — the embed
        program pools them, the score program turns them into per-token
        logprobs.  K/V still flows through the pool writes (the caller
        points every table row at the scratch page, so nothing is
        allocated and the junk is never attended).

        Returns ``(hidden [B, S, H] f32, w [V, H] f32, *pools)``."""
        pools, table, lens, lora = self._split_extra(args)
        S = ids.shape[1]
        pos_ids = jnp.arange(S, dtype=jnp.int64)[None, :]
        x, w, pools = self._run(params, bufs, ids, pools, table, lens,
                                pos_ids, self.tag, lora=lora)
        return (x.astype(jnp.float32), w.astype(jnp.float32)) + pools

    def encode_chunk(self, params, bufs, ids, *args):
        """Prefix-cached embed/score forward: run ``ids [B, C]`` — the
        UNSHARED tail of each prompt — at per-slot positions
        ``lens[b]..lens[b]+C-1`` through the chunk cache variant, attending
        the resident shared-run pages the table points at.  Because K/V at
        position p is a pure function of tokens 0..p, hiddens for the tail
        computed this way are byte-identical to a full-prompt
        :meth:`encode`, which is what lets multi-tenant embed/score skip
        recompute of a cached system prompt.  The tail's own K/V lands in
        the table rows past the shared run — the caller points those at
        the scratch page (tail < page_size means every lane gets a
        DISTINCT in-page offset, so within-dispatch causality still
        holds) or at transient pages for longer tails.

        Returns ``(hidden [B, C, H] f32, w [V, H] f32, *pools)`` — the
        :meth:`encode` contract over tail positions only."""
        pools, table, lens, lora = self._split_extra(args)
        C = ids.shape[1]
        pos_ids = lens[:, None].astype(jnp.int64) \
            + jnp.arange(C, dtype=jnp.int64)[None, :]
        pos_ids = jnp.minimum(pos_ids, self.max_model_len - 1)
        x, w, pools = self._run(params, bufs, ids, pools, table, lens,
                                pos_ids, self.chunk_tag, lora=lora)
        return (x.astype(jnp.float32), w.astype(jnp.float32)) + pools

    def step(self, params, bufs, last, *args):
        pools, table, lens, lora = self._split_extra(args)
        pos_ids = lens[:, None].astype(jnp.int64)
        x, w, pools = self._run(params, bufs, last, pools, table, lens,
                                pos_ids, self.tag, lora=lora)
        logits = x[:, -1].astype(jnp.float32) @ w.T.astype(jnp.float32)
        return (logits,) + pools

    def verify(self, params, bufs, ids, *args):
        """Multi-token verification step (speculative decoding): run
        ``ids [B, C]`` — each row the slot's last sampled token followed by
        C-1 draft tokens — at per-slot positions ``lens[b]..lens[b]+C-1``.
        All C K/V per slot are written into the global pools and attended
        against them in ONE call (the chunk cache variant), and logits
        come back for EVERY position: ``logits[b, t]`` is the next-token
        distribution after ``ids[b, :t+1]``, which is exactly what
        accepting/rejecting draft t+1 needs.

        Returns ``(logits [B, C, V] f32, *pools)``."""
        pools, table, lens, lora = self._split_extra(args)
        C = ids.shape[1]
        pos_ids = lens[:, None].astype(jnp.int64) \
            + jnp.arange(C, dtype=jnp.int64)[None, :]
        # clamp: rows shorter than the padded draft may reach past the
        # position table near the model cap; those positions' logits are
        # junk the engine never reads (draft lengths are capped host-side)
        pos_ids = jnp.minimum(pos_ids, self.max_model_len - 1)
        x, w, pools = self._run(params, bufs, ids, pools, table, lens,
                                pos_ids, self.chunk_tag, lora=lora)
        logits = x.astype(jnp.float32) @ w.T.astype(jnp.float32)
        return (logits,) + pools

    def prefill_chunk(self, params, bufs, ids, nvalid, *args):
        """One CHUNK of a long prompt's prefill: run ``ids [B, C]`` — the
        next C prompt tokens of each row, right-padded past ``nvalid[b]``
        — at per-slot positions ``lens[b]..lens[b]+C-1`` through the chunk
        cache variant (the verify machinery reused for prompt ingestion:
        within-chunk causality and the pool writes come for free), and
        return the next-token logits at each row's last REAL chunk lane
        ``nvalid[b] - 1``.  Pad-lane K/V lands past the row's valid length
        (or in dropped OOB lanes), invisible to seq_lens masking and
        overwritten by the next chunk/decode write — the
        paged_table_chunk_write contract.

        Only the FINAL chunk's logits are consumed (they seed decode);
        intermediate chunks exist for their pool writes.  Returns
        ``(logits [B, V] f32, *pools)`` — the prefill contract, so the
        engine's sampler/guard plumbing is shared."""
        pools, table, lens, lora = self._split_extra(args)
        C = ids.shape[1]
        pos_ids = lens[:, None].astype(jnp.int64) \
            + jnp.arange(C, dtype=jnp.int64)[None, :]
        pos_ids = jnp.minimum(pos_ids, self.max_model_len - 1)
        x, w, pools = self._run(params, bufs, ids, pools, table, lens,
                                pos_ids, self.chunk_tag, lora=lora)
        idx = jnp.maximum(nvalid.astype(jnp.int32) - 1, 0)[:, None, None]
        h = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        logits = h.astype(jnp.float32) @ w.T.astype(jnp.float32)
        return (logits,) + pools


class StatedCacheAdapter(PagedAdapter):
    """Adapter for a decoder that is not ``.gpt`` and states its caches:
    rotary positions (no position table: the model states the cap on
    ``max_model_len``), pages for the cache rows it names, and, for a
    hybrid, a fixed-size recurrent state a slot beside them
    (:class:`paddle_tpu.text.models.Lfm2MoeForCausalLM`: grouped KV heads
    in SOME layers, gated short convolutions in the others;
    :class:`paddle_tpu.text.models.OuroForCausalLM`: pages only, one row a
    (step, layer) of a stack that runs several times).  The engine builds
    it for a model that has ``serving_caches`` and takes no flag.

    What it asks of the model, and all it reads of it:

    - ``model.serving_caches()``: ``{"attention_layers", "kv_heads",
      "head_dim"}`` (the pages: ``attention_layers`` counts CACHE ROWS),
      ``"max_positions"`` and ``"dtype"``; optionally ``"state_shape"``
      (``(layers, rows, width)`` of the state ONE sequence carries) and
      ``"loop_steps"`` (how often the stack runs: a fact for the
      signature, the rows are counted already);
    - ``model.model`` is the decoder, called as ``decoder(ids, position_ids,
      cache=(tag, (kp, vp), table, lens))`` and returning ``(hidden, (kp,
      vp))``; with a state also ``conv_state=[L_state, B, R, W],
      valid=[B]``, returning the state after the call third;
    - ``model.head_weight()`` is the head as ``[V, H]``.

    The pool tuple is ``(kp, vp)`` or ``(kp, vp, state)``: ``kp`` / ``vp``
    ``[rows, P, ps, hkv, d]`` indexed by a cache row, ``state [L_state,
    slots + 1, R, W]`` by a state layer's rank and the slot; row ``slots``
    is the scratch row of idle lanes.  The state's rules (each pinned in
    ``tests/test_lfm2.py``):

    - a chunk that starts at position 0 (``lens[b] == 0``) and a monolithic
      prefill enter with ZERO state, whatever the slot's last tenant left;
    - a chunk (and a right-padded prompt) leaves the state at its row's
      last REAL lane (``nvalid`` / ``lens``), not at the pad;
    - a decode step shifts it by one; a lane with ``lens[b] == 0`` is idle
      (retired, or mid-prefill: its state is the chunk program's) and reads
      and writes only the scratch row;
    - nothing resets it on the host: preemption re-prefills.

    Its closures have :class:`GPTAdapter`'s shape and a text of their own
    (positions are rotary, the head is the model's, a state may enter and
    leave beside the pools): ROADMAP, Design 2.  ``verify`` and ``encode``
    exist for :class:`GPTAdapter` only (ROADMAP R7).
    """

    def __init__(self, model, page_size, num_slots):
        need = model.serving_caches()
        self.decoder = model.model
        self._set_geometry(
            model, page_size, num_layers=need["attention_layers"],
            num_kv_heads=need["kv_heads"], head_dim=need["head_dim"],
            dtype=need["dtype"], max_model_len=need["max_positions"])
        #: (layers, rows, width) of the state one sequence carries, or None
        shape = need.get("state_shape")
        self.state_shape = None if shape is None \
            else tuple(int(n) for n in shape)
        self.slot_state = self.state_shape is not None
        self.n_pools = 3 if self.slot_state else 2
        #: how often the decoder runs its layers (1: once)
        self.loop_steps = int(need.get("loop_steps", 1))
        self.num_slots = int(num_slots)

    def signature(self):
        sig = dict(super().signature(), cache_layers=int(self.num_layers),
                   loop_steps=int(self.loop_steps))
        if self.slot_state:
            sig.update(state_shape=list(self.state_shape),
                       num_slots=int(self.num_slots))
        return sig

    # ----------------------------------------------------------- pool hooks
    def init_pools(self, num_pages):
        """``(kp, vp)`` over the cache rows, and with a state ``(kp, vp,
        state)``: the zeroed per-slot state with its scratch row."""
        kp = self._page_pool(num_pages)
        if not self.slot_state:
            return kp, jnp.zeros_like(kp)
        layers, rows, width = self.state_shape
        state = jnp.zeros((layers, self.num_slots + 1, rows, width),
                          self.dtype)
        return kp, jnp.zeros_like(kp), state

    def pool_owners(self):
        if not self.slot_state:
            return super().pool_owners()
        return (("kv.pages", (0, 1)), ("state.slots", (2,)))

    def state_bytes_per_slot(self):
        if not self.slot_state:
            return 0
        layers, rows, width = self.state_shape
        return layers * rows * width * jnp.dtype(self.dtype).itemsize

    # ------------------------------------------------------------- closures
    def _split_extra(self, args):
        """``(*pools, table, lens[, slot])`` -> ``((kp, vp), state, table,
        lens, slot)``; ``state`` and ``slot`` are None without a state.
        With one, the decode program's rows are the slots already; a lane
        that holds nothing (``lens == 0``) goes to the scratch row."""
        n = self.n_pools
        takes = (n + 2, n + 3) if self.slot_state else (n + 2,)
        if len(args) not in takes:
            raise TypeError(
                f"{type(self).__name__} closures take {n} pool arrays + "
                f"table + lens" + (" (+ the slot's index)"
                                   if self.slot_state else "")
                + f"; got {len(args)} trailing args")
        table, lens = args[n], args[n + 1]
        if not self.slot_state:
            return tuple(args[:2]), None, table, lens, None
        if len(args) == n + 3:
            slot = args[n + 2].astype(jnp.int32)
        else:
            slot = jnp.where(lens > 0,
                             jnp.arange(lens.shape[0], dtype=jnp.int32),
                             jnp.int32(self.num_slots))
        return tuple(args[:2]), args[2], table, lens, slot

    def _run(self, params, bufs, ids, kv, entering, valid, table, lens,
             pos_ids, tag):
        """``(hidden, head [V, H], (kp, vp), the state after the call or
        None)``."""
        from ..framework import random as _rng
        from ..framework.state import no_grad_ctx
        from ..tensor.tensor import Tensor

        beside = {} if entering is None else {
            "conv_state": Tensor(entering),
            "valid": None if valid is None else Tensor(valid)}
        with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                self.model.bind(params, bufs):
            x, kv, *after = self.decoder(
                Tensor(ids), position_ids=Tensor(pos_ids),
                cache=(tag, tuple(Tensor(p) for p in kv), Tensor(table),
                       Tensor(lens)), **beside)
            w = self.model.head_weight()._value
            return x._value, w, tuple(p._value for p in kv), \
                (after[0]._value if after else None)

    @staticmethod
    def _logits(h, w):
        return h.astype(jnp.float32) @ w.T.astype(jnp.float32)

    @staticmethod
    def _leaving(kv, state, slot, after):
        """The pool tuple a closure hands back: the pages, and the state
        with the rows of ``slot`` as the call left them."""
        return kv if state is None else kv + (state.at[:, slot].set(after),)

    def prefill(self, params, bufs, ids, *args):
        kv, state, table, lens, slot = self._split_extra(args)
        pos_ids = jnp.arange(ids.shape[1], dtype=jnp.int64)[None, :]
        fresh = None if state is None else jnp.zeros_like(state[:, slot])
        x, w, kv, after = self._run(params, bufs, ids, kv, fresh, lens,
                                    table, lens, pos_ids, self.tag)
        idx = (lens.astype(jnp.int32) - 1)[:, None, None]
        h = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        return (self._logits(h, w),) + self._leaving(kv, state, slot, after)

    def step(self, params, bufs, last, *args):
        kv, state, table, lens, slot = self._split_extra(args)
        pos_ids = lens[:, None].astype(jnp.int64)
        entering = None if state is None else state[:, slot]
        x, w, kv, after = self._run(params, bufs, last, kv, entering, None,
                                    table, lens, pos_ids, self.tag)
        return (self._logits(x[:, -1], w),) \
            + self._leaving(kv, state, slot, after)

    def prefill_chunk(self, params, bufs, ids, nvalid, *args):
        kv, state, table, lens, slot = self._split_extra(args)
        C = ids.shape[1]
        pos_ids = lens[:, None].astype(jnp.int64) \
            + jnp.arange(C, dtype=jnp.int64)[None, :]
        pos_ids = jnp.minimum(pos_ids, self.max_model_len - 1)
        # a slot's first chunk starts a sequence: zero state, whatever the
        # last tenant left in the row
        entering = None if state is None else jnp.where(
            (lens > 0)[None, :, None, None], state[:, slot],
            jnp.zeros((), state.dtype))
        x, w, kv, after = self._run(params, bufs, ids, kv, entering, nvalid,
                                    table, lens, pos_ids, self.chunk_tag)
        idx = jnp.maximum(nvalid.astype(jnp.int32) - 1, 0)[:, None, None]
        h = jnp.take_along_axis(x, idx, axis=1)[:, 0]
        return (self._logits(h, w),) + self._leaving(kv, state, slot, after)
