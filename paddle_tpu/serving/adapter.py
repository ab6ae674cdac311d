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
block specs take the layer as a leading block dimension of one, the writer
merges a chunk's rows into the pages it touches through aliased operands)
and hands the tuple to the next: no program slices a layer out of a pool
or stacks layers back.  On the TPU a payload pool's rows are as wide as
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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


class GPTAdapter:
    """Adapter for :class:`paddle_tpu.text.models.GPTForCausalLM` (and any
    model exposing the same ``.gpt`` decoder structure over the paged
    cache).  Subclasses override the pool hooks (``init_pools``,
    ``page_bytes``, ``pool_owners``, ``pool_pspecs``) to change the KV
    storage format without touching the closure shapes: the cache seam
    (``ops.paged_attention.paged_cache_attend``) tells the formats apart
    by the pool tuple it is handed."""

    #: paged-cache tags this adapter drives (``paged_cache_attend``): one
    #: token or a whole prompt per slot, and a chunk at the slot's own
    #: position
    tag = "served"
    chunk_tag = "served_chunk"
    #: number of arrays in the pool tuple (the engine donates all of them)
    n_pools = 2
    #: storage format label ("native" = the model dtype)
    kv_dtype = "native"

    def __init__(self, model, page_size=16):
        self.model = model
        self.gpt = model.gpt
        blk = self.gpt.layers[0]
        self.num_layers = len(self.gpt.layers)
        self.head_dim = blk.head_dim
        # local head count from the actual projection width (TP-safe); an
        # int8-weight model (serving.quant.quantize_model_weights) stores
        # the projection as an Int8Linear whose weight lives in the
        # ``weight_int8`` buffer — same shape, different attribute
        qkv_w = getattr(blk.qkv, "weight", None)
        if qkv_w is None:
            qkv_w = blk.qkv.weight_int8
        self.num_kv_heads = qkv_w.shape[-1] // (3 * blk.head_dim)
        self.dtype = self.gpt.word_embeddings.weight._value.dtype
        self.max_model_len = self.gpt.position_embeddings.weight.shape[0]
        self.page_size = int(page_size)

    #: set by ServingEngine(mesh=...) — the jax Mesh whose "model" axis the
    #: pools/weights are sharded over (None = single-device serving).  The
    #: TPU flash kernels consult it at trace time (mp_shard_scope) so each
    #: shard's Pallas page sweep covers only its local KV heads.
    mp_mesh = None
    mp_axis = "model"

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
        """Zeroed K/V pools ``(kp, vp)``, each [L, P, ps, h, d]: every
        layer's pages in one array, ``d`` the head size in whole lanes."""
        from ..ops.paged_attention import pool_lane_dim

        shape = (self.num_layers, int(num_pages), self.page_size,
                 self.num_kv_heads, pool_lane_dim(self.head_dim))
        kp = jnp.zeros(shape, self.dtype)
        return kp, jnp.zeros_like(kp)

    def page_bytes(self):
        """HBM bytes ONE page costs across all layers, K and V (the unit
        BlockManager capacity math and the serving.kv_bytes_per_token
        gauge are denominated in)."""
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
