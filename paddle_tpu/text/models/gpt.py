"""GPT decoder LM (reference analog: PaddleNLP gpt/modeling.py — baseline
config #5 trains GPT-3-style models under dp+mp+pp hybrid parallelism,
SURVEY.md §2.3/§3.4).

TPU-first structure:
- TP: when fleet's hybrid mesh has mp>1, projections build as
  Column/RowParallelLinear and the vocab embedding as
  VocabParallelEmbedding — distribution is sharding annotations, the
  module code is identical either way.
- PP: every decoder block is structurally identical, so the stacked block
  parameters feed the SPMD pipeline engine
  (``stack_block_params`` + ``pipeline_forward`` →
  fleet.meta_parallel.spmd_pipeline) for dp x mp x pp training in ONE
  compiled program.
- Long context: attention routes through
  nn.functional.scaled_dot_product_attention (flash/ring kernels pluggable
  via paddle_tpu.ops).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ...nn import functional as F
from ...nn.layer import Layer, LayerList
from ...nn.layers.common import Dropout, Embedding, Linear
from ...nn.layers.norm import LayerNorm
from ...ops.paged_attention import paged_cache_attend
from ...tensor.dispatch import apply as _apply
from ...tensor.tensor import Tensor


def _mp_degree():
    from ...distributed.topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    if hcg is not None and "mp" in hcg.mesh.axis_names:
        return hcg.mesh.shape["mp"]
    return 1


def _col_linear(d_in, d_out, bias=True):
    if _mp_degree() > 1:
        from ...distributed.fleet.meta_parallel import ColumnParallelLinear

        return ColumnParallelLinear(d_in, d_out, gather_output=False,
                                    has_bias=bias)
    return Linear(d_in, d_out, bias_attr=None if bias else False)


def _row_linear(d_in, d_out, bias=True):
    if _mp_degree() > 1:
        from ...distributed.fleet.meta_parallel import RowParallelLinear

        return RowParallelLinear(d_in, d_out, input_is_parallel=True,
                                 has_bias=bias)
    return Linear(d_in, d_out, bias_attr=None if bias else False)


def _vocab_embedding(vocab, hidden):
    if _mp_degree() > 1:
        from ...distributed.fleet.meta_parallel import VocabParallelEmbedding

        return VocabParallelEmbedding(vocab, hidden)
    return Embedding(vocab, hidden)


class GPTDecoderLayer(Layer):
    """Pre-LN causal block: ln1 -> attn -> +res -> ln2 -> mlp -> +res."""

    def __init__(self, hidden_size, num_heads, intermediate_size, dropout=0.0,
                 attn_dropout=0.0, act="gelu"):
        super().__init__()
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.head_dim = hidden_size // num_heads
        self.ln1 = LayerNorm(hidden_size, 1e-5)
        self.qkv = _col_linear(hidden_size, 3 * hidden_size)
        self.out_proj = _row_linear(hidden_size, hidden_size)
        self.ln2 = LayerNorm(hidden_size, 1e-5)
        self.ffn1 = _col_linear(hidden_size, intermediate_size)
        self.ffn2 = _row_linear(intermediate_size, hidden_size)
        self.dropout = Dropout(dropout)
        self.attn_dropout = attn_dropout
        self.act = getattr(F, act)

    def _lin(self, name, x, lora):
        """One decoder Linear call with an optional per-row LoRA bypass.

        ``lora`` is this layer's multi-tenant adapter slice (or None): a
        dict mapping target name -> flat tuple of per-row gathered
        ``(A [B, d_in, r], B [B, r, d_out])`` pairs, one pair per rank
        bucket (serving.multitenant; ops.lora).  The base projection may
        be an Int8Linear (weight_dtype="int8") — the bypass rides on its
        output either way, which is exactly how int8 base + full-precision
        LoRA compose."""
        y = getattr(self, name)(x)
        if lora is not None and name in lora:
            from ...ops.lora import apply_lora

            y = _apply(apply_lora, x, y, *lora[name], op_name="lora")
        return y

    def forward(self, x, cache=None, lora=None):
        residual = x
        h = self.ln1(x)
        qkv = self._lin("qkv", h, lora)
        B, S = h.shape[0], h.shape[1]
        # head count derived from the actual projection width: under manual
        # tensor parallelism the local shard carries num_heads/mp heads.
        # qkv output layout is HEAD-MAJOR [heads, 3, head_dim] so a contiguous
        # column split over 'mp' hands each rank whole (q,k,v) heads.
        heads_here = qkv.shape[-1] // (3 * self.head_dim)
        qkv = qkv.reshape([B, S, heads_here, 3, self.head_dim])
        q, k, v = qkv[:, :, :, 0], qkv[:, :, :, 1], qkv[:, :, :, 2]
        if cache is not None and len(cache) == 5:
            # PAGED cache (the serving engine's; generate(cache_impl=
            # "paged") with an identity page table): ``(tag, layer, pools,
            # table, lens)``.  The pool tuple holds every layer and this
            # layer is an index into it: K/V are written and attended where
            # they lie and the tuple goes on to the next layer.  What a
            # paged cache is, is ops.paged_attention's business; an
            # admit-time prompt attends densely, nothing being cached yet.
            attn, cache = paged_cache_attend(
                q, k, v, cache,
                lambda q, k, v: F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, dropout_p=0.0, training=False))
        elif cache is not None and len(cache) == 3:
            # STATIC cache (jitted decode): fixed [B, T, h, d] buffers written
            # in place at ``pos`` — shapes never change, so every decode step
            # reuses one compiled program (donated cache, no concat growth)
            k_buf, v_buf, pos = cache

            def write(buf, new, p):
                return jax.lax.dynamic_update_slice_in_dim(buf, new, p, 1)

            k_buf = _apply(write, k_buf, k, pos, op_name="cache_write")
            v_buf = _apply(write, v_buf, v, pos, op_name="cache_write")
            T = k_buf.shape[1]

            def build_mask(p):
                i = jnp.arange(S, dtype=jnp.int32)[:, None]
                j = jnp.arange(T, dtype=jnp.int32)[None, :]
                return jnp.where(j <= p + i, jnp.float32(0.0),
                                 jnp.float32(-1e30))[None, None]

            mask = _apply(build_mask, pos, op_name="cache_mask")
            attn = F.scaled_dot_product_attention(
                q, k_buf, v_buf, attn_mask=mask, dropout_p=0.0,
                training=False)
            cache = (k_buf, v_buf, pos)
        else:
            if cache is not None:
                from ...tensor import manipulation as M

                k = M.concat([cache[0], k], axis=1)
                v = M.concat([cache[1], v], axis=1)
                cache = (k, v)
            attn = F.scaled_dot_product_attention(
                q, k, v, is_causal=cache is None,
                dropout_p=self.attn_dropout, training=self.training)
        attn = attn.reshape([B, S, heads_here * self.head_dim])
        x = residual + self.dropout(self._lin("out_proj", attn, lora))
        residual = x
        h = self.ln2(x)
        h = self._lin("ffn2", self.act(self._lin("ffn1", h, lora)), lora)
        x = residual + self.dropout(h)
        return x if cache is None else (x, cache)


class GPTModel(Layer):
    def __init__(self, vocab_size=50304, hidden_size=768, num_hidden_layers=12,
                 num_attention_heads=12, intermediate_size=None,
                 hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
                 max_position_embeddings=1024, type_vocab_size=1,
                 initializer_range=0.02, pad_token_id=0, hidden_act="gelu"):
        super().__init__()
        intermediate_size = intermediate_size or 4 * hidden_size
        self.hidden_size = hidden_size
        self.word_embeddings = _vocab_embedding(vocab_size, hidden_size)
        self.position_embeddings = Embedding(max_position_embeddings, hidden_size)
        self.drop = Dropout(hidden_dropout_prob)
        self.layers = LayerList([
            GPTDecoderLayer(hidden_size, num_attention_heads, intermediate_size,
                            hidden_dropout_prob, attention_probs_dropout_prob,
                            hidden_act)
            for _ in range(num_hidden_layers)
        ])
        self.final_ln = LayerNorm(hidden_size, 1e-5)

    def embed(self, input_ids, position_ids=None):
        if position_ids is None:
            S = input_ids.shape[1]
            position_ids = Tensor(jnp.arange(S, dtype=jnp.int64)[None, :])
        return self.drop(self.word_embeddings(input_ids)
                         + self.position_embeddings(position_ids))

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                use_cache=False, cache=None, lora=None):
        # ``lora``: per-layer multi-tenant adapter slices (see
        # GPTDecoderLayer._lin / paddle_tpu.serving.multitenant) — a list
        # of per-layer dicts, or None for the base model
        # ``cache``: a list of per-layer caches, or ONE paged cache
        # ``(tag, pools, table, lens)`` whose stacked pools every layer
        # reads and writes at its own index (GPTDecoderLayer's paged
        # branch): the pool tuple is threaded through the layers and what
        # the last one returns comes back in the cache's place
        x = self.embed(input_ids, position_ids)
        served = isinstance(cache, tuple)
        new_cache = cache[1] if served else []
        for i, layer in enumerate(self.layers):
            li = lora[i] if lora is not None else None
            if served:
                x, new_cache = layer(
                    x, (cache[0], i, new_cache) + cache[2:], lora=li)
            elif cache is not None:
                x, c = layer(x, cache[i], lora=li)
                new_cache.append(c)
            else:
                x = layer(x, lora=li)
        x = self.final_ln(x)
        return (x, new_cache) if cache is not None else x


@jax.named_scope("lm_head_loss")
def _lm_head_loss(hidden, w, labels):
    """Next-token loss through the head tied to the embedding ``w``
    [vocab, hidden].  The scope names the vocabulary-wide product and the
    loss, forward and backward, in a device trace."""
    logits = _apply(lambda h, wv: h @ wv.T, hidden, w, op_name="matmul")
    return F.cross_entropy(
        logits[:, :-1].reshape([-1, logits.shape[-1]]),
        labels[:, 1:].reshape([-1]), reduction="mean")


class GPTForCausalLM(Layer):
    """LM head tied to the vocab embedding (reference GPTForCausalLM /
    GPTLMHeadModel)."""

    def __init__(self, gpt=None, **kwargs):
        super().__init__()
        self.gpt = gpt if gpt is not None else GPTModel(**kwargs)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                labels=None):
        hidden = self.gpt(input_ids, position_ids, attention_mask)
        w = self.gpt.word_embeddings.weight  # [vocab, hidden]
        if labels is None:
            return _apply(lambda h, wv: h @ wv.T, hidden, w, op_name="matmul")
        return _lm_head_loss(hidden, w, labels)

    # ------------------------------------------------------------ generation
    def generate(self, input_ids, max_new_tokens=32, temperature=1.0, top_k=0,
                 top_p=1.0, seed=None, use_cache=True,
                 decode_strategy="sampling", num_beams=4, length_penalty=0.0,
                 eos_token_id=None, cache_impl="dense", page_size=16,
                 max_len=None):
        """Autoregressive generation.

        ``use_cache=True`` (default): jitted two-phase decode via the shared
        decode loop (``_decode.jitted_decode``) — one compiled prefill
        writes the prompt's K/V into fixed [B, T, h, d] buffers, then ONE
        compiled single-token step (donated cache, static shapes) runs per
        new token.  Greedy (temperature=0) output is identical to the eager
        loop; sampling supports temperature/top-k/top-p via jax PRNG.
        ``use_cache=False``: the eager full-prefix loop (reference parity /
        debug path).

        ``cache_impl="paged"``: block-paged KV cache — page pools (every
        layer stacked in one array) instead of dense [B, T] rectangles, on
        the serving engine's cache contract with an identity page table:
        decode attention through the length-bounded Pallas decode kernel
        (ops/paged_attention), where each row's page sweep stops at its own
        last valid page.  Same tokens as the dense path
        (tests/test_paged_attention.py); KV HBM is bounded by pages
        allocated (ceil(T/page_size) per sequence), the serving property
        the reference's paged engine exists for."""
        if decode_strategy == "beam_search":
            from ._decode import beam_search

            return beam_search(self, input_ids, max_new_tokens,
                               num_beams=num_beams,
                               length_penalty=length_penalty,
                               eos_token_id=eos_token_id)
        if not use_cache:
            return self._generate_eager(input_ids, max_new_tokens, temperature,
                                        top_k, top_p, seed)
        if max_new_tokens <= 0:
            return input_ids
        import jax
        import numpy as np

        from ...framework import random as _rng
        from ...framework.state import no_grad_ctx
        from ._decode import jitted_decode

        ids0 = np.asarray(input_ids.numpy()).astype("int64")
        B, S0 = ids0.shape
        # max_len pre-sizes the KV cache/page pool independently of this
        # call's max_new_tokens (serving: one compiled step serves requests
        # of any length up to it; bench: pins compiled shapes across runs)
        T = max(S0 + max_new_tokens, max_len or 0)
        max_pos = self.gpt.position_embeddings.weight.shape[0]
        if T > max_pos:
            raise ValueError(
                f"generate: prompt {S0} + max_new_tokens {max_new_tokens} "
                f"(cache {T}) exceeds max_position_embeddings {max_pos}")
        gpt = self.gpt
        L = len(gpt.layers)
        blk = gpt.layers[0]
        h_heads = blk.qkv.weight.shape[-1] // (3 * blk.head_dim)
        dt = gpt.word_embeddings.weight._value.dtype

        if cache_impl == "paged":
            from ._decode import decode_loop, paged_cache, paged_pool_shape

            pool = (L,) + paged_pool_shape(B, T, h_heads, blk.head_dim,
                                           page_size)

            def fwd_paged(params, bufs, ids, pools, pos):
                with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                        self.bind(params, bufs):
                    S = ids.shape[1]
                    pos_ids = pos + jnp.arange(S, dtype=jnp.int32)[None, :]
                    x, pools = gpt(Tensor(ids), position_ids=Tensor(pos_ids),
                                   cache=paged_cache(pools, B, pos))
                    w = gpt.word_embeddings.weight._value
                    logits = (x._value[:, -1].astype(jnp.float32)
                              @ w.T.astype(jnp.float32))
                return logits, tuple(p._value for p in pools)

            def init_cache():
                kp = jnp.zeros(pool, dt)
                return kp, jnp.zeros_like(kp)

            return decode_loop(self, fwd_paged, ids0, max_new_tokens,
                               init_cache, temperature=temperature,
                               top_k=top_k, top_p=top_p, seed=seed,
                               program_key=("paged", B, S0, T, page_size,
                                            temperature, top_k, top_p,
                                            bool(self.training)))
        if cache_impl != "dense":
            raise ValueError(f"cache_impl must be 'dense' or 'paged', "
                             f"got {cache_impl!r}")

        def fwd(params, bufs, ids, ks, vs, pos):
            with no_grad_ctx(), _rng.rng_scope(jax.random.key(0)), \
                    self.bind(params, bufs):
                S = ids.shape[1]
                pos_ids = pos + jnp.arange(S, dtype=jnp.int32)[None, :]
                cache = [(Tensor(ks[i]), Tensor(vs[i]), Tensor(pos))
                         for i in range(L)]
                x, new_cache = gpt(Tensor(ids), position_ids=Tensor(pos_ids),
                                   cache=cache)
                w = gpt.word_embeddings.weight._value
                logits = (x._value[:, -1].astype(jnp.float32)
                          @ w.T.astype(jnp.float32))
                ks = jnp.stack([c[0]._value for c in new_cache])
                vs = jnp.stack([c[1]._value for c in new_cache])
            return logits, ks, vs

        return jitted_decode(self, fwd, ids0, max_new_tokens,
                             (L, B, T, h_heads, blk.head_dim), dt,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, seed=seed,
                             program_key=("dense", B, S0, T, temperature,
                                          top_k, top_p, bool(self.training)))

    def _generate_eager(self, input_ids, max_new_tokens=32, temperature=1.0,
                        top_k=0, top_p=1.0, seed=None):
        """Greedy/top-k sampling loop (eager; each step reuses the jit cache
        for its shape)."""
        import numpy as np

        ids = input_ids.numpy()
        max_pos = self.gpt.position_embeddings.weight.shape[0]
        if ids.shape[1] + max_new_tokens > max_pos:
            raise ValueError(
                f"generate: prompt {ids.shape[1]} + max_new_tokens {max_new_tokens} "
                f"exceeds max_position_embeddings {max_pos}")
        rng = np.random.RandomState(seed)
        for _ in range(max_new_tokens):
            logits = self.forward(Tensor(jnp.asarray(ids)))
            step = np.asarray(logits.numpy()[:, -1])
            if temperature != 1.0:
                step = step / max(temperature, 1e-6)
            if top_k:
                kk = min(int(top_k), step.shape[-1])
                kth = np.sort(step, axis=-1)[:, -kk][:, None]
                step = np.where(step < kth, -np.inf, step)
            if temperature == 0.0:
                nxt = step.argmax(-1)
            else:
                p = np.exp(step - step.max(-1, keepdims=True))
                p /= p.sum(-1, keepdims=True)
                if top_p < 1.0:  # nucleus: smallest prefix >= top_p
                    srt = np.argsort(-p, axis=-1)
                    ps = np.take_along_axis(p, srt, -1)
                    keep = np.cumsum(ps, -1) - ps < top_p
                    ps = np.where(keep, ps, 0.0)
                    ps = ps / ps.sum(-1, keepdims=True)
                    pick = np.stack([rng.choice(ps.shape[-1], p=ps[i])
                                     for i in range(ps.shape[0])])
                    nxt = np.take_along_axis(srt, pick[:, None], -1)[:, 0]
                else:
                    nxt = np.array([rng.choice(p.shape[-1], p=p[i])
                                    for i in range(p.shape[0])])
            ids = np.concatenate([ids, nxt[:, None]], axis=1)
        return Tensor(jnp.asarray(ids))


# ---------------------------------------------------------------- pipeline
# TP placement of each block parameter inside the manual pipeline region:
# which dim of the RAW weight is sharded over 'mp' (None = replicated).
_TP_DIM = {
    "qkv.weight": 1, "qkv.bias": 0,
    "ffn1.weight": 1, "ffn1.bias": 0,
    "out_proj.weight": 0, "ffn2.weight": 0,
}


def mp_param_specs(axis="model"):
    """Suffix -> ``PartitionSpec`` map for Megatron-style tensor
    parallelism of a decoder block's parameters over one mesh axis —
    the serving-side reading of :data:`_TP_DIM` (qkv/ffn1
    column-parallel, out_proj/ffn2 row-parallel).  The qkv projection is
    HEAD-MAJOR (``[heads, 3, head_dim]`` flattened), so a contiguous
    column split hands each shard whole (q, k, v) head triples — the
    layout the per-shard paged KV pools line up with.

    Keys are dotted-name suffixes (match with ``name.endswith``), so one
    map covers every layer of ``named_parameters()``.  ``weight_int8``
    buffers (quantization.Int8Linear payloads) shard exactly like the
    full-precision weights they replace; anything unmatched (embeddings,
    LayerNorms, the row-parallel biases) is replicated.
    """
    from jax.sharding import PartitionSpec as P

    specs = {}
    for name, dim in _TP_DIM.items():
        ndim = 2 if name.endswith(".weight") else 1
        entries = [None] * ndim
        entries[dim] = axis
        specs["." + name] = P(*entries)
        if name.endswith(".weight"):
            specs["." + name + "_int8"] = P(*entries)
    return specs


def stack_block_params(model: GPTModel, pp: int, order="stage"):
    """Stack the (structurally identical) decoder blocks' parameters into
    [pp, layers_per_stage, ...] pytrees for the SPMD pipeline engine.
    ``order='stage'`` places layer j at [j // per, j % per] (contiguous
    chunks per rank — the gpipe schedule); ``order='lap'`` places layer j
    at [j % pp, j // pp] (round-robin virtual stages — what the circular /
    interleaved schedule executes lap-major).
    Returns (stacked, specs): specs shard the stage dim over 'pp' and the
    TP dim (per _TP_DIM) over 'mp' when the model was built tensor-parallel."""
    from jax.sharding import PartitionSpec as P

    n = len(model.layers)
    if n % pp:
        raise ValueError(f"{n} layers not divisible by pp={pp}")
    per = n // pp
    names = [k for k, _ in model.layers[0].named_parameters()]
    mp = _mp_degree()
    stacked, specs = {}, {}
    for name in names:
        leaves = []
        for layer in model.layers:
            p = dict(layer.named_parameters())[name]
            leaves.append(p._value)
        arr = jnp.stack(leaves)  # [n_layers, ...]
        if order == "lap":
            stacked[name] = arr.reshape((per, pp) + arr.shape[1:]).swapaxes(0, 1)
        else:
            stacked[name] = arr.reshape((pp, per) + arr.shape[1:])
        entries = ["pp", None] + [None] * (arr.ndim - 1)
        tp_dim = _TP_DIM.get(name)
        if mp > 1 and tp_dim is not None:
            entries[2 + tp_dim] = "mp"
        specs[name] = P(*entries)
    return stacked, specs


def block_fn_for(model: GPTModel):
    """(stage_params, x) -> x for spmd_pipeline: runs layers_per_stage blocks
    sequentially, binding each slice into block 0's module structure."""
    block = model.layers[0]

    def block_fn(stage_params, x):
        per = next(iter(stage_params.values())).shape[0]
        h = x
        for i in range(per):
            sl = {k: v[i] for k, v in stage_params.items()}
            with block.bind(sl, {}):
                h = block(Tensor(h))._value if not isinstance(h, Tensor) else \
                    block(h)
        return h._value if isinstance(h, Tensor) else h

    return block_fn


def single_block_fn_for(model: GPTModel):
    """(one-layer params, x) -> x — the per-VIRTUAL-stage body the circular
    (interleaved) schedule calls once per lap."""
    block = model.layers[0]

    def block_fn(stage_params, x):
        with block.bind(stage_params, {}):
            return block(Tensor(x))._value

    return block_fn


class GPTForCausalLMPipe(Layer):
    """GPTForCausalLM with the decoder stack run through the SPMD pipeline
    engine (reference analog: PaddleNLP's GPTForCausalLMPipe built on
    PipelineLayer).  Embedding + head stay partitioner-sharded; blocks run
    manual pp (x mp x dp)."""

    def __init__(self, lm: "GPTForCausalLM" = None, mesh=None, n_micro=1,
                 batch_axis=None, schedule=None, **kwargs):
        super().__init__()
        self.lm = lm if lm is not None else GPTForCausalLM(**kwargs)
        if mesh is None:
            from ...distributed.topology import get_hybrid_communicate_group

            hcg = get_hybrid_communicate_group()
            mesh = hcg.mesh if hcg is not None else None
        if mesh is None:
            raise ValueError("GPTForCausalLMPipe needs a mesh (fleet.init first)")
        if schedule is None:
            # reference contract: with strategy.pipeline ENABLED,
            # pipeline_configs['schedule_mode'] selects the schedule
            # ('F-then-B'/'1F1B'/'Interleave'); otherwise gpipe
            schedule = "gpipe"
            try:
                from ...distributed import fleet as _fleet

                st = _fleet.get_strategy()
                if st is not None and getattr(st, "pipeline", False):
                    mode = str(st.pipeline_configs.get(
                        "schedule_mode", "1F1B")).strip().lower()
                    table = {"1f1b": "1f1b", "interleave": "interleaved",
                             "interleaved": "interleaved",
                             "f-then-b": "gpipe", "gpipe": "gpipe"}
                    if mode not in table:
                        import warnings

                        warnings.warn(
                            f"unknown pipeline schedule_mode {mode!r}; "
                            "falling back to gpipe (F-then-B)")
                    schedule = table.get(mode, "gpipe")
            except ImportError:  # fleet not importable: single-process use
                pass
        self._mesh = mesh
        self._n_micro = n_micro
        self._batch_axis = batch_axis
        self._schedule = schedule

    def forward(self, input_ids, labels=None):
        hidden = pipeline_forward(self.lm.gpt, input_ids, self._mesh,
                                  self._n_micro, axis="pp",
                                  batch_axis=self._batch_axis,
                                  schedule=self._schedule)
        w = self.lm.gpt.word_embeddings.weight
        mp = dict(zip(self._mesh.axis_names, self._mesh.devices.shape)).get("mp", 1)
        if labels is not None and mp > 1:
            # vocab-sharded head + CE: the [B, S, V] logits tensor never
            # materializes per rank (c_softmax_with_cross_entropy analog)
            from ...distributed.fleet.meta_parallel.mp_layers import (
                sharded_vocab_head_loss)

            return sharded_vocab_head_loss(hidden, w, labels, self._mesh,
                                           batch_axis=self._batch_axis)
        if labels is None:
            return _apply(lambda h, wv: h @ wv.T, hidden, w, op_name="matmul")
        return _lm_head_loss(hidden, w, labels)


def pipeline_forward(model: GPTModel, input_ids, mesh, n_micro, axis="pp",
                     batch_axis=None, schedule="gpipe"):
    """Full GPT forward with the decoder stack pipelined over ``axis``:
    embed (all ranks, partitioner-sharded) -> spmd_pipeline(blocks, manual
    pp x mp x dp) -> final_ln.  input_ids: [B, S]; B divides into n_micro
    micro-batches.  ``schedule='interleaved'`` runs the circular virtual-
    stage schedule (layer j on rank j % pp), shrinking the fill/drain bubble
    by ~layers_per_stage."""
    from ...distributed.fleet.meta_parallel import spmd_pipeline

    pp = mesh.shape[axis]
    order = "lap" if schedule == "interleaved" else "stage"
    stacked, specs = stack_block_params(model, pp, order=order)
    x = model.embed(input_ids)
    B = x.shape[0]
    micro = B // n_micro
    xm = x._value.reshape((n_micro, micro) + tuple(x.shape[1:]))
    fn = single_block_fn_for(model) if schedule == "interleaved" \
        else block_fn_for(model)
    out = spmd_pipeline(fn, stacked, xm, mesh, axis=axis,
                        batch_axis=batch_axis, param_specs=specs,
                        schedule=schedule)
    out = out.reshape((B,) + tuple(x.shape[1:]))
    return model.final_ln(Tensor(out))
