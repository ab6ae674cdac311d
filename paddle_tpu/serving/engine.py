"""Continuous-batching LLM serving engine (Orca iteration-level scheduling
x vLLM paged KV blocks, TPU-native).

One fixed-shape batch of ``num_slots`` decode slots runs against per-layer
GLOBAL page pools; a background scheduler thread executes iterations:

1. retire slots that hit EOS / max_new_tokens / deadline / cancellation
   (pages return to the :class:`~.block_manager.BlockManager` immediately);
2. admit waiting prompts into free slots while the page pool can cover
   their worst case (prompt + max_new) — each admit runs one compiled
   prefill that writes the prompt's K/V into its pages and samples the
   first token;
3. run ONE compiled decode step for the whole batch — every slot at its
   OWN position (per-slot lens / page table rows), inactive slots pointed
   at a scratch page.  The loop runs one step AHEAD of the host: a turn
   enqueues its programs (the step's ``last`` is the previous step's
   sampled ids, still on the device) and then reads back, emits and
   retires what the turn BEFORE left, while the device computes.  What
   the host knows ahead it acts on ahead (a lane whose budget is
   dispatched to the end leaves its lane and frees its pages at once);
   an EOS hit, a cancel, a deadline or a non-finite row is found one step
   late, and the token computed meanwhile is dropped by the slot's
   generation.  An engine whose next step's inputs are made on the host
   from this step's token (speculative drafts, grammar masks) reads every
   dispatch back at once, through the same loop.

No caller ever waits for the slowest sequence in the batch: a short
request retires and its slot backfills from the queue while long ones keep
decoding.  The compiled programs follow the ``_decode.py`` discipline —
pools are DONATED into each call and the jitted prefill/step pair is
cached in :func:`~paddle_tpu.text.models._decode.program_store`, so there
is exactly ONE trace per (model, batch-shape, sampler) tuple; trace
counters are exported so tests can assert it.

Observability (PR-1 metrics registry): ``serving.ttft_seconds``,
``serving.inter_token_seconds``, ``serving.step_seconds``,
``serving.prefill_seconds`` histograms; ``serving.queue_depth``,
``serving.active_slots``, ``serving.slot_occupancy``,
``serving.page_utilization``, ``serving.pages_in_use`` gauges;
``serving.requests{status=...}``, ``serving.tokens_generated``,
``serving.admissions_blocked``, ``serving.preemptions``,
``serving.step_traces``, ``serving.prefill_traces`` counters;
``serving.decode_batch_size`` and ``serving.step_page_utilization``
histograms, observed once per decode dispatch (a window's mean).  The
scheduler thread's turns are spans of ``observability.tracing`` (README
"Distributed tracing & forensics" names them), in any ``jax.profiler``
trace too.

Speculative decoding (``speculative_k > 0``, see ``serving/speculative.py``
and README "Speculative decoding"): each iteration drafts up to k tokens
per slot by n-gram suffix match over the slot's own context (prompt-lookup
— no second model) and verifies them in ONE compiled multi-token step (the
``("verify", k_pad, …)`` program family; K/V for all k+1 positions lands
in the page pools through ``ops.paged_attention.paged_cache_attend``: one
chunk write, one chunk attention).  The scheduler consumes the longest accepted
prefix plus the bonus token — 1..k+1 tokens per dispatch — with EOS /
deadline / cancel / budget checks per emitted token.  Greedy rows accept
by exact argmax match, so greedy output is byte-identical to the
non-speculative engine; temperature rows use standard rejection sampling.
Extra metrics: ``serving.spec_proposed``, ``serving.spec_accepted``,
``serving.acceptance_rate`` (also on /statusz), ``serving.verify_traces``.

Resilience (PR-4, README "Resilience & fault tolerance"): a health state
machine (healthy → degraded → draining) surfaced on /healthz and /statusz;
deadline-aware load shedding at submit with distinct rejection reasons
(``RequestRejectedError.reason``); transient scheduler failures trigger an
engine auto-restart that rebuilds the page pools and transparently
re-queues in-flight requests (prompt + tokens-so-far, remaining budget)
instead of failing their handles; ``stop()`` without ``drain=True`` fails
in-flight handles fast with :class:`EngineStoppedError`; ``stop(drain=
True)`` finishes all in-flight work first.  Extra metrics:
``serving.load_shed{reason=}``, ``serving.engine_restarts``,
``serving.requests_requeued``, ``serving.health_state``.

Quantized serving (``kv_dtype="int8"`` / ``weight_dtype="int8"``, see
``serving/quant`` and README "Quantized serving"): the paged KV pools
store int8 payloads with parallel per-(page slot, head) float32 scale
pools — quant fused into every pool write, dequant into the paged
attention kernels, so decode streams half the bf16 cache bytes and the
same HBM budget holds ~2x the resident slots; the model's Linears can
ride along as :class:`~paddle_tpu.quantization.Int8Linear`.  The engine
is layout-agnostic: the adapter defines the pool tuple, every compiled
program donates all of it, and the quantized program families are
attributed separately (``decode@int8`` etc.) in the perf table.  Extra
metrics: ``serving.kv_bytes_per_token``, ``serving.pool_bytes{dtype=}``.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import itertools
import logging
import os
import queue as _queue
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..observability import faults as _faults
from ..observability import memory as _obs_memory
from ..observability import numerics as _numerics
from ..observability import perf as _perf
from ..observability import programs as _programs
from ..observability import tracing as _tracing
from ..resilience.retry import (EngineStoppedError, NumericFault,  # noqa: F401 — re-exported
                                classify_failure)
from .adapter import GPTAdapter, StatedCacheAdapter
from .block_manager import BlockManager

_logger = logging.getLogger("paddle_tpu.serving")

_HEALTH_CODE = {"healthy": 0, "degraded": 1, "draining": 2, "stopped": 3,
                "error": 4}

# prefill bucketing: prompts up to this many pages compile one prefill
# program per page count; above it, page counts round up to the next power
# of two so long-prompt traffic stops minting a program per page increment
_PREFILL_POW2_PAGES = 4

#: the mesh axis tensor-parallel serving shards over (pool KV-head dim,
#: Megatron weight splits) — the Fleet mp axis name, serving-side
_MP_AXIS = "model"


def _normalize_mesh(mesh):
    """``ServingEngine(mesh=...)`` input -> ``(jax Mesh | None, mp)``.

    Accepts a :class:`jax.sharding.Mesh` with a ``"model"`` axis, a
    :class:`paddle_tpu.distributed.ProcessMesh` carrying a ``"model"``
    dim, or a flat sequence of devices (meshed over one ``"model"``
    axis).  A 1-sized model axis degrades to unsharded serving on that
    single device (mp=1, plain ``device=`` placement) so a dp pool over
    mp-sized submeshes handles ``mp=1`` carves uniformly.  Returns
    ``(mesh, mp, solo_device)``."""
    if mesh is None:
        return None, 1, None
    if hasattr(mesh, "jax_mesh"):        # distributed ProcessMesh
        if _MP_AXIS not in mesh.dim_names:
            raise ValueError(
                f"ProcessMesh {mesh!r} has no '{_MP_AXIS}' dim — serving "
                f"tensor parallelism shards over a '{_MP_AXIS}' axis")
        mesh = mesh.jax_mesh
    if isinstance(mesh, jax.sharding.Mesh):
        if _MP_AXIS not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} carry no '{_MP_AXIS}' axis "
                f"— serving tensor parallelism shards over '{_MP_AXIS}'")
        mp = int(mesh.shape[_MP_AXIS])
        devs = list(mesh.devices.flat)
    else:                                # flat device sequence
        devs = list(mesh)
        if not devs:
            raise ValueError("mesh= device list must be non-empty")
        mp = len(devs)
        mesh = jax.sharding.Mesh(np.array(devs), (_MP_AXIS,))
    if mp > 1:
        return mesh, mp, None
    return None, 1, devs[0]


class RequestRejectedError(RuntimeError):
    """Raised by submit() for requests the engine can never serve (too long
    for the model/page pool) or that are load-shed.  ``reason`` is the
    machine-readable rejection class: ``unservable`` (exceeds model/pool
    caps), ``queue_full``, ``deadline_unmeetable`` (the request's deadline
    cannot be met given current queue/stall state), or ``draining`` (the
    engine is shutting down gracefully)."""

    def __init__(self, message, reason="rejected"):
        super().__init__(message)
        self.reason = reason


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling.  ``temperature <= 0`` is greedy; temperature
    rows and greedy rows share ONE compiled step (the batched sampler
    branches per slot).  top_k/top_p are engine-level statics — part of the
    compiled program key, not per-request."""

    temperature: float = 0.0
    seed: int | None = None  # reserved; draws come from the engine stream


@dataclasses.dataclass
class Request:
    prompt: list
    max_new_tokens: int
    sampling: SamplingParams
    eos_token_id: int | None
    deadline: float | None      # absolute time.time() seconds
    handle: "RequestHandle"
    # multi-tenant serving (serving/multitenant; every field defaults to
    # the single-tenant base-model request, so the plain engine's paths
    # are untouched): the tenant's registered LoRA adapter name, the
    # compiled token-FSM constraining this row's output, the request kind
    # (generate | embed | score), the embed pooling, and the store lease
    # held while the request is admitted
    adapter: str | None = None
    grammar: object = None
    mode: str = "generate"
    pooling: str = "mean"
    lease: object = None
    # QoS tier name (serving/qos.py) — None on engines without a tier
    # table; resolved to a configured tier at submit on QoS engines, and
    # carried verbatim across requeues (restart recovery / preemption)
    tier: str | None = None


class RequestHandle:
    """Caller-side view of a submitted request.

    ``result(timeout)`` blocks for the generated ids; ``stream()`` yields
    tokens as the engine produces them (closing the iterator cancels the
    request and frees its pages); ``cancel()`` retires it at the next
    iteration."""

    def __init__(self, request_id, prompt_len):
        self.request_id = request_id
        self.prompt_len = prompt_len
        # multi-tenant surface: request kind, the non-generate result
        # payload (embed vector / score list), the tenant's adapter name,
        # and the constrained row's live FSM state (kept on the HANDLE so
        # an engine restart's re-admission resumes the grammar where the
        # emitted tokens left it)
        self.mode = "generate"
        self.value = None
        self.adapter = None
        self._fsm_state = None
        # QoS surface: the request's resolved tier name (None on non-QoS
        # engines) and how many times a higher tier evicted it from a
        # decode slot (each eviction requeued it as prompt+tokens-so-far,
        # so greedy output is unaffected — only latency is)
        self.tier = None
        self.preemptions = 0
        # distributed-tracing identity: every span this request touches
        # (submit -> prefill -> each decode iteration) carries/links it
        self.trace_id = _tracing.new_trace_id()
        self.token_ids = []            # generated ids (appended by the engine)
        # wall-clock stamp of every emission — the request's token-level
        # timeline (observability.slo evaluates TTFT/ITL/e2e targets on it)
        self.token_times = []
        self.status = "queued"
        self.submitted_at = time.time()
        self.admitted_at = None        # queue -> slot (first dispatch start)
        self.compile_s = 0.0           # compile stalls this request waited out
        self.first_token_at = None
        self.finished_at = None
        self.first_token_iteration = None
        self.finished_iteration = None
        self._events = _queue.Queue()
        self._done = threading.Event()
        self._cancel = threading.Event()
        self._error = None

    # ----------------------------------------------------------------- api
    def cancel(self):
        self._cancel.set()

    @property
    def cancelled(self):
        return self._cancel.is_set()

    @property
    def done(self):
        return self._done.is_set()

    def result(self, timeout=None):
        """Generated token ids (blocks until the request finishes).
        ``mode="embed"`` requests return the pooled hidden-state vector,
        ``mode="score"`` the per-token logprob list."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished after {timeout}s")
        if self._error is not None:
            # EngineStoppedError / NumericFault are per-request verdicts
            # (stopped mid-flight; this row's logits went non-finite) —
            # surface them as-is, not as an engine-wide failure
            if isinstance(self._error, (EngineStoppedError, NumericFault)):
                raise self._error
            raise RuntimeError("serving engine failed") from self._error
        if self.mode != "generate":
            return self.value
        return list(self.token_ids)

    def stream(self):
        """Token-at-a-time iterator.  Abandoning the iterator (``close()``
        / ``break`` + GC) cancels the request so its pages free."""
        try:
            while True:
                kind, val = self._events.get()
                if kind == "token":
                    yield val
                else:
                    break
            if self._error is not None:
                if isinstance(self._error,
                              (EngineStoppedError, NumericFault)):
                    raise self._error
                raise RuntimeError("serving engine failed") from self._error
        finally:
            if not self._done.is_set():
                self.cancel()

    __iter__ = stream

    @property
    def ttft(self):
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    # ------------------------------------------- TTFT decomposition (PR 16)
    @property
    def queue_s(self):
        """Submit -> admission wait (None until admitted)."""
        if self.admitted_at is None:
            return None
        return self.admitted_at - self.submitted_at

    @property
    def prefill_s(self):
        """TTFT minus queueing minus compile stalls — the dispatch work
        itself.  Defined as the remainder so the decomposition sums
        exactly: ``queue_s + compile_s + prefill_s == ttft``."""
        t = self.ttft
        if t is None or self.queue_s is None:
            return None
        return max(0.0, t - self.queue_s - self.compile_s)

    def ttft_breakdown(self):
        """Cold-start forensics: where this request's first token went.
        ``None`` until the first token lands."""
        t = self.ttft
        if t is None:
            return None
        return {"ttft_s": t, "queue_s": self.queue_s,
                "compile_s": self.compile_s, "prefill_s": self.prefill_s,
                "cold": self.compile_s > 0.0, "trace_id": self.trace_id}


class _Slot:
    __slots__ = ("handle", "req", "alloc", "table_row", "length", "last",
                 "produced", "temp", "eos", "max_new", "deadline",
                 "last_token_t", "idx", "prefilled", "unread", "gen")

    def __init__(self, req, alloc, table_row):
        self.idx = None                     # batch lane (set at admission)
        self.handle = req.handle
        self.req = req
        self.alloc = alloc
        self.table_row = table_row          # np.int32 [<= NP] real pages
        self.length = len(req.prompt)       # tokens whose K/V are in pages
        self.last = 0                       # last sampled token id
        self.produced = 0                   # tokens EMITTED to the caller
        # tokens dispatched and not read back yet (``produced + unread`` is
        # what the device has sampled or will: the budget counts that)
        self.unread = 0
        # bumped when the lane is taken from the request before its budget
        # ends (EOS, cancel, deadline, a non-finite row, preemption): a
        # result in flight that recorded an older value is dropped unread
        self.gen = 0
        self.temp = float(req.sampling.temperature)
        self.eos = req.eos_token_id
        self.max_new = req.max_new_tokens
        self.deadline = req.deadline
        self.last_token_t = None
        # chunked prefill: prompt tokens whose K/V have landed so far.
        # None = monolithic prefill / ingestion complete (lane decodes);
        # an int means the slot is mid-prefill — its persistent host row
        # stays inert (scratch table, length 0) so decode dispatches skip
        # it, and _advance_prefills drives the next chunk.
        self.prefilled = None


class _Flight:
    """One dispatch whose results the host has not read: the arrays, still
    on the device, and whom they belong to.  ``lanes`` holds ``(row, lane,
    slot, gen, expects)``: the result's row, the batch lane and its slot
    at dispatch, the slot's generation then, and 1 if the row's token is
    the request's next one (a chunk that is not a prompt's last yields
    none)."""

    __slots__ = ("kind", "fam", "turn", "lanes", "hist", "t0", "t_read",
                 "cold", "ahead", "tok", "bad", "nstats")

    def __init__(self, kind, fam, turn, lanes, hist=None):
        self.kind = kind                    # "prefill" | "chunk" | "step"
        self.fam = fam
        self.turn = turn
        self.lanes = lanes
        self.hist = hist                    # the kind's seconds histogram
        self.cold = self.ahead = False
        self.tok = self.bad = self.nstats = None


@functools.partial(jax.jit, donate_argnums=0)
def _seed_last(last, lane, tok):
    """The decode step's ``last [B, 1]`` with row ``lane`` set to the first
    token ``tok [1]`` a prefill sampled: a lane goes live on the device."""
    return last.at[lane, 0].set(tok[0].astype(last.dtype))


@jax.jit
def _carry_last(tok):
    """A step's sampled ids ``[B]`` as the next step's ``last [B, 1]``."""
    return tok[:, None].astype(jnp.int64)


class ServingEngine:
    """See module docstring.  Typical use::

        engine = ServingEngine(model, num_slots=4, page_size=16)
        with engine:
            h = engine.submit([1, 2, 3], max_new_tokens=64)
            for tok in h.stream():
                ...
    """

    @_programs.phase("serving.engine_init")
    def __init__(self, model, num_slots=4, page_size=16, max_model_len=None,
                 num_pages=None, top_k=0, top_p=1.0, prefix_sharing=False,
                 max_queue=None, seed=0, adapter=None, watchdog_s=None,
                 telemetry_port=None, max_engine_restarts=3,
                 degraded_stall_s=2.0, restart_cooldown_s=10.0,
                 speculative_k=0, draft_max_ngram=3, draft_min_ngram=1,
                 replica="0", device=None, health_gating=True, slo=None,
                 kv_dtype=None, weight_dtype=None, numeric_guard=None,
                 prefill_chunk_tokens=None, mesh=None, qos=None,
                 prefix_cache=None, kv_spill=False,
                 kv_spill_budget_bytes=None):
        self._model = model
        # chunked prefill (README "Flash decode & chunked prefill"):
        # prompts longer than N tokens are admitted IMMEDIATELY and
        # ingested N tokens at a time through the chunk cache variant,
        # interleaved with the batch decode dispatch each scheduler
        # iteration — one long prompt stops stalling the whole decode
        # batch for its entire prefill, while greedy outputs stay
        # byte-identical to the monolithic path.  None/0 disables.
        if prefill_chunk_tokens:
            prefill_chunk_tokens = int(prefill_chunk_tokens)
            if prefill_chunk_tokens < 1:
                raise ValueError(f"prefill_chunk_tokens must be >= 1, "
                                 f"got {prefill_chunk_tokens}")
        else:
            prefill_chunk_tokens = None
        self._chunk_tokens = prefill_chunk_tokens
        self._prefill_rr = 0    # round-robin cursor over prefilling slots
        # quantized serving (serving/quant, README "Quantized serving"):
        # kv_dtype="int8" stores the paged KV pools as int8 with parallel
        # per-(page slot, head) scale pools — quant fused into the pool
        # writes, dequant into the paged-attention kernels, ~2x resident
        # slots per HBM byte; weight_dtype="int8" converts the model's
        # Linears to Int8Linear in place (idempotent — cluster replicas
        # over one shared model convert once).  The default (None /
        # "native" / "bf16") is byte-identical to the unquantized engine.
        kv_dtype = str(kv_dtype).lower() if kv_dtype is not None else "native"
        if kv_dtype in ("native", "bf16", "bfloat16", "float32", "fp32"):
            kv_dtype = "native"
        elif kv_dtype != "int8":
            raise ValueError(f"kv_dtype must be None/'native' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.weight_dtype = str(weight_dtype).lower() \
            if weight_dtype is not None else "native"
        if self.weight_dtype not in ("native", "int8"):
            raise ValueError(f"weight_dtype must be None/'native' or "
                             f"'int8', got {weight_dtype!r}")
        if self.weight_dtype == "int8":
            from .quant.weights import quantize_model_weights

            quantize_model_weights(model)
        # quantized program families get their own perf-attribution names
        # (decode@int8, prefill/<bucket>@int8, verify/k<k>@int8) so the
        # roofline table can judge the dequant-fused programs separately
        self._fam_suffix = "@int8" if kv_dtype == "int8" else ""
        # replica identity (cluster serving): stamps every serving.* metric
        # series with a replica= label so N engines in one process don't
        # overwrite each other, keys the /statusz|/healthz provider
        # registration, and names the per-replica fault sites
        # serving.{step_crash,scheduler_wedge}@<replica>
        self.replica = str(replica)
        self._site_wedge = f"serving.scheduler_wedge@{self.replica}"
        self._site_step_crash = f"serving.step_crash@{self.replica}"
        # replica-loss chaos site (QoS/autoscaling bench): when armed and
        # it fires, the scheduler raises a FATAL error — the replica dies
        # like a reclaimed spot host, the cluster reroutes its in-flight
        # work and the autoscaler reaps + replaces it
        self._site_replica_preempt = f"cluster.replica_preempt@{self.replica}"
        self._provider_key = f"serving/{self.replica}"
        # False for cluster replicas: the replica still shows on /healthz
        # but the ServingCluster's any-replica-routable component gates
        # the 503 fold instead (one dead replica must not fail the fleet)
        self._health_gating = bool(health_gating)
        self._device = device
        # tensor-parallel serving (README "Tensor-parallel serving"):
        # mesh= shards this engine's programs SPMD over a "model" mesh
        # axis — paged KV pools on the KV-head dim, decoder weights
        # Megatron-style (qkv/ffn1 column-, out_proj/ffn2 row-parallel),
        # page table / seq_lens / sampler state host-side and replicated
        # so the scheduler, prefix sharing and admission logic never see
        # the second device axis.  Accepts a jax.sharding.Mesh (an axis
        # named "model"), a distributed ProcessMesh with a "model" dim,
        # or a flat device sequence (meshed over one "model" axis).
        self._mesh, self._mp, solo = _normalize_mesh(mesh)
        if mesh is not None and device is not None:
            raise ValueError(
                "device= and mesh= are mutually exclusive: a dp replica "
                "commits to ONE device, an mp engine to a mesh (compose "
                "them via ReplicaPool(devices=..., mp=...))")
        if solo is not None:    # 1-sized mesh = plain dp placement
            self._device = device = solo
        # mp program families get their own perf-attribution suffix
        # (decode@mp2, prefill/<b>@mp2, ...) and program-store keys, so an
        # mp=1 engine's programs stay byte-identical to pre-mesh builds
        self._mp_suffix = f"@mp{self._mp}" if self._mp > 1 else ""
        # the one rule by which the engine picks an adapter from the model:
        # a decoder that is not ``.gpt`` states the caches it needs
        # (``model.serving_caches()``) and is served through them
        if adapter is not None:
            self._adapter = adapter
        elif hasattr(model, "serving_caches"):
            self._adapter = StatedCacheAdapter(model, int(page_size),
                                               int(num_slots))
        elif kv_dtype == "int8":
            from .quant.adapter import QuantizedGPTAdapter

            self._adapter = QuantizedGPTAdapter(model, page_size)
        else:
            self._adapter = GPTAdapter(model, page_size)
        # per-slot state beside the pages (adapter.StatedCacheAdapter): the
        # one-request programs get the slot's index, and the mechanisms
        # that move or share pages without the state are refused by name.
        # A decoder that states pages alone shares, spills and re-reads
        # them like any other; what it is refused is what its adapter
        # lacks, by name
        self._slot_state = bool(getattr(self._adapter, "slot_state", False))
        if self._slot_state:
            self._refuse_with_slot_state(
                prefix_sharing=prefix_sharing, prefix_cache=prefix_cache,
                kv_spill=kv_spill, speculative_k=speculative_k,
                kv_dtype=kv_dtype, mesh=mesh)
        if getattr(self._adapter, "window_layers", 0):
            self._refuse_with_window_pages(
                prefix_sharing=prefix_sharing, prefix_cache=prefix_cache,
                kv_spill=kv_spill, speculative_k=speculative_k,
                kv_dtype=kv_dtype, mesh=mesh)
        self._refuse_what_the_adapter_lacks(
            self._adapter, speculative_k=speculative_k, kv_dtype=kv_dtype,
            mesh=mesh)
        if self._mp > 1:
            self._adapter.validate_mp(self._mp)
            # the adapter carries the mesh so the TPU flash kernels trace
            # under mp_shard_scope (each shard sweeps its local KV heads);
            # off-TPU the jnp reference paths are GSPMD-partitioned from
            # the operand shardings and the scope is a no-op
            self._adapter.mp_mesh = self._mesh
        self.page_size = int(page_size)
        self.num_slots = int(num_slots)
        cap = self._adapter.max_model_len
        self.max_model_len = min(int(max_model_len), cap) if max_model_len \
            else cap
        self.table_width = -(-self.max_model_len // self.page_size)  # NP
        if num_pages is None:
            num_pages = self.num_slots * self.table_width  # full residency
        self._num_pages = int(num_pages)
        # sliding-window layers keep their pages in a pool of their own, a
        # sequence at most what its window and the widest write need
        # (adapter.plan_window): a chunk, or a whole prompt where prompts
        # are not chunked
        plan = getattr(self._adapter, "plan_window", None)
        self._window_plan = plan(
            self.num_slots, self.table_width,
            self._prefill_bucket(self._chunk_tokens) if self._chunk_tokens
            else self.table_width * self.page_size) if plan else None
        # hierarchical KV cache (README "Hierarchical KV cache"):
        # prefix_cache="radix" swaps the BlockManager's exact-key prefix
        # matching for the page-granular radix index (serving/
        # prefix_index.py) — allocate reuses the LONGEST shared page run,
        # and prefill starts past the cached tokens instead of
        # recomputing the run; "lru" is an explicit alias for the legacy
        # exact-key sharing (memory reuse, full recompute).  kv_spill=True
        # adds the host-DRAM tier (serving/kv_spill.py): idle pages
        # evicted off-device re-page on the next matching prefix instead
        # of recomputing, bounded by PADDLE_KV_SPILL_BUDGET_BYTES (or the
        # kv_spill_budget_bytes arg) and accounted to the ledger's
        # kv.spilled host owner.
        if prefix_cache not in (None, "lru", "radix"):
            raise ValueError(f"prefix_cache must be None, 'lru' or "
                             f"'radix', got {prefix_cache!r}")
        self._prefix_cache = prefix_cache
        self._radix = prefix_cache == "radix"
        self._prefix_sharing = bool(prefix_sharing) \
            or prefix_cache is not None
        self._spill = None
        if kv_spill:
            if not self._radix:
                raise ValueError(
                    "kv_spill=True needs prefix_cache='radix': spilled "
                    "pages are content-addressed through the radix index")
            from .kv_spill import KVSpillTier

            self._spill = KVSpillTier(replica=self.replica,
                                      budget_bytes=kv_spill_budget_bytes)
        # HBM accounting (quantized serving satellite): every page costs
        # adapter.page_bytes() across all layers, K+V, scale pools
        # included — BlockManager carries it so capacity math, stats()
        # and /statusz all read one number.  Under mp the pools shard the
        # KV-head dim, so a page costs 1/mp of the global bytes PER CHIP —
        # capacity math (max_resident_sequences, admission pre-flight
        # against PADDLE_HBM_BUDGET_BYTES) is denominated in per-shard
        # bytes: a 2-way-sharded pool holds 2x slots per chip at the same
        # HBM budget.  Exact division: page_bytes is linear in
        # num_kv_heads, which validate_mp pinned divisible by mp.
        self._bytes_per_page = int(self._adapter.page_bytes()) // self._mp
        self._pool_dtype = "int8" if self.kv_dtype == "int8" \
            else str(self._adapter.dtype)
        state_bytes = getattr(self._adapter, "state_bytes_per_slot", None)
        self._state_bytes_per_slot = int(state_bytes()) if state_bytes else 0
        self._bm = self._new_block_manager()
        # pool row num_pages is the SCRATCH page: inactive decode slots and
        # padded table tails point at it (every table entry must be a valid
        # pool row; junk written there is never attended)
        self._scratch = int(num_pages)
        # a table row that holds nothing (each pool's scratch page)
        self._scratch_row = self._bm.scratch_row(self.table_width)
        # dp-replica placement (device=): commit this replica's params/
        # buffers and page pools to its device — uncommitted per-step host
        # arrays (table/lens/ids) follow the committed operands, so every
        # dispatch of this engine runs there.  mp placement (mesh=): commit
        # weights with their Megatron annotations and pools with the
        # KV-head sharding — GSPMD propagates the layouts through the
        # unchanged adapter closures, so every program family compiles
        # ONCE as a single SPMD program (not per shard), and the
        # uncommitted host arrays (table/lens/ids/temps) replicate onto
        # the mesh automatically
        with _programs.phase("serving.engine_init.pools"):
            self._pools = tuple(self._adapter.init_pools(num_pages + 1))
            if device is not None:
                self._pools = jax.device_put(self._pools, device)
            elif self._mesh is not None:
                self._pools = self._shard_pools(self._pools)
        with _programs.phase("serving.engine_init.weights"):
            self._params, self._bufs = self._adapter.params_and_buffers()
            if device is not None:
                self._params = jax.device_put(self._params, device)
                self._bufs = jax.device_put(self._bufs, device)
            elif self._mesh is not None:
                self._params = self._shard_tree(self._params)
                self._bufs = self._shard_tree(self._bufs)
        if self._spill is not None:
            # transport callables close over self: every spill/resurrect
            # reads the CURRENT pool tuple, so donation rebinds and
            # post-crash pool rebuilds need no re-attachment
            self._spill.attach(self._spill_snapshot, self._spill_restore)
        from ..text.models._decode import (make_batched_sampler,
                                           make_guarded_batched_sampler)

        self._sampler = make_batched_sampler(top_k, top_p)
        self._top = (int(top_k), float(top_p))
        # NaN-safe serving (README "Numerics observability"): the guarded
        # program variant returns a per-row non-finite-logits flag (and a
        # logits stats row for the numerics stream) next to the sampled
        # tokens; the scheduler fails exactly the flagged requests with
        # status="error" / NumericFault while finite rows' token math is
        # untouched (the guard wraps the SAME sampler, so greedy output
        # stays byte-identical).  Off — the default, unless the active
        # TensorCheckerConfig asks for serving_guard — every program is
        # the pre-guard one: byte-identical keys, traces and dispatches.
        self._numeric_guard = bool(_numerics.serving_guard_default()
                                   if numeric_guard is None
                                   else numeric_guard)
        self._guard_sampler = make_guarded_batched_sampler(top_k, top_p)
        self._base_key = jax.random.key(int(seed))
        self._key_counter = itertools.count()
        self._rid_counter = itertools.count()

        # speculative decoding (serving/speculative.py): n-gram drafts are
        # verified k+1 tokens at a time by ONE compiled multi-token step —
        # greedy rows accept by exact argmax match (byte-identical output),
        # temperature rows by rejection sampling
        self._spec_k = int(speculative_k)
        if self._spec_k < 0:
            raise ValueError(f"speculative_k must be >= 0, got {speculative_k}")
        self._drafter = None
        self._verifier = None
        if self._spec_k:
            from .speculative import NgramDrafter, make_verifier

            self._drafter = NgramDrafter(self._spec_k, draft_max_ngram,
                                         draft_min_ngram)
            self._verifier = make_verifier(top_k, top_p)
        self._spec_proposed_total = 0
        self._spec_accepted_total = 0

        # QoS tiers (serving/qos.py, README "QoS tiers & autoscaling"):
        # qos=True installs the default realtime/standard/batch table, a
        # QoSConfig a custom one.  The queue becomes per-tier with
        # priority-weighted head selection; submits carry tier=, each
        # tier with an SLOPolicy gets its own accountant (tier= label),
        # admission sheds by the brownout ladder, and high-tier requests
        # preempt lower-tier decode slots instead of waiting.
        self._qos = None
        self._tier_slo = {}
        self._tier_ema = {}          # per-tier completed-duration EMAs
        self._last_preempt_t = None
        self._bo_cache = (0.0, None)  # throttled brownout snapshot
        if qos:
            from .qos import QoSConfig

            if qos is True:
                qos = QoSConfig()
            if not isinstance(qos, QoSConfig):
                raise TypeError(f"qos must be a QoSConfig or True, "
                                f"got {qos!r}")
            self._qos = qos
            from ..observability.slo import SLOAccountant as _TierAcct

            for t in qos.tiers:
                if t.slo is not None:
                    self._tier_slo[t.name] = _TierAcct(
                        t.slo, replica=self.replica, tier=t.name)
        if self._qos is not None:
            from .qos import TieredQueue

            self._queue = TieredQueue(self._qos)
        else:
            self._queue = collections.deque()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._slots = [None] * self.num_slots
        # persistent per-step host buffers: rows change on admit/retire and
        # per-token advances only, so the hot decode dispatch stops
        # re-allocating and re-filling a fresh [B, NP] table every
        # iteration (measured per-step host overhead on the paged path)
        self._h_last = np.zeros((self.num_slots, 1), np.int64)
        self._h_lens = np.zeros((self.num_slots,), np.int32)
        self._h_temps = np.zeros((self.num_slots,), np.float32)
        self._h_table = np.tile(self._scratch_row, (self.num_slots, 1))
        if self._spec_k:
            self._h_ids = np.zeros((self.num_slots, self._spec_k + 1),
                                   np.int64)
            self._h_dlen = np.zeros((self.num_slots,), np.int32)
        self._n_temp = 0          # live slots with temperature sampling
        self._gauges_t = 0.0      # last _update_gauges stamp (throttled)
        # the decode loop runs one step ahead of the host (depth 1): a
        # turn enqueues its programs and THEN reads what the turn before
        # left in ``_pending``, so emitting, retiring, admitting and
        # building arguments happen while the device computes.  Depth 0
        # (every dispatch read back at once) where the next step's inputs
        # are made on the host from this step's token; the engine derives
        # it from what it was built with, there is no option
        self._depth = 0 if self._host_makes_step_inputs() else 1
        self._pending = collections.deque()     # _Flight, dispatch order
        self._turn_no = 0
        self._t_step = 0.0        # when the last step's result was read
        # depth 1: the step's ``last`` stays on the device (a step's tok
        # carried over, a new lane's first token merged in)
        self._d_last = self._h_last
        if self._depth:
            # both small programs compile here, not inside a window
            z = np.zeros((self.num_slots,), np.int64)
            if device is not None:
                z = jax.device_put(z, device)
            _seed_last(_carry_last(z), np.int32(0), z[:1])
        self._max_queue = max_queue
        self._stop_evt = threading.Event()
        self._thread = None
        self._started = False
        self._modes = None
        self._iteration = 0
        self._error = None
        # observability wiring (PR-3): scheduler heartbeat for the serving
        # watchdog, plus opt-in watchdog/telemetry (ctor arg or env)
        self._progress_t = None
        self._compiling = False  # first dispatch of a program (XLA compile)
        self._watchdog_s = watchdog_s
        self._telemetry_port = telemetry_port
        self._watchdog = None
        self._status_provider = None
        self._health_provider = None
        # resilience wiring (PR-4): health state machine, load shedding,
        # transient-failure auto-restart with in-flight requeue
        self._draining = False
        self._max_engine_restarts = int(max_engine_restarts)
        self._degraded_stall_s = float(degraded_stall_s)
        self._restart_cooldown_s = float(restart_cooldown_s)
        self._engine_restarts = 0
        self._last_restart_t = None
        self._ema_request_s = None   # EMA of completed request durations
        self._admitting = None       # request popped but not yet slotted

        from ..profiler import metrics as _metrics

        # request-level SLO accounting (observability.slo): evaluate every
        # finished request's token timeline against the policy, export
        # rolling attainment/burn-rate/goodput gauges per replica
        self._slo = None
        ttft_buckets = itl_buckets = None
        if slo is not None:
            from ..observability.slo import (SLOAccountant, SLOPolicy,
                                             slo_histogram_buckets)

            if not isinstance(slo, SLOPolicy):
                raise TypeError(f"slo must be an SLOPolicy, got {slo!r}")
            self._slo = SLOAccountant(slo, replica=self.replica)
            # align the latency histogram edges with the SLO thresholds so
            # "fraction of samples under target" reads straight off the
            # Prometheus _bucket series
            if slo.ttft_s:
                ttft_buckets = slo_histogram_buckets(
                    _metrics._DEFAULT_BUCKETS, slo.ttft_s)
            if slo.itl_s:
                itl_buckets = slo_histogram_buckets(
                    _metrics._DEFAULT_BUCKETS, slo.itl_s)

        # every serving.* series carries replica=<id> (default "0") so N
        # engines in one process keep distinct series; per-call labels like
        # status=/reason= merge on top of it (metrics.bind)
        def _h(name, help, buckets=None):
            return _metrics.bind(_metrics.histogram(name, help,
                                                    buckets=buckets),
                                 replica=self.replica)

        def _g(name, help):
            return _metrics.bind(_metrics.gauge(name, help),
                                 replica=self.replica)

        def _c(name, help):
            return _metrics.bind(_metrics.counter(name, help),
                                 replica=self.replica)

        self._m_ttft = _h("serving.ttft_seconds", "submit -> first token",
                          buckets=ttft_buckets)
        self._m_ttft_cold = _h(
            "serving.ttft_cold_seconds",
            "submit -> first token for requests that paid a compile stall "
            "(subset of serving.ttft_seconds)", buckets=ttft_buckets)
        self._m_itl = _h(
            "serving.inter_token_seconds", "per-sequence inter-token latency",
            buckets=itl_buckets)
        self._m_step_seconds = _h(
            "serving.step_seconds", "one batched decode iteration")
        # observed once per decode / verify dispatch, so a window's delta
        # of _sum over _count is a mean (the gauges of the same quantities
        # are refreshed every 50 ms for /metrics and average nothing)
        self._m_decode_batch = _h(
            "serving.decode_batch_size", "lanes in one decode dispatch",
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256))
        self._m_step_page_util = _h(
            "serving.step_page_utilization",
            "KV pages in use / pool size at a decode dispatch",
            buckets=(0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7, 0.8, 0.9, 1.0))
        self._m_prefill_seconds = _h(
            "serving.prefill_seconds", "admit-time prefill")
        self._m_queue_depth = _g(
            "serving.queue_depth", "requests waiting for a slot")
        self._m_active = _g(
            "serving.active_slots", "slots decoding this iteration")
        self._m_occupancy = _g(
            "serving.slot_occupancy", "active_slots / num_slots")
        self._m_page_util = _g(
            "serving.page_utilization", "KV pages in use / pool size")
        self._m_pages_used = _g(
            "serving.pages_in_use", "KV pages held by live sequences")
        self._m_tokens = _c(
            "serving.tokens_generated", "tokens emitted to callers")
        self._m_requests = _c(
            "serving.requests", "requests by terminal status")
        self._m_blocked = _c(
            "serving.admissions_blocked",
            "admissions deferred: page pool exhausted")
        self._m_preempt = _c(
            "serving.preemptions",
            "sequences evicted from their decode slot (reason=deadline: "
            "retired expired; reason=qos: requeued for a higher tier)")
        self._m_ahead = _c(
            "serving.steps_dispatched_ahead",
            "decode dispatches made while the previous step's result was "
            "still unread (over serving.decode_batch_size_count: the share "
            "of steps the device did not wait for the host)")
        self._m_drains = _c(
            "serving.pipeline_drains",
            "turns of a run-ahead engine that read back everything in "
            "flight with nothing enqueued behind it, by reason")
        self._m_discarded = _c(
            "serving.tokens_discarded",
            "sampled tokens dropped unread or unemitted: their lane was "
            "retired, preempted or failed while they were in flight")
        # per-tier pressure gauges (QoS engines set them; registered
        # unconditionally so the metric families are stable)
        self._m_tier_depth = _g(
            "serving.tier.queue_depth", "queued requests per QoS tier")
        self._m_tier_active = _g(
            "serving.tier.active_slots", "decoding slots held per QoS tier")
        self._m_step_traces = _c(
            "serving.step_traces", "decode-step program traces")
        self._m_prefill_traces = _c(
            "serving.prefill_traces", "prefill program traces")
        self._m_prefill_chunk_seconds = _h(
            "serving.prefill_chunk_seconds",
            "one chunked-prefill dispatch (prefill_chunk_tokens tokens)")
        self._m_prefill_chunk_traces = _c(
            "serving.prefill_chunk_traces",
            "chunked-prefill program traces")
        self._m_shed = _c(
            "serving.load_shed", "requests shed at submit, by reason")
        self._m_engine_restarts = _c(
            "serving.engine_restarts",
            "scheduler auto-restarts after transient failures")
        self._m_requeued = _c(
            "serving.requests_requeued",
            "in-flight requests transparently re-queued across a restart")
        self._m_health = _g(
            "serving.health_state",
            "0 healthy, 1 degraded, 2 draining, 3 stopped, 4 error")
        self._m_spec_proposed = _c(
            "serving.spec_proposed", "draft tokens submitted to verification")
        self._m_spec_accepted = _c(
            "serving.spec_accepted", "draft tokens accepted by verification")
        self._m_accept_rate = _g(
            "serving.acceptance_rate",
            "speculative acceptance: spec_accepted / spec_proposed")
        self._m_verify_traces = _c(
            "serving.verify_traces", "verify-step program traces")
        # numerics observability (ISSUE 13): requests retired because the
        # guarded program flagged their logits row non-finite, plus a
        # sampled weight dequant->requant drift gauge for quant engines
        self._m_numeric_faults = _c(
            "serving.numeric_faults",
            "requests failed on non-finite logits (guarded programs)")
        self._m_quant_drift = _g(
            "serving.quant_drift",
            "sampled int8 weight dequant->requant roundtrip error "
            "(relative, one layer per tick)")
        self._drift_idx = 0
        self._drift_t = 0.0
        self._npoll_t = 0.0
        # quantized-serving occupancy gauges: bytes one token position
        # costs in the KV pools (layers x K+V, scale pools included) and
        # the allocated pool HBM, labelled by pool dtype
        self._m_kv_bytes_tok = _g(
            "serving.kv_bytes_per_token",
            "KV-cache HBM bytes per token position (all layers, K+V, "
            "scale pools included)")
        self._m_pool_bytes = _g(
            "serving.pool_bytes",
            "allocated KV page-pool HBM bytes (scratch page included)")
        self._m_state_bytes_slot = _g(
            "serving.state_bytes_per_slot",
            "HBM bytes of per-slot state one resident sequence holds beside "
            "its KV pages (0 for a decoder whose only cache is paged)")
        self._m_cache_layers = _g(
            "serving.cache_layers",
            "rows of the KV page pools: the layers that hold pages (steps x "
            "layers for a decoder that runs its layers several times)")
        self._m_loop_steps = _g(
            "serving.loop_steps",
            "times the decoder runs its layers over one set of weights in "
            "one forward pass (1 for a plain decoder)")
        self._set_pool_gauges()
        # memory observability (observability/memory.py): every long-lived
        # device allocation this engine owns registers with the process
        # ledger, and admission pre-flight projects new requests against
        # PADDLE_HBM_BUDGET_BYTES — fixed bytes (params + buffers) plus
        # pages already committed to admitted-but-unfinished requests
        self._fixed_bytes = int(
            sum(int(v.nbytes) for v in self._params.values())
            + sum(int(v.nbytes) for v in self._bufs.values())
            + self._state_bytes_per_slot * (self.num_slots + 1))
        self._committed_pages = 0
        self._commit_lock = threading.Lock()
        self._mem_regs = []
        self._register_memory()

    @staticmethod
    def _refuse_with_slot_state(prefix_sharing, prefix_cache, kv_spill,
                                speculative_k, kv_dtype, mesh):
        """A decoder with per-slot state (adapter.StatedCacheAdapter) is
        served on the plain path only: every mechanism that shares, moves
        or re-reads pages WITHOUT the state that belongs to them is
        refused here by name, at construction."""
        why = None
        if prefix_sharing or prefix_cache is not None:
            why = ("prefix_sharing / prefix_cache (and the cached-prefill "
                   "path behind them): a shared page run carries no "
                   "per-slot state at its edge, so a prefill that starts "
                   "past it would enter with the wrong state")
        elif kv_spill:
            why = ("kv_spill: the host spill tier snapshots pages, not the "
                   "state a sequence had at their edge")
        elif speculative_k:
            why = ("speculative_k: a rejected draft would need the "
                   "per-slot state rolled back, and no snapshot is kept")
        elif kv_dtype == "int8":
            why = ("kv_dtype='int8': the quantized pool tuple has no place "
                   "for the per-slot state")
        elif mesh is not None:
            why = ("mesh=: the per-slot state pool has no sharding rule")
        if why is not None:
            raise ValueError(
                f"this model is served with per-slot state beside its "
                f"paged KV; not supported with it: {why}")

    @staticmethod
    def _refuse_with_window_pages(prefix_sharing, prefix_cache, kv_spill,
                                  speculative_k, kv_dtype, mesh):
        """A decoder with sliding-window layers keeps their pages in a pool
        of its own where a sequence reuses the pages behind its window
        (block_manager.WindowPages): every mechanism that assumes a layer
        holds every page of a sequence is refused here by name."""
        why = None
        if prefix_sharing or prefix_cache is not None:
            why = ("prefix_sharing / prefix_cache: a shared page run would "
                   "need the window layers' pages too, and those a "
                   "sequence overwrites behind its window")
        elif kv_spill:
            why = ("kv_spill: the host spill tier snapshots pages of one "
                   "pool, by content address; window pages have none")
        elif speculative_k:
            why = ("speculative_k: a verify chunk writes drafts into "
                   "window pages that a rejected draft would have to give "
                   "back")
        elif kv_dtype == "int8":
            why = ("kv_dtype='int8': the windowed kernels read pages in the "
                   "model's type, and the window pool has no scale pools")
        elif mesh is not None:
            why = ("mesh=: the head-sharded kernels take no window")
        if why is not None:
            raise ValueError(
                f"this model keeps its sliding-window layers' pages in a "
                f"pool of their own; not supported with it: {why}")

    @staticmethod
    def _refuse_what_the_adapter_lacks(adapter, speculative_k, kv_dtype,
                                       mesh):
        """The served paths that exist for :class:`GPTAdapter` only
        (ROADMAP R7) are refused at construction, each by its name, for an
        adapter that does not bring them: not for what the model is."""
        why = None
        if speculative_k and not hasattr(adapter, "verify"):
            why = ("speculative_k: verification runs the adapter's `verify` "
                   "program (logits at every drafted position), which "
                   f"{type(adapter).__name__} does not have")
        elif kv_dtype == "int8" \
                and getattr(adapter, "kv_dtype", None) != "int8":
            why = ("kv_dtype='int8': the quantized pool tuple is "
                   "serving.quant.QuantizedGPTAdapter's, written over a "
                   f"`.gpt` decoder; {type(adapter).__name__} keeps its "
                   "pages in the model's type")
        elif mesh is not None and not hasattr(adapter, "param_pspec"):
            why = ("mesh=: the sharding rules (`validate_mp`, "
                   "`pool_pspecs`, `param_pspec`) are GPTAdapter's; "
                   f"{type(adapter).__name__} has none")
        if why is not None:
            raise ValueError(f"not supported for this model: {why}")

    def _register_memory(self):
        """Register this engine's device allocations with the process
        MemoryLedger.  Sources close over a weakref — the ledger never
        pins the engine, and every read resolves the CURRENT pool tuple,
        so a post-crash ``_recover()`` rebuild needs no re-registration."""
        led = _obs_memory.ledger()
        ref = weakref.ref(self)

        def _pools_src(idx):
            def src():
                eng = ref()
                if eng is None:
                    return None
                return [eng._pools[i] for i in idx]
            return src

        for owner, idx in self._adapter.pool_owners():
            meta = None
            if owner == "kv.pages":
                meta = {
                    "kind": "kv",
                    # per-shard when mp > 1 (shard= below): the unit the
                    # per-chip capacity math is denominated in
                    "bytes_per_page": self._bytes_per_page,
                    "page_size": self.page_size,
                    "num_pages": self._num_pages,
                    "max_model_len": self.max_model_len,
                    "max_resident_slots":
                        self._bm.max_resident_sequences(self.max_model_len),
                }
            elif owner == "kv.scales":
                meta = {"kind": "kv_scales"}
            if meta is not None and self._mp > 1:
                # sharded pools: label the owner with the mesh split so
                # ledger.report()'s per-device view can divide the global
                # array bytes by the shard count (live_arrays and the
                # sources both report GLOBAL nbytes, so reconciliation
                # still accounts 100% of live bytes either way)
                meta["shard"] = f"{_MP_AXIS}:{self._mp}"
            self._mem_regs.append(led.register(
                owner, _pools_src(idx), replica=self.replica, meta=meta))

        def _named_src(which, pred):
            def src():
                eng = ref()
                if eng is None:
                    return None
                d = eng._params if which == "params" else eng._bufs
                return [v for k, v in d.items() if pred(k)]
            return src

        # int8-converted weights get their own owner row; everything else
        # (f32/bf16 params, residual buffers, Int8Linear biases) is
        # model.params.  Int8Linear stores its payload in a buffer named
        # ``<sublayer>.weight_int8`` (quantization.Int8Linear).
        is_q = lambda k: k.endswith("weight_int8")  # noqa: E731
        self._mem_regs.append(led.register(
            "model.params", _named_src("params", lambda k: True),
            replica=self.replica, meta={"kind": "weights"}))
        self._mem_regs.append(led.register(
            "model.params", _named_src("bufs", lambda k: not is_q(k)),
            replica=self.replica, meta={"kind": "weights"}))
        if self.weight_dtype == "int8":
            self._mem_regs.append(led.register(
                "model.weights_int8", _named_src("bufs", is_q),
                replica=self.replica, meta={"kind": "weights_int8"}))

        if self._spill is not None:
            sref = weakref.ref(self._spill)

            def _spill_src():
                tier = sref()
                return None if tier is None else tier.nbytes()

            # host-DRAM tier: device="host" rows are bookkeeping only —
            # outside the jax.live_arrays reconciliation, exactly like
            # checkpoint.snapshot's pinned host buffers
            self._mem_regs.append(led.register(
                "kv.spilled", _spill_src, replica=self.replica,
                device="host",
                meta={"kind": "kv-spill",
                      "budget_bytes": self._spill.budget_bytes}))

    # --------------------------------------------------------- mp sharding
    def _shard_tree(self, tree):
        """Commit a params/buffers dict to the mesh with each leaf's
        Megatron annotation (adapter.param_pspec; unmatched leaves
        replicate).  device_put with a NamedSharding — the same
        shard_tensor mechanics as distributed.auto_parallel, minus the
        Tensor wrapper (the engine holds raw jax arrays)."""
        from jax.sharding import NamedSharding

        return {k: jax.device_put(
            v, NamedSharding(self._mesh,
                             self._adapter.param_pspec(k, _MP_AXIS)))
            for k, v in tree.items()}

    def _shard_pools(self, pools):
        """Commit a fresh pool tuple to the mesh on the KV-head dim (the
        adapter owns the per-pool specs — the quantized 4-tuple shards
        its scale pools alongside the payloads)."""
        from jax.sharding import NamedSharding

        specs = self._adapter.pool_pspecs(_MP_AXIS)
        return tuple(jax.device_put(p, NamedSharding(self._mesh, s))
                     for p, s in zip(pools, specs))

    def _new_block_manager(self):
        return BlockManager(self._num_pages, self.page_size,
                            prefix_sharing=self._prefix_sharing,
                            replica=self.replica,
                            bytes_per_page=self._bytes_per_page,
                            pool_dtype=self._pool_dtype,
                            shards=self._mp,
                            radix=self._radix, spill=self._spill,
                            state_bytes_per_seq=self._state_bytes_per_slot,
                            window=self._window_plan)

    # ------------------------------------------------- hierarchical KV cache
    def _spill_snapshot(self, page):
        """Device->host copy of ONE page row across EVERY pool array —
        the KVSpillTier's snapshot callable.  Walking the whole tuple is
        what keeps int8 payload+scale pairs together: the quantized
        adapter's (kp, vp, ks, vs) all slice at the same page index.
        Results in flight need no read-back first: the copy waits for
        every program enqueued before it, and the page it takes is idle
        (a lane in flight writes pages it still holds; one that left
        ahead wrote past its prompt's shared pages)."""
        return tuple(np.asarray(p[:, page]) for p in self._pools)

    def _spill_restore(self, page, payload):
        """Host->device re-page of a resurrected entry into device slot
        ``page``: one scatter per pool (eager ``.at[].set`` — a
        device_put of the host bytes plus a copy that preserves the
        pool's placement/sharding), rebinding the pool tuple like every
        dispatch does."""
        self._pools = tuple(
            p.at[:, page].set(jnp.asarray(a, p.dtype))
            for p, a in zip(self._pools, payload))

    def prefix_index_summary(self):
        """Resident-prefix digests for cross-replica placement (None
        outside radix mode) — ReplicaPool folds this into router states
        and stats() so the PrefixAffinityRouter can send a request to the
        replica with the deepest matching resident run."""
        return self._bm.index_summary()

    def _set_pool_gauges(self):
        self._m_kv_bytes_tok.set(self._bytes_per_page / self.page_size)
        self._m_state_bytes_slot.set(float(self._state_bytes_per_slot))
        self._m_cache_layers.set(float(self._adapter.num_layers))
        self._m_loop_steps.set(
            float(getattr(self._adapter, "loop_steps", 1)))
        # one series PER POOL DTYPE: the quantized engine's f32 scale
        # pools are real device residency — folding them into the int8
        # series used to make serving.pool_bytes disagree with what the
        # arrays actually occupy (ISSUE 12 satellite fix)
        by_dtype = {}
        for p in self._pools:
            dt = str(p.dtype)
            by_dtype[dt] = by_dtype.get(dt, 0) + int(p.nbytes)
        for dt, b in by_dtype.items():
            self._m_pool_bytes.set(float(b), dtype=dt)

    def pool_bytes_by_dtype(self):
        """Actual pool-tuple device bytes, keyed by array dtype (payload
        AND scale pools — what /statusz and the bench memory section
        reconcile against the ledger)."""
        out = {}
        for p in self._pools:
            dt = str(p.dtype)
            out[dt] = out.get(dt, 0) + int(p.nbytes)
        return out

    # ------------------------------------------------------------ lifecycle
    def start(self):
        # error check FIRST: after a scheduler-thread crash _started may
        # still read True, and submit() must reject loudly, not enqueue
        # onto a dead engine
        if self._error is not None:
            raise RuntimeError("engine previously failed") from self._error
        if self._started:
            return self
        with _programs.phase("serving.engine_start"):
            self._modes = [(m, m.training)
                           for m in self._model.sublayers(include_self=True)]
            self._model.eval()
            self._stop_evt.clear()
            self._draining = False
            self._engine_restarts = 0   # a fresh start() is a fresh budget
            self._progress_t = time.monotonic()
            self._thread = threading.Thread(
                target=self._loop,
                name=f"paddle-serving-engine[{self.replica}]", daemon=True)
            self._started = True
            self._thread.start()
            self._start_observability()
        return self

    # ------------------------------------------------------------- warmup
    @_programs.phase("serving.warmup")
    def warmup(self, manifest):
        """Replay a :class:`~paddle_tpu.observability.programs
        .WarmupManifest` ahead of admission: every engine-owned key in the
        manifest is compiled via an INERT dispatch (all lanes inactive —
        scratch table rows, zero lengths — so the program computes junk
        lanes nobody reads and the donated pools round-trip unchanged in
        meaning).  After warmup the first real request dispatches with
        zero new traces.

        Accepts a manifest object, a saved path, or its JSON dict.  Keys
        whose static axes (slot count, table width, pool shape/dtype,
        sampler, guard, mp) don't match THIS engine are skipped, as are
        keys a subclass's request-dependent extras can't replay.  Must run
        before :meth:`start` — replay donates the live pools, which must
        not race the scheduler thread."""
        if self._started:
            raise RuntimeError(
                "warmup() must run before start(): replay dispatches "
                "donate the live page pools")
        if isinstance(manifest, (str, os.PathLike)):
            manifest = _programs.WarmupManifest.load(manifest)
        elif isinstance(manifest, dict):
            manifest = _programs.WarmupManifest.from_json(manifest)
        want = manifest.meta.get("adapter")
        have = self._adapter_signature()
        if want is not None and want != have:
            raise ValueError(
                f"manifest captured for adapter {want}, this engine is "
                f"{have} — replaying would mint useless programs")
        # replay must trace in eval mode, exactly like the scheduler
        modes = [(m, m.training)
                 for m in self._model.sublayers(include_self=True)]
        self._model.eval()
        t0 = time.perf_counter()
        warmed, skipped = 0, []
        try:
            for key in manifest:
                try:
                    ok = self._warm_one(key)
                except Exception as exc:
                    _logger.warning("warmup: replay of %r failed: %r",
                                    key, exc)
                    ok = False
                if ok:
                    warmed += 1
                    ent = _programs.ledger().entry(key, store=self._store())
                    if ent is not None and ent.trace_id is None:
                        ent.trace_id = "warmup"  # provenance: nobody paid
                else:
                    skipped.append(key)
        finally:
            for m, tr in modes:
                m.training = tr
        info = {"warmed": warmed, "skipped": len(skipped),
                "seconds": round(time.perf_counter() - t0, 3)}
        self._warmed = info
        _logger.info("warmup: %(warmed)d programs in %(seconds).2fs "
                     "(%(skipped)d keys skipped)", info)
        return info

    def capture_manifest(self):
        """Snapshot this model's live program-store key set, stamped with
        the adapter signature so :meth:`warmup` refuses a mismatched
        model geometry."""
        return _programs.WarmupManifest.capture(
            self._model, meta={"adapter": self._adapter_signature()})

    def _adapter_signature(self):
        sig = getattr(self._adapter, "signature", None)
        return sig() if callable(sig) else None

    def _warm_one(self, key):
        """Compile one manifest key if it belongs to this engine's static
        configuration.  Returns True when the key is now warm."""
        kind = key[0] if isinstance(key, tuple) and key else None
        if kind == "serve_step" and key == self._step_store_key():
            self._warm_step()
            return True
        if kind == "serve_prefill" and len(key) > 1 \
                and key == self._prefill_store_key(key[1]):
            self._warm_prefill(key[1])
            return True
        if kind == "serve_prefill_chunk" and len(key) > 1 \
                and key == self._prefill_chunk_store_key(key[1]):
            self._warm_prefill_chunk(key[1])
            return True
        if kind == "verify" and self._spec_k and len(key) > 1 \
                and key == self._verify_store_key(self._spec_k):
            self._warm_verify()
            return True
        return False

    def _warm_step(self):
        prog, traces = self._step_program()
        n0 = traces[0]
        if n0:
            return
        guard = self._numeric_guard
        rkey = self._base_key
        extra = self._step_extra()
        tail = (self._numeric_inject(),) if guard else ()
        args = (self._params, self._bufs, self._h_last, *self._pools,
                self._h_table, self._h_lens, self._h_temps, rkey,
                *extra, *tail)
        win = _programs.ledger().compile_window(
            self._step_store_key(), family=self._decode_family(),
            replica=self.replica, device=self._device_label(),
            store=self._store(), owner=self._model, engine=self)
        win.attach(prog, args)
        try:
            if guard:
                _tok, _bad, _st, *pools = prog(*args)
            else:
                _tok, *pools = prog(*args)
            self._pools = tuple(pools)
        finally:
            win.close(traced=traces[0] > n0)

    def _warm_prefill(self, s_pad):
        prog, traces = self._prefill_program(s_pad)
        n0 = traces[0]
        if n0:
            return
        guard = self._numeric_guard
        ids = np.zeros((1, s_pad), np.int64)
        table = self._scratch_row[None].copy()
        lens = np.asarray([s_pad], np.int32)   # junk K/V lands in scratch
        temps = np.zeros((1,), np.float32)
        rkey = self._base_key
        extra = self._warmup_prefill_extra()
        tail = (self._numeric_inject(1),) if guard else ()
        args = (self._params, self._bufs, ids, *self._pools, table, lens,
                temps, rkey, *extra, *tail)
        win = _programs.ledger().compile_window(
            self._prefill_store_key(s_pad),
            family=self._prefill_family(s_pad), replica=self.replica,
            device=self._device_label(), store=self._store(),
            owner=self._model, engine=self)
        win.attach(prog, args)
        try:
            if guard:
                _tok, _bad, _st, *pools = prog(*args)
            else:
                _tok, *pools = prog(*args)
            self._pools = tuple(pools)
        finally:
            win.close(traced=traces[0] > n0)

    def _warm_prefill_chunk(self, c_pad):
        prog, traces = self._prefill_chunk_program(c_pad)
        n0 = traces[0]
        if n0:
            return
        guard = self._numeric_guard
        ids = np.zeros((1, c_pad), np.int64)
        nvalid = np.asarray([c_pad], np.int32)
        table = self._scratch_row[None].copy()
        lens = np.zeros((1,), np.int32)
        temps = np.zeros((1,), np.float32)
        rkey = self._base_key
        extra = self._warmup_prefill_extra()
        tail = (self._numeric_inject(1),) if guard else ()
        args = (self._params, self._bufs, ids, nvalid, *self._pools,
                table, lens, temps, rkey, *extra, *tail)
        win = _programs.ledger().compile_window(
            self._prefill_chunk_store_key(c_pad),
            family=self._prefill_chunk_family(c_pad), replica=self.replica,
            device=self._device_label(), store=self._store(),
            owner=self._model, engine=self)
        win.attach(prog, args)
        try:
            if guard:
                _tok, _bad, _st, *pools = prog(*args)
            else:
                _tok, *pools = prog(*args)
            self._pools = tuple(pools)
        finally:
            win.close(traced=traces[0] > n0)

    def _warm_verify(self):
        prog, traces = self._verify_program()
        n0 = traces[0]
        if n0:
            return
        guard = self._numeric_guard
        rkey = self._base_key
        extra = self._verify_extra([])
        tail = (self._numeric_inject(),) if guard else ()
        args = (self._params, self._bufs, self._h_ids, *self._pools,
                self._h_table, self._h_lens, self._h_dlen, self._h_temps,
                rkey, *extra, *tail)
        win = _programs.ledger().compile_window(
            self._verify_store_key(self._spec_k),
            family=self._verify_family(), replica=self.replica,
            device=self._device_label(), store=self._store(),
            owner=self._model, engine=self)
        win.attach(prog, args)
        try:
            if guard:
                _t, _a, _b, _s, *pools = prog(*args)
            else:
                _t, _a, *pools = prog(*args)
            self._pools = tuple(pools)
        finally:
            win.close(traced=traces[0] > n0)

    def _warmup_prefill_extra(self):
        """Request-independent stand-in for :meth:`_prefill_extra` during
        warmup replay (there is no request).  The base engine's extras
        are empty; subclasses whose extras depend on the request override
        this (or let the per-key try/except skip the key)."""
        return self._prefill_extra(None)

    def program_traces(self):
        """Total trace count across this model's program store (serving
        entries carry a ``[count]`` trace box; generate() pairs don't).
        The warmup invariant — a warmed engine's first request mints
        nothing — is asserted as a zero delta of this sum."""
        total = 0
        for ent in self._store().values():
            if isinstance(ent, tuple) and len(ent) == 2 \
                    and isinstance(ent[1], list) and ent[1] \
                    and isinstance(ent[1][0], int):
                total += ent[1][0]
        return total

    def drain(self, timeout=600):
        """Graceful rundown: stop admitting (submits reject with reason
        ``draining``, /healthz answers 503) and wait for the queue and
        every slot to empty.  Returns True once nothing is in flight;
        raises TimeoutError if work remains after ``timeout``."""
        self._draining = True
        deadline = time.monotonic() + float(timeout)
        while time.monotonic() < deadline:
            if self._error is not None or not self._started:
                return True  # aborted/stopped: nothing left in flight
            if self.quiescent:
                return True
            time.sleep(0.01)
        raise TimeoutError(f"engine did not drain within {timeout}s: "
                           f"{self.stats()}")

    def stop(self, drain=False, drain_timeout=600):
        """Stop the scheduler.  ``drain=True`` first finishes all in-flight
        work (no request ever left hanging); without it, in-flight and
        queued requests FAIL FAST — their handles raise a clear
        :class:`EngineStoppedError` from ``result()``/``stream()`` instead
        of blocking until the caller's timeout."""
        if not self._started:
            return
        if drain:
            self.drain(timeout=drain_timeout)
        self._stop_evt.set()
        with self._cv:
            self._cv.notify_all()
        # generous join: a first-call prefill may sit in a minutes-long XLA
        # compile.  NEVER touch slots/pages while the thread could still be
        # alive — that would double-free pages it is about to retire.
        self._thread.join(timeout=600)
        if self._thread.is_alive():
            raise RuntimeError(
                "serving scheduler thread did not stop within 600s "
                "(stuck in a compile or device call); engine state left "
                "untouched — retry stop() once the call returns")
        for s in self._drop_pending():
            self._fail_stopped(s.handle)
        for i, s in enumerate(self._slots):
            if s is not None:
                self._bm.free(s.alloc)
                self._release_tenant(s.req)
                self._slots[i] = None
                self._fail_stopped(s.handle)
        self._reset_host_buffers()
        with self._lock:
            while self._queue:
                self._fail_stopped(self._queue.popleft().handle)
        self._draining = False
        if self._modes is not None:
            for m, tr in self._modes:
                m.training = tr
            self._modes = None
        if self._watchdog is not None:
            self._watchdog.stop()
        if self._status_provider is not None or self._health_provider is not None:
            # unregister OUR providers only (a newer engine may own the key
            # by now); also frees this engine for GC — the global registry
            # must not pin model params/pools past stop()
            from ..observability import telemetry as _telemetry

            _telemetry.remove_providers_if_owner(
                self._provider_key, self._status_provider,
                self._health_provider)
            self._status_provider = None
            self._health_provider = None
        self._started = False

    def _fail_stopped(self, handle):
        """A request in flight at (non-drain) stop(): fail its handle
        loudly rather than leaving result() to block until timeout."""
        if handle.cancelled:
            self._finish(handle, "cancelled")
            return
        handle._error = EngineStoppedError(
            f"request {handle.request_id} was still in flight when the "
            "engine stopped; use stop(drain=True) to finish in-flight work")
        self._finish(handle, "stopped")

    def _start_observability(self):
        """Opt-in forensics: flight recorder from PADDLE_FLIGHT_DIR, the
        /metrics|/healthz|/statusz endpoint from PADDLE_TELEMETRY_PORT (or
        the ``telemetry_port`` ctor arg; 0 = ephemeral), the wedged-
        scheduler watchdog from PADDLE_SERVING_WATCHDOG_S (or
        ``watchdog_s``).  All default to off: an engine with none of them
        set behaves exactly as before."""
        from ..observability import flight_recorder as _flight
        from ..observability import telemetry as _telemetry
        from ..observability import watchdog as _watchdog

        _flight.maybe_enable_from_env()
        try:
            port = self._telemetry_port
            if port is None:
                env = os.environ.get("PADDLE_TELEMETRY_PORT")
                port = int(env) if env else None
            if port is not None:
                _telemetry.serve(port)
                # registration is KEYED by replica id ("serving/<replica>")
                # so a second engine in the process gets its own /statusz
                # section and /healthz component instead of clobbering the
                # first's, and unregister-on-stop stays per replica
                self._status_provider = self._statusz
                _telemetry.add_status_provider(self._provider_key,
                                               self._status_provider)
                self._health_provider = self.health_state
                _telemetry.add_health_provider(self._provider_key,
                                               self._health_provider,
                                               gating=self._health_gating)
        except Exception as e:
            # opt-in observability must never take down serving startup
            # (EADDRINUSE on a shared port, malformed env value, ...)
            import logging

            logging.getLogger("paddle_tpu.observability").error(
                "telemetry endpoint not started (%r); serving continues "
                "without /metrics|/statusz", e)
        wd = self._watchdog_s
        if wd is None:
            env = os.environ.get("PADDLE_SERVING_WATCHDOG_S")
            wd = float(env) if env else None
        if not wd or wd <= 0:  # 0 is the natural 'disabled' spelling
            wd = None
        if wd is not None and self._watchdog is None:
            self._watchdog = _watchdog.ServingWatchdog(self, deadline_s=wd)
        if self._watchdog is not None:
            self._watchdog.start()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()

    # ------------------------------------------------------------------ api
    def submit(self, prompt_ids, max_new_tokens=32, temperature=0.0,
               eos_token_id=None, deadline_s=None, sampling=None,
               adapter=None, grammar=None, mode="generate", pooling="mean",
               tier=None, _fsm_state=None, _autostart=True):
        """Queue one request; returns a :class:`RequestHandle` immediately.
        ``deadline_s`` is a wall-clock budget from now — a sequence still
        queued or decoding past it is retired with status ``expired``.

        Multi-tenant parameters (:class:`MultiTenantEngine` only — the
        base engine rejects non-defaults loudly): ``adapter`` names a
        registered LoRA adapter serving this row; ``grammar`` is a
        :class:`~.multitenant.grammar.CompiledGrammar` constraining the
        row's output (``_fsm_state`` resumes it mid-document — the
        cluster failover path); ``mode`` picks generate | embed | score
        (embed/score ride the scheduler and prefill programs but retire
        without decode slots or pages); ``pooling`` (mean | last) shapes
        the embed vector.

        ``tier`` names a QoS tier (``ServingEngine(qos=...)`` engines
        only; ``None`` = the config's default tier) — it selects the
        request's queue, admission weight, SLO accounting and preemption
        rank (README "QoS tiers & autoscaling").

        ``_autostart=False`` (the cluster's leg path) never starts a
        stopped engine: the submit is rejected instead, atomically with
        the enqueue, so a leg racing ``stop()`` cannot resurrect the
        replica or enqueue past the stop-time handle sweep."""
        # chaos site: an armed fn here drives deterministic overload (the
        # bench's traffic-spike arm submits a burst from inside the Nth
        # submit call) — disarmed it is one flag check
        _faults.maybe("serving.traffic_spike")
        if self._qos is not None:
            tier = self._qos.resolve(tier)
        elif tier is not None:
            raise ValueError(
                "tier= needs a QoS-enabled engine (ServingEngine(qos=...))")
        prompt = self._normalize_prompt(prompt_ids)
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        eos_token_id = self._validate_tenant(adapter, grammar, mode, pooling,
                                             eos_token_id)
        sampling = sampling if sampling is not None \
            else SamplingParams(temperature=temperature)
        if mode != "generate":
            max_new_tokens = 1          # no decode slot is ever occupied
        total = len(prompt) + int(max_new_tokens)
        handle = RequestHandle(next(self._rid_counter), len(prompt))
        handle.mode = mode
        handle.adapter = adapter
        handle.tier = tier
        if grammar is not None:
            handle._fsm_state = _fsm_state if _fsm_state is not None \
                else grammar.start
        if mode != "generate":
            # embed/score: the prompt runs through the prefill programs
            # against the scratch page — no pages, no decode positions
            if len(prompt) > self.max_model_len:
                self._m_requests.inc(status="rejected")
                raise RequestRejectedError(
                    f"{mode} prompt {len(prompt)} exceeds max_model_len "
                    f"{self.max_model_len}", reason="unservable")
        elif total > self.max_model_len \
                or self._bm.pages_for(total) > self._bm.num_pages:
            self._m_requests.inc(status="rejected")
            raise RequestRejectedError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"needs {self._bm.pages_for(total)} pages / "
                f"{total} positions; engine caps are "
                f"{self._bm.num_pages} pages / {self.max_model_len} positions",
                reason="unservable")
        if _autostart:
            self.start()  # before enqueue: a failed engine rejects loudly
        with _tracing.span("serving.submit", trace_id=handle.trace_id,
                           request_id=handle.request_id,
                           prompt_len=len(prompt)):
            with self._cv:
                # stop() sets _stop_evt before its queue sweep (which holds
                # this lock): a leg either rejects here, or its enqueue
                # precedes the sweep and the sweep fails its handle
                if not _autostart and (not self._started or self._error
                                       is not None
                                       or self._stop_evt.is_set()):
                    raise EngineStoppedError(
                        f"replica {self.replica} is not running")
                if self._draining:
                    self._shed("draining",
                               "engine is draining; not admitting new work",
                               tier=tier)
                if self._qos is not None:
                    self._check_qos_admission(tier)
                if self._max_queue is not None \
                        and len(self._queue) >= self._max_queue:
                    self._shed("queue_full",
                               f"admission queue full ({self._max_queue})",
                               tier=tier)
                if deadline_s is not None:
                    self._check_deadline_meetable(float(deadline_s),
                                                  tier=tier)
                self._preflight_hbm(handle, prompt, total, mode)
                deadline = time.time() + deadline_s \
                    if deadline_s is not None else None
                self._queue.append(Request(prompt, int(max_new_tokens),
                                           sampling, eos_token_id, deadline,
                                           handle, adapter=adapter,
                                           grammar=grammar, mode=mode,
                                           pooling=pooling, tier=tier))
                self._m_requests.inc(status="submitted")
                self._m_queue_depth.set(len(self._queue))
                self._cv.notify_all()
        return handle

    def _validate_tenant(self, adapter, grammar, mode, pooling,
                         eos_token_id):
        """Submit-time validation of the multi-tenant parameters; the
        base engine serves exactly one tenant in one mode, so anything
        non-default is rejected here (MultiTenantEngine overrides).
        Returns the effective ``eos_token_id``."""
        if adapter is not None or grammar is not None \
                or mode != "generate" or pooling != "mean":
            raise ValueError(
                "adapter=/grammar=/mode=/pooling= need a multi-tenant "
                "engine (paddle_tpu.serving.multitenant.MultiTenantEngine)")
        return eos_token_id

    def _shed(self, reason, message, tier=None):
        """Reject at admission with a distinct, machine-readable reason
        (load shedding under pressure beats timing out after queueing).
        The ``tier=`` label is only attached on QoS engines so that
        label-less ``.get(reason=...)`` lookups keep working elsewhere."""
        if tier is not None:
            self._m_shed.inc(reason=reason, tier=tier)
        else:
            self._m_shed.inc(reason=reason)
        self._m_requests.inc(status="rejected")
        raise RequestRejectedError(message, reason=reason)

    def _check_qos_admission(self, tier):
        """SLO-aware admission for QoS engines (called under the cv lock):
        shed whole tiers by the brownout ladder — a tier is shed once the
        protected tier's error-budget burn rate crosses that tier's
        ``shed_burn_rate`` — and enforce per-tier queue caps.  This
        replaces pressure signalling via one global ``queue_full`` gate
        with attribution: during a brownout only the tiers whose
        threshold tripped are rejected."""
        bo = self._brownout()
        if tier in bo["shed"]:
            self._shed(
                "brownout",
                f"tier {tier!r} shed at brownout level {bo['level']} "
                f"({bo['state']}): protected-tier burn rate "
                f"{bo['burn_rate']:.2f}", tier=tier)
        pol = self._qos.tier(tier)
        if pol.max_queue is not None \
                and self._queue.depth(tier) >= pol.max_queue:
            self._shed("queue_full",
                       f"tier {tier!r} queue full ({pol.max_queue})",
                       tier=tier)

    def _preflight_hbm(self, handle, prompt, total, mode):
        """OOM forensics' prevention half (observability/memory.py):
        when ``PADDLE_HBM_BUDGET_BYTES`` is set, project this request's
        worst-case page need against what the budget leaves after the
        fixed allocations (params + buffers + the full page pools are
        already resident; what grows with admission is the COMMITTED
        page count across admitted-but-unfinished requests).  Shedding
        here with ``reason="hbm_budget"`` never changes what admitted
        requests compute — pages either fit or the request never runs —
        so greedy outputs stay byte-identical to an unbudgeted engine."""
        if mode != "generate":
            return                      # no pages are ever committed
        budget = _obs_memory.hbm_budget_bytes()
        if budget is None:
            return
        need = self._bm.pages_for(total)
        headroom = int(budget) - self._fixed_bytes
        page_budget = headroom // self._bytes_per_page if headroom > 0 else 0
        # pools cap the committed total too: never promise pages past P
        page_budget = min(page_budget, self._num_pages)
        with self._commit_lock:
            if self._committed_pages + need > page_budget:
                self._shed(
                    "hbm_budget",
                    f"request needs {need} pages "
                    f"({need * self._bytes_per_page} B) but "
                    f"{self._committed_pages}/{page_budget} budgeted pages "
                    f"are committed (PADDLE_HBM_BUDGET_BYTES={budget}, "
                    f"fixed {self._fixed_bytes} B)")
            self._committed_pages += need
            handle._hbm_pages = need

    def _release_hbm(self, handle):
        """Idempotent un-commit of a handle's pre-flight page reservation
        (every terminal path funnels through ``_finish``)."""
        n = getattr(handle, "_hbm_pages", 0)
        if n:
            handle._hbm_pages = 0
            with self._commit_lock:
                self._committed_pages -= n

    def _check_deadline_meetable(self, deadline_s, tier=None):
        """Deadline-aware admission (called under the cv lock): shed NOW if
        the scheduler has been stalled longer than the whole deadline
        budget, or if the queue-position estimate (queue depth over slots
        times the completed-request duration EMA) already exceeds it —
        rejecting in microseconds beats returning 'expired' after the
        deadline burned queue and pages.

        QoS engines estimate per tier: the duration EMA is the submitting
        tier's own completed-request EMA (one global EMA lets slow
        batch-tier requests inflate the estimate and falsely shed fast
        realtime traffic), and the queue-ahead count only counts requests
        at the same or higher priority — lower tiers behind us in the
        weighted queue (and preemptible under pressure) don't delay us."""
        stamp = self._progress_t
        if stamp is not None and not self._compiling:
            stall = time.monotonic() - stamp
            if stall > max(self._degraded_stall_s, deadline_s):
                self._shed("deadline_unmeetable",
                           f"scheduler stalled for {stall:.2f}s, longer "
                           f"than the {deadline_s:.2f}s deadline",
                           tier=tier)
        if self._qos is not None and tier is not None:
            ema = self._tier_ema.get(tier, self._ema_request_s)
            ahead = self._queue.depth_at_or_above(
                self._qos.tier(tier).priority)
        else:
            ema = self._ema_request_s
            ahead = len(self._queue)
        if ema is not None and ahead:
            est = (ahead / max(self.num_slots, 1) + 1.0) * ema
            if est > deadline_s:
                self._shed(
                    "deadline_unmeetable",
                    f"estimated completion in {est:.2f}s (queue-ahead "
                    f"{ahead}, typical request {ema:.2f}s"
                    + (f" for tier {tier!r}" if tier is not None else "")
                    + f") exceeds the {deadline_s:.2f}s deadline",
                    tier=tier)

    def generate(self, prompt_ids, max_new_tokens=32, timeout=None, **kw):
        """Blocking convenience: submit + wait; returns generated ids."""
        return self.submit(prompt_ids, max_new_tokens, **kw).result(timeout)

    def stream(self, prompt_ids, max_new_tokens=32, **kw):
        """Token-at-a-time iterator (see :meth:`RequestHandle.stream`)."""
        return self.submit(prompt_ids, max_new_tokens, **kw).stream()

    # ------------------------------------------------------------ internals
    @staticmethod
    def _normalize_prompt(prompt_ids):
        arr = prompt_ids
        if hasattr(arr, "numpy"):
            arr = arr.numpy()
        arr = np.asarray(arr)
        if arr.ndim == 2 and arr.shape[0] == 1:
            arr = arr[0]
        if arr.ndim != 1:
            raise ValueError(f"prompt must be 1-D (or [1, S]), "
                             f"got shape {arr.shape}")
        return [int(t) for t in arr]

    def _next_key(self):
        return jax.random.fold_in(self._base_key, next(self._key_counter))

    def _program(self, key, build, family=None):
        store = self._store()
        ent = store.get(key)
        if ent is None:
            t0 = time.perf_counter()
            ent = store[key] = build()
            # every store mint lands a ledger row (provenance + build
            # wall); the dispatch site's compile window adds the stall
            _programs.ledger().record_mint(
                key, family=family or str(key[0]), replica=self.replica,
                device=self._device_label(), store=store,
                owner=self._model, build_s=time.perf_counter() - t0)
        return ent

    def _device_label(self):
        if self._mp > 1:
            return f"mesh[{self._mp}]:{_MP_AXIS}"
        if self._device is not None:
            return str(self._device)
        try:
            return str(jax.devices()[0])
        except Exception:
            return None

    def _guard_key(self):
        """Program-store key component for the numeric-guard variant.
        Empty when the guard is off so the unguarded keys — and therefore
        the cached programs and their trace counters — stay byte-for-byte
        what they were before the guard existed."""
        return ("nguard",) if self._numeric_guard else ()

    def _mp_key(self):
        """Program-store key component for the tensor-parallel variant.
        Pool shapes stay GLOBAL under GSPMD, so without this an mp engine
        sharing the model with an unsharded one would collide with its
        cached single-device programs.  Empty at mp=1 — pre-mesh keys
        (and trace counters) stay byte-for-byte identical."""
        return ("mp", self._mp) if self._mp > 1 else ()

    def _state_key(self):
        """Program-store key component for a pool tuple with per-slot
        state: its shape follows ``num_slots``, which the one-request
        programs' keys do not hold.  Empty without state — those keys stay
        byte-for-byte what they were."""
        return ("state", self._pools[-1].shape) if self._slot_state else ()

    def _store(self):
        from ..text.models._decode import program_store

        return program_store(self._model)

    # program-store key builders — shared by the mint sites, the dispatch
    # sites' compile windows (ledger attribution), and warmup() replay
    def _step_store_key(self):
        return ("serve_step", self.num_slots, self.table_width,
                self._pools[0].shape, str(self._pools[0].dtype),
                self._top) + self._guard_key() + self._mp_key() \
            + self._state_key()

    def _verify_store_key(self, k_pad):
        return ("verify", k_pad, self.num_slots, self.table_width,
                self._pools[0].shape, str(self._pools[0].dtype),
                self._top) + self._guard_key() + self._mp_key() \
            + self._state_key()

    def _prefill_store_key(self, s_pad):
        return ("serve_prefill", s_pad, self.table_width,
                self._pools[0].shape, str(self._pools[0].dtype),
                self._top) + self._guard_key() + self._mp_key() \
            + self._state_key()

    def _prefill_chunk_store_key(self, c_pad):
        return ("serve_prefill_chunk", c_pad, self.table_width,
                self._pools[0].shape, str(self._pools[0].dtype),
                self._top) + self._guard_key() + self._mp_key() \
            + self._state_key()

    def _step_program(self):
        key = self._step_store_key()
        n = len(self._pools)  # pools are DONATED; count is adapter-defined

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._sampler
            guard, gsampler = self._numeric_guard, self._guard_sampler
            low = _numerics.low_dtype()

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def step(params, bufs, last, *rest):
                traces[0] += 1  # python side effect: runs at TRACE time only
                if guard:
                    # trailing [B] f32 inject vector (zeros disarmed, NaN
                    # in one lane when numerics.nan_inject trips) keeps
                    # the program shape independent of fault arming
                    pools, (table, lens, temps, rkey, inj) = \
                        rest[:n], rest[n:]
                    out = adapter.step(params, bufs, last, *pools, table,
                                       lens)
                    logits = out[0] + inj[:, None]
                    tok, bad = gsampler(logits, temps, rkey)
                    stats = _numerics.stats_row(logits, low)[None]
                    return (tok, bad, stats) + tuple(out[1:])
                pools, (table, lens, temps, rkey) = rest[:n], rest[n:]
                out = adapter.step(params, bufs, last, *pools, table, lens)
                return (sampler(out[0], temps, rkey),) + tuple(out[1:])

            return step, traces

        return self._program(key, build, family=self._decode_family())

    def _verify_program(self):
        """The compiled multi-token verification step (speculative
        decoding): the ``("verify", k_pad, …)`` bucket family in the
        program store — one trace per (k, batch-shape, sampler) tuple,
        exactly like the plain decode step."""
        k_pad = self._spec_k
        key = self._verify_store_key(k_pad)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, verifier = self._adapter, self._verifier
            guard = self._numeric_guard
            low = _numerics.low_dtype()

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def verify(params, bufs, ids, *rest):
                traces[0] += 1
                if guard:
                    pools, (table, lens, dlen, temps, rkey, inj) = \
                        rest[:n], rest[n:]
                    out = adapter.verify(params, bufs, ids, *pools, table,
                                         lens)
                    logits = out[0] + inj[:, None, None]
                    targets, accept = verifier(logits, ids[:, 1:], dlen,
                                               temps, rkey)
                    bad = ~jnp.all(jnp.isfinite(logits), axis=(-2, -1))
                    stats = _numerics.stats_row(logits, low)[None]
                    return (targets, accept, bad, stats) + tuple(out[1:])
                pools, (table, lens, dlen, temps, rkey) = rest[:n], rest[n:]
                out = adapter.verify(params, bufs, ids, *pools, table, lens)
                targets, accept = verifier(out[0], ids[:, 1:], dlen, temps,
                                           rkey)
                return (targets, accept) + tuple(out[1:])

            return verify, traces

        return self._program(key, build, family=self._verify_family())

    def _prefill_bucket(self, S0):
        """Padded prefill width for a prompt of ``S0`` tokens: multiples of
        page_size up to ``_PREFILL_POW2_PAGES`` pages, then the next
        power-of-two page count (clamped to the table width) — long-prompt
        traffic mints O(log max_len) compiled prefill programs instead of
        one per page-size increment.  Correctness is untouched: the pad
        region is causally invisible to the logits gather at ``lens-1``,
        and its junk K/V lands in pages a later real write overwrites
        before per-slot ``seq_lens`` masking ever exposes them."""
        ps = self.page_size
        pages = max(1, -(-int(S0) // ps))
        if pages > _PREFILL_POW2_PAGES:
            pages = 1 << (pages - 1).bit_length()
        return min(pages, self.table_width) * ps

    def _prefill_program(self, s_pad):
        key = self._prefill_store_key(s_pad)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._sampler
            guard, gsampler = self._numeric_guard, self._guard_sampler
            low = _numerics.low_dtype()

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(3, 3 + n)))
            def prefill(params, bufs, ids, *rest):
                traces[0] += 1
                if guard:
                    pools, (table, lens, temps, rkey, *extra, inj) = \
                        rest[:n], rest[n:]
                    out = adapter.prefill(params, bufs, ids, *pools, table,
                                          lens, *extra)
                    logits = out[0] + inj[:, None]
                    tok, bad = gsampler(logits, temps, rkey)
                    stats = _numerics.stats_row(logits, low)[None]
                    return (tok, bad, stats) + tuple(out[1:])
                pools, (table, lens, temps, rkey, *extra) = \
                    rest[:n], rest[n:]
                out = adapter.prefill(params, bufs, ids, *pools, table, lens,
                                      *extra)
                return (sampler(out[0], temps, rkey),) + tuple(out[1:])

            return prefill, traces

        return self._program(key, build, family=self._prefill_family(s_pad))

    def _prefill_chunk_program(self, c_pad):
        """The compiled chunked-prefill step: the ``("serve_prefill_chunk",
        C, …)`` family — every chunk of every long prompt reuses ONE trace
        per (chunk width, pool shape, sampler) tuple (trace-count plateau
        asserted in tests).  ``nvalid`` rides as a 4th positional so the
        adapter's ``_split_extra`` tail (LoRA ids/pools) composes
        unchanged; pools are donated from position 4."""
        key = self._prefill_chunk_store_key(c_pad)
        n = len(self._pools)

        def build():
            traces = [0]
            adapter, sampler = self._adapter, self._sampler
            guard, gsampler = self._numeric_guard, self._guard_sampler
            low = _numerics.low_dtype()

            @functools.partial(jax.jit,
                               donate_argnums=tuple(range(4, 4 + n)))
            def chunk(params, bufs, ids, nvalid, *rest):
                traces[0] += 1
                if guard:
                    pools, (table, lens, temps, rkey, *extra, inj) = \
                        rest[:n], rest[n:]
                    out = adapter.prefill_chunk(params, bufs, ids, nvalid,
                                                *pools, table, lens, *extra)
                    logits = out[0] + inj[:, None]
                    tok, bad = gsampler(logits, temps, rkey)
                    stats = _numerics.stats_row(logits, low)[None]
                    return (tok, bad, stats) + tuple(out[1:])
                pools, (table, lens, temps, rkey, *extra) = \
                    rest[:n], rest[n:]
                out = adapter.prefill_chunk(params, bufs, ids, nvalid,
                                            *pools, table, lens, *extra)
                return (sampler(out[0], temps, rkey),) + tuple(out[1:])

            return chunk, traces

        return self._program(key, build,
                             family=self._prefill_chunk_family(c_pad))

    @property
    def step_traces(self):
        """Trace count of this engine's decode-step program (the continuous
        batching invariant: 1 for the engine's lifetime)."""
        try:
            return self._step_program()[1][0]
        except Exception:
            return 0

    # ---------------------------------------------------------- loop thread
    def _loop(self):
        while not self._stop_evt.is_set():
            try:
                # heartbeat FIRST, fault hook second: a wedge injected here
                # leaves the stamp stale exactly like a real stuck iteration
                self._progress_t = time.monotonic()
                _faults.maybe("serving.scheduler_wedge")
                _faults.maybe(self._site_wedge)  # replica-scoped chaos site
                if _faults.armed(self._site_replica_preempt):
                    # injected replica loss (autoscaler reap / cluster
                    # reroute drill): when the site trips, this replica
                    # dies FATALLY — the raised message deliberately
                    # avoids every transient pattern (including the word
                    # in the site name) so classify_failure routes it to
                    # abort, not self-restart
                    before = _faults.trip_count(self._site_replica_preempt)
                    _faults.maybe(self._site_replica_preempt)
                    if _faults.trip_count(self._site_replica_preempt) \
                            > before:
                        raise RuntimeError(
                            f"replica {self.replica} lost: host reclaimed "
                            "by the cluster scheduler (injected replica "
                            "loss)")
                if self._queue or self._pending \
                        or any(s is not None for s in self._slots):
                    # one tree of spans per turn that does work; what its
                    # duration holds beyond its children is the turn's
                    # self time: gauges, ledgers, host-buffer writes
                    with _tracing.span("serving.iteration"):
                        self._turn()
                    continue
                self._update_gauges()
                with _tracing.span("serving.idle_wait"), self._cv:
                    if not self._queue and not self._stop_evt.is_set():
                        self._cv.wait(timeout=0.02)
            except BaseException as e:
                # OOM forensics FIRST, while the allocation state that
                # produced the failure is still live: one flight dump
                # carrying the ledger owner table and per-program peak
                # bytes (observability/memory.py), then normal recovery
                if _obs_memory.is_oom_error(e):
                    try:
                        _obs_memory.oom_dump(e, replica=self.replica)
                    except Exception:
                        pass
                # the budget is a burst limit, not a lifetime one: a full
                # cooldown of healthy operation since the last restart
                # heals it (3 recovered blips spread over weeks must not
                # arm a kill switch for the 4th)
                if self._engine_restarts and self._last_restart_t is not None \
                        and time.monotonic() - self._last_restart_t \
                        > self._restart_cooldown_s:
                    self._engine_restarts = 0
                if classify_failure(e) == "transient" \
                        and self._engine_restarts < self._max_engine_restarts:
                    try:
                        self._recover(e)
                        continue
                    except BaseException as e2:  # recovery itself died
                        e = e2
                # fatal (or restart budget burned): surface to every
                # waiter, don't hang
                self._error = e
                self._abort_all(e)
                return

    def _turn(self):
        """One scheduler iteration with something queued, in a slot or in
        flight."""
        self._turn_no += 1
        with _tracing.span("serving.admit"):
            self._admit()
        # chunked prefill rides the SAME scheduler iteration as the
        # decode dispatch: one budget's worth of chunk work, then the
        # batch decode over the lanes that finished ingesting
        self._advance_prefills()
        self._update_gauges()
        if self._live():
            self._step_once()
        if self._pending and self._pending[-1].turn < self._turn_no:
            # nothing was enqueued this turn (every lane had left or was
            # retired): no later dispatch reads these results for it
            self._m_drains.inc(reason="idle")
            with _tracing.span("serving.device_wait"):
                ready = self._take(len(self._pending))
            self._deliver(ready)

    def _live(self):
        """Does any lane decode (its prompt ingested, its slot held)?"""
        return any(s is not None and s.prefilled is None
                   for s in self._slots)

    def _recover(self, exc):
        """Transient scheduler failure (classified by
        :func:`paddle_tpu.resilience.retry.classify_failure`): rebuild
        device state and transparently re-queue every in-flight request
        instead of failing its handle.  Tokens already emitted stay
        emitted — each request is re-admitted as prompt + tokens-so-far
        with the remaining budget, so a greedy request's final ids are the
        ones an uninterrupted run would have produced."""
        self._engine_restarts += 1
        self._last_restart_t = time.monotonic()
        self._m_engine_restarts.inc()
        _logger.error(
            "serving engine auto-restart %d/%d after transient failure %r; "
            "re-queueing in-flight requests", self._engine_restarts,
            self._max_engine_restarts, exc)
        # results in flight are dropped with the pools they were computed
        # from: ``produced`` counts what was EMITTED, so a re-admission
        # computes the dropped tokens again
        inflight = [(s.req, s.produced) for s in self._drop_pending()]
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                self._release_tenant(s.req)
                inflight.append((s.req, s.produced))
        pending, self._admitting = self._admitting, None
        if pending is not None:
            self._release_tenant(pending)
        if pending is not None and \
                all(req.handle is not pending.handle for req, _ in inflight):
            inflight.append((pending, 0))
        # fresh device state: the page pools were donated into the crashed
        # dispatch; re-admission prefills rewrite every sequence's K/V
        # (a quantized engine rebuilds int8 + scale pools the same way —
        # the adapter owns the layout).  The host spill tier resets with
        # it: spilled bytes would still be valid (K/V is deterministic in
        # tokens + weights) but the rebuilt radix index starts empty, and
        # a coherent cold cache beats a warm one that needs cross-checks.
        if self._spill is not None:
            self._spill.clear()
        self._bm = self._new_block_manager()
        self._pools = tuple(self._adapter.init_pools(self._num_pages + 1))
        if self._device is not None:
            self._pools = jax.device_put(self._pools, self._device)
        elif self._mesh is not None:
            # mp restart: the rebuilt pools re-commit to the mesh with the
            # same KV-head sharding, so re-admission dispatches land on
            # the cached SPMD programs (byte-identical ids, no retrace)
            self._pools = self._shard_pools(self._pools)
        self._set_pool_gauges()
        self._reset_host_buffers()
        with self._lock:
            for req, produced in reversed(inflight):
                h = req.handle
                if h.done:
                    continue
                if h.cancelled:
                    self._finish(h, "cancelled")
                    continue
                remaining = req.max_new_tokens - produced
                if remaining <= 0:  # had finished, crash beat the retire
                    self._finish(h, "completed")
                    continue
                prompt = list(req.prompt) + \
                    ([int(t) for t in h.token_ids[-produced:]]
                     if produced else [])
                h.status = "queued"
                # dataclasses.replace keeps the multi-tenant fields
                # (adapter / grammar / mode) riding across the restart;
                # the LEASE is dropped — re-admission re-acquires against
                # the rebuilt adapter pools.  The grammar state needs no
                # replay: it lives on the HANDLE, already advanced through
                # every emitted token.
                self._queue.appendleft(dataclasses.replace(
                    req, prompt=prompt, max_new_tokens=remaining,
                    lease=None))
                self._m_requeued.inc()
            self._m_queue_depth.set(len(self._queue))

    # --------------------------------------------------- QoS preemption
    def _queue_pop(self, req):
        """Pop the already-peeked head ``req`` (called under the lock).
        QoS engines pop by identity — preemption may have appendleft'd
        victims into lower-priority tiers between the peek and this pop,
        and a positional pop must never swallow a victim."""
        if self._qos is not None:
            self._queue.pop_exact(req)
        else:
            self._queue.popleft()

    def _count_preemption(self, req, reason):
        """serving.preemptions: label-less on non-tiered requests (the
        deadline-expiry path predates QoS; its exact-match ``.get()``
        lookups must keep resolving), ``{tier=,reason=}`` on QoS ones."""
        if req.tier is not None:
            self._m_preempt.inc(tier=req.tier, reason=reason)
        else:
            self._m_preempt.inc()

    def _preempt_victims(self, req):
        """Decode slots ``req`` may evict, cheapest first: strictly
        lower-priority preemptible tiers, ordered lowest priority then
        least produced (minimum re-prefill work on resume).  Slots that
        already hit EOS / budget are skipped — they retire and free their
        resources on the very next step without losing anything."""
        pri = self._qos.tier(req.tier).priority
        out = []
        for i, s in enumerate(self._slots):
            if s is None or s.req.tier is None:
                continue
            pol = self._qos.tier(s.req.tier)
            if not pol.preemptible or pol.priority >= pri:
                continue
            if (s.eos is not None and s.last == s.eos) \
                    or s.produced >= s.max_new:
                continue
            out.append((pol.priority, s.produced, i))
        out.sort()
        return [i for _, _, i in out]

    def _preempt_for_slot(self, req):
        """All slots busy: evict one lower-tier victim so ``req`` admits
        this iteration instead of waiting out a full decode.  Returns the
        freed slot index, or None (non-QoS engine / nothing evictable)."""
        if self._qos is None or req.tier is None:
            return None
        victims = self._preempt_victims(req)
        if not victims:
            return None
        i = victims[0]
        self._preempt_slot(i)
        return i

    def _preempt_for_pages(self, req):
        """Page pool exhausted: evict lower-tier victims until ``req``'s
        allocation fits.  Guarded against thrash — if evicting EVERY
        eligible victim still could not cover the need, nothing is
        evicted and the request parks (blocked), exactly as before."""
        if self._qos is None or req.tier is None:
            return None
        victims = self._preempt_victims(req)
        if not victims:
            return None
        need = self._bm.pages_for(len(req.prompt) + req.max_new_tokens)
        free = self._bm.num_pages - self._bm.used_pages
        gain = sum(len(self._slots[i].alloc.pages) for i in victims)
        if free + gain < need:
            return None
        for i in victims:
            self._preempt_slot(i)
            alloc = self._bm.allocate(
                req.prompt, len(req.prompt) + req.max_new_tokens)
            if alloc is not None:
                return alloc
        return None

    def _preempt_slot(self, i):
        """Evict slot ``i`` for QoS (called under the lock): free its
        pages, clear its lane, and re-queue it at the FRONT of its tier as
        prompt + tokens-so-far with the remaining budget — the _recover
        requeue machinery scheduled on purpose, so a preempted greedy
        request's final ids are byte-identical to an uninterrupted run.
        Tokens already emitted stay emitted."""
        s = self._slots[i]
        h = s.handle
        produced = s.produced       # emitted: a token in flight is dropped
        self._release_lane(i, s)
        if h.cancelled:
            self._finish(h, "cancelled")
            return
        remaining = s.req.max_new_tokens - produced
        if remaining <= 0:      # had finished; eviction beat the retire
            self._finish(h, "completed")
            return
        prompt = list(s.req.prompt) + \
            ([int(t) for t in h.token_ids[-produced:]] if produced else [])
        h.status = "queued"
        h.preemptions += 1
        self._queue.appendleft(dataclasses.replace(
            s.req, prompt=prompt, max_new_tokens=remaining, lease=None))
        self._m_requeued.inc()
        self._count_preemption(s.req, "qos")
        self._last_preempt_t = time.monotonic()
        self._bo_cache = (0.0, None)    # ladder rung changed: drop cache

    def _brownout(self):
        """Current brownout rung (cached ~50ms — burn rates move at
        request cadence, admission runs per submit)."""
        from . import qos as _qos_mod

        now = time.monotonic()
        cached_t, cached = self._bo_cache
        if cached is not None and now - cached_t < 0.05:
            return cached
        preempting = self._last_preempt_t is not None \
            and now - self._last_preempt_t < 1.0
        bo = _qos_mod.brownout(self._qos, self.qos_burn_rate(),
                               preempting=preempting)
        self._bo_cache = (now, bo)
        return bo

    def qos_burn_rate(self):
        """The protected (highest-priority) tier's error-budget burn rate
        — the scalar driving the brownout ladder and the autoscaler; 0.0
        until that tier has completed requests in its window (or on
        non-QoS engines)."""
        if self._qos is None:
            return 0.0
        acct = self._tier_slo.get(self._qos.protected.name)
        if acct is None:
            return 0.0
        cur = acct.current()
        if not cur or cur.get("burn_rate") is None:
            return 0.0
        return float(cur["burn_rate"])

    def begin_drain(self):
        """Non-blocking drain request (autoscaler scale-down): stop
        admitting — submits shed with reason ``draining`` — while
        in-flight work runs to completion.  Poll :attr:`quiescent` to
        learn when the replica can be retired."""
        self._draining = True

    @property
    def quiescent(self):
        """True once nothing is queued or in flight (drain complete)."""
        if self._error is not None or not self._started:
            return True
        with self._lock:
            return not self._queue and not self._pending \
                and all(s is None for s in self._slots) \
                and self._admitting is None

    def _abort_all(self, exc):
        pending, self._admitting = self._admitting, None
        if pending is not None:
            self._release_tenant(pending)
        if pending is not None and not pending.handle.done:
            pending.handle._error = exc
            self._finish(pending.handle, "error")
        for s in self._drop_pending():
            s.handle._error = exc
            self._finish(s.handle, "error")
        for i, s in enumerate(self._slots):
            if s is not None:
                self._bm.free(s.alloc)
                self._release_tenant(s.req)
                self._slots[i] = None
                s.handle._error = exc
                self._finish(s.handle, "error")
        self._reset_host_buffers()
        with self._lock:
            while self._queue:
                req = self._queue.popleft()
                req.handle._error = exc
                self._finish(req.handle, "error")

    def _admit(self):
        while True:
            with self._lock:
                req = None
                while self._queue:
                    cand = self._queue[0]
                    if cand.handle.cancelled:
                        self._queue.popleft()
                        self._finish(cand.handle, "cancelled")
                        continue
                    if cand.deadline is not None \
                            and time.time() > cand.deadline:
                        self._queue.popleft()
                        self._finish(cand.handle, "expired")
                        continue
                    req = cand
                    break
                if req is None:
                    return
                if req.mode != "generate":
                    # embed/score: no decode slot, no pages — runs one
                    # prefill-family dispatch against the scratch page and
                    # retires immediately (multi-tenant engine only; the
                    # base engine's submit validation never queues these)
                    if not self._acquire_tenant(req):
                        return          # adapter slots pinned: stay queued
                    self._queue_pop(req)
                    self._m_queue_depth.set(len(self._queue))
                    self._admitting = req
                    alloc = free_slot = None
                else:
                    free_slot = next((i for i, s in enumerate(self._slots)
                                      if s is None), None)
                    if free_slot is None:
                        # QoS: a full batch must not gate high-tier work —
                        # evict the cheapest strictly-lower-tier slot and
                        # take its lane (no-op on non-QoS engines)
                        free_slot = self._preempt_for_slot(req)
                    if free_slot is None:
                        return
                    alloc = self._bm.allocate(
                        req.prompt, len(req.prompt) + req.max_new_tokens)
                    if alloc is None:
                        alloc = self._preempt_for_pages(req)
                    if alloc is None:
                        # FIFO admission: park until a retirement frees
                        # pages
                        self._m_blocked.inc()
                        return
                    if not self._acquire_tenant(req):
                        # adapter pool pinned solid: the adapter analog of
                        # page exhaustion — stay queued, release the pages
                        self._bm.free(alloc)
                        self._m_blocked.inc()
                        return
                    self._queue_pop(req)
                    self._m_queue_depth.set(len(self._queue))
                    # between dequeue and slot assignment the request lives
                    # in _admitting so a crash mid-prefill can still
                    # requeue it
                    self._admitting = req
            if req.mode != "generate":
                self._run_passthrough(req)
            elif self._chunk_tokens \
                    and len(req.prompt) > self._chunk_tokens:
                self._admit_chunked(req, alloc, free_slot)
            else:
                self._prefill(req, alloc, free_slot)

    def _acquire_tenant(self, req):
        """Pin the request's tenant resources (LoRA adapter slot) for its
        lifetime; False parks the request in the queue.  Base engine: no
        tenants, always True (MultiTenantEngine overrides)."""
        return True

    def _release_tenant(self, req):
        """Counterpart of :meth:`_acquire_tenant` at retirement."""

    def _run_passthrough(self, req):
        """Execute a non-generate (embed/score) request.  Unreachable in
        the base engine — submit validation rejects those modes."""
        raise RuntimeError(
            f"mode={req.mode!r} request reached the base engine scheduler")

    def _prefill(self, req, alloc, slot_idx):
        """Admit ``req`` into lane ``slot_idx`` with ONE dispatch over its
        prompt; the lane decodes from this turn's step on, its first token
        still on the device.

        Hierarchical KV cache: leading pages the radix index matched (or
        the spill tier resurrected) already hold byte-valid K/V, so the
        chunk program runs just the divergent tail at positions
        ``cached..S0-1`` (clamped so at least the last prompt position is
        computed: its logits seed the first token).  Greedy output stays
        byte-identical: K/V at a position is a pure function of the token
        prefix and the weights.  Such a dispatch has its own
        ``prefill/<b>@cached<p>`` perf family and span
        (``serving.prefill_cached``)."""
        h = req.handle
        if h.admitted_at is None:   # TTFT decomposition: queue_s
            h.admitted_at = time.time()
        S0 = len(req.prompt)
        cached = min(alloc.cached_pages * self.page_size, S0 - 1) \
            if alloc.cached_pages else 0
        width = self._prefill_bucket(S0 - cached)
        table_row = self._bm.table_row(alloc, S0 + req.max_new_tokens,
                                       self.table_width)
        if cached > 0:
            fam = self._prefill_cached_family(width, alloc.cached_pages)
            cm = _tracing.span(
                "serving.prefill_cached", trace_id=h.trace_id,
                request_id=h.request_id, slot=slot_idx, prompt_len=S0,
                cached_tokens=cached)
        else:
            fam = self._prefill_family(width)
            cm = _tracing.span(
                "serving.prefill", trace_id=h.trace_id,
                request_id=h.request_id, slot=slot_idx, prompt_len=S0)
        slot = _Slot(req, alloc, table_row)
        slot.idx = slot_idx
        fl = _Flight("prefill", fam, self._turn_no,
                     [(0, slot_idx, slot, 0, 1)], self._m_prefill_seconds)

        def sent():
            # between dequeue and here the request lived in _admitting, so
            # a crash mid-dispatch could still requeue it
            h.status = "running"
            self._slots[slot_idx] = slot
            self._admitting = None
            if slot.temp > 0:
                self._n_temp += 1
            self._go_live(slot, slot_idx, fl.tok)

        self._dispatch(
            fl, cm, *self._ingest_program(req, slot_idx, table_row, cached,
                                          S0 - cached, width, cached > 0),
            (h,), self._m_prefill_traces, sent)

    def _ingest_program(self, req, slot_idx, table_row, start, nval, width,
                        chunk):
        """``(program, traces, store key, arguments)`` of the one-request
        dispatch that ingests prompt tokens ``start .. start+nval-1``
        right-padded to ``width``: the chunk program at positions
        ``start..`` (``chunk``), else the monolithic prefill from 0.
        Pad-lane junk K/V lands past the valid length (or drops OOB):
        invisible to seq_lens masking, overwritten by the first decode
        write."""
        ids = np.zeros((1, width), np.int64)
        ids[0, :nval] = req.prompt[start:start + nval]
        table = self._scratch_row[None].copy()
        table[0, :len(table_row)] = table_row
        temps = np.asarray([req.sampling.temperature], np.float32)
        if chunk:
            prog, traces = self._prefill_chunk_program(width)
            key = self._prefill_chunk_store_key(width)
            head = (ids, np.asarray([nval], np.int32))
            lens = np.asarray([start], np.int32)
        else:
            prog, traces = self._prefill_program(width)
            key = self._prefill_store_key(width)
            head = (ids,)
            lens = np.asarray([nval], np.int32)
        tail = (self._numeric_inject(1),) if self._numeric_guard else ()
        return prog, traces, key, (
            self._params, self._bufs, *head, *self._pools, table, lens,
            temps, self._next_key(), *self._prefill_extra(req, slot_idx),
            *tail)

    def _go_live(self, slot, i, tok):
        """Lane ``i`` decodes from the next step on: its persistent host
        row (rebuilt here and on retire only, never per step), and its
        first token ``tok``, which the host has not read, merged into the
        step's ``last`` on the device."""
        slot.prefilled = None
        slot.unread = 1
        self._h_table[i, :] = self._scratch_row
        self._h_table[i, :len(slot.table_row)] = slot.table_row
        self._h_lens[i] = slot.length
        self._h_temps[i] = slot.temp
        if self._depth:
            self._d_last = _seed_last(self._d_last, np.int32(i), tok)
        self._on_admitted(slot, i)
        if self._drafter is not None:
            # draft context = prompt + every emitted token (re-admission
            # after a restart passes prompt+tokens-so-far as the prompt,
            # so the rebuilt index sees the same stream)
            self._drafter.register(i, slot.req.prompt)
        self._leave_if_spent(i, slot)

    # -------------------------------------------- dispatch and read-back
    def _dispatch(self, fl, cm, prog, traces, key, args, handles, traced,
                  sent):
        """Enqueue one compiled program inside its dispatching span
        ``cm``, do the host's bookkeeping that its results do not feed
        (``sent``), then read back what EARLIER dispatches left (this
        one's too at depth 0) and deliver that."""
        if _perf.needs_cost(fl.fam):
            # capture arg shapes ONCE per family; the cost_analysis
            # re-lower+compile itself runs lazily, off this thread
            _perf.register_cost_thunk(fl.fam,
                                      _perf.jit_cost_thunk(prog, args))
        n0 = traces[0]
        # first dispatch of a program = minutes-long XLA compile: the
        # ledger compile window flags self._compiling for the watchdog/
        # health paths, holds programs.compile_in_progress up, and bills
        # the stall to the TTFT decomposition of every waiting request
        win = _programs.ledger().compile_window(
            key, family=fl.fam, replica=self.replica,
            device=self._device_label(), store=self._store(),
            owner=self._model, handles=handles, engine=self, cold=n0 == 0)
        win.attach(prog, args)
        fl.t0 = time.perf_counter()
        try:
            with cm:
                with _tracing.span("serving.dispatch"):
                    out = prog(*args)
                    k = 1
                    fl.tok = out[0]
                    if self._numeric_guard:
                        k = 3
                        fl.bad, fl.nstats = out[1], out[2]
                    self._pools = tuple(out[k:])
                fl.cold = traces[0] > n0
                sent()
                self._pending.append(fl)
                with _tracing.span("serving.device_wait"):
                    ready = self._read_back(fl)
        finally:
            win.close(traced=traces[0] > n0)
            self._progress_t = time.monotonic()
        if fl.cold:
            traced.inc(traces[0] - n0)
        self._deliver(ready)

    def _read_back(self, fl):
        """Inside ``fl``'s dispatching span, after its enqueue: read the
        results that are due.  Depth 0: everything.  Depth 1: what the
        turns BEFORE this one left, once this turn's last program is
        enqueued behind it (the step; a chunk that no step follows), so
        the device goes on while the host blocks here; everything, when
        the dispatch left no slot held and nothing can run ahead."""
        pend = self._pending
        if not self._depth:
            n = len(pend)
        elif all(s is None for s in self._slots):
            n = len(pend)
            self._m_drains.inc(reason="no_lane")
        elif fl.kind == "step" or (fl.kind == "chunk" and not self._live()):
            n = sum(1 for f in pend if f.turn < self._turn_no)
        else:
            n = 0
        return self._take(n)

    def _take(self, n):
        """Read the ``n`` oldest dispatches' results, in order.  They are
        popped only once every read is through: a read that raises leaves
        them all in flight for whoever recovers."""
        ready = [self._read(f) for f in itertools.islice(self._pending, n)]
        for _ in range(n):
            self._pending.popleft()
        return ready

    def _read(self, fl):
        """Block for one dispatch's results: ``(fl, tok, bad)`` on the
        host.  A chunk that yields no token and carries no guard has
        nothing to read."""
        tok = bad = None
        if any(ln[4] for ln in fl.lanes):
            tok = np.asarray(fl.tok)
        if fl.bad is not None:
            bad = np.asarray(fl.bad)
        fl.t_read = time.perf_counter()
        return fl, tok, bad

    def _deliver(self, ready):
        """Results read back, in dispatch order: observe what each cost,
        emit its tokens, retire.  A lane whose slot was taken from the
        request since the dispatch has a newer generation: its result is
        dropped, not emitted."""
        if not ready:
            return
        stream = f"serving/{self.replica}"
        with _tracing.span("serving.emit"):
            for fl, tok, bad in ready:
                if fl.kind == "step":
                    # what a decode step costs the loop: between two
                    # steps' results reaching the host, or from its own
                    # dispatch where the last result was read before it
                    dt = fl.t_read - (self._t_step if fl.ahead else fl.t0)
                    self._t_step = fl.t_read
                    self._m_step_seconds.observe(dt)
                else:
                    dt = fl.t_read - fl.t0      # dispatch -> host
                    fl.hist.observe(dt)
                if not fl.cold:
                    # warm dispatch: attribute its time to the program
                    # family (a trace+compile wall is not device time)
                    _perf.record(fl.fam, dt)
                if fl.nstats is not None:
                    _numerics.submit(stream, ("logits",), fl.nstats,
                                     step=self._iteration)
                for row, i, slot, gen, expects in fl.lanes:
                    if slot.gen != gen:
                        if expects:
                            self._m_discarded.inc()
                        continue
                    slot.unread -= expects
                    if bad is not None and bad[row]:
                        # this row's logits went non-finite: fail exactly
                        # this request; finite rows emit unchanged tokens
                        self._fail_numeric(i, slot)
                        continue
                    if not expects:
                        continue
                    slot.produced += 1
                    slot.last = int(tok[row])
                    if self._slots[i] is slot:
                        self._h_last[i, 0] = slot.last
                    self._emit_token(slot, slot.last)
                    if not self._retire_if_done(i, slot) \
                            and self._drafter is not None:
                        # the drafter's context must keep growing or it
                        # would never find a matching suffix again
                        self._drafter.extend(i, [slot.last])

    def _drop_pending(self):
        """Forget every result in flight (the pools are rebuilt, or the
        engine ends).  Returns the slots that had left their lane ahead of
        their last tokens: no entry of ``_slots`` holds them any more, so
        whoever drops the results settles their requests."""
        left, n = [], 0
        for fl in self._pending:
            for _, i, slot, gen, expects in fl.lanes:
                n += expects if slot.gen == gen else 0
                if self._slots[i] is not slot and slot.gen == gen \
                        and not slot.handle.done and slot not in left:
                    left.append(slot)
        self._pending.clear()
        if n:
            self._m_discarded.inc(n)
        return left

    def _leave_if_spent(self, i, slot):
        """What the host knows ahead it acts on ahead: with its budget
        dispatched to the end, ``slot`` leaves lane ``i`` and frees its
        pages now; the tokens still in flight are its last and are emitted
        when read.  (At depth 0 the read-back retires it, at once.)"""
        if self._depth and slot.produced + slot.unread >= slot.max_new:
            self._release_lane(i, slot, drop=False)

    def _release_lane(self, i, slot, drop=True):
        """Take lane ``i`` from ``slot``'s request: its pages return to the
        pool and the lane backfills at the next admit.  Whatever is still
        in flight for it is dropped unread, unless it leaves ahead
        (``drop=False``)."""
        if drop:
            slot.gen += 1
        if self._slots[i] is slot:
            self._bm.free(slot.alloc, reached=slot.length)
            self._release_tenant(slot.req)
            self._slots[i] = None
            self._clear_slot_row(i, slot)

    # ------------------------------------------------- chunked prefill
    def _admit_chunked(self, req, alloc, slot_idx):
        """Admit a long prompt WITHOUT running its prefill: the slot goes
        live immediately with ``prefilled=0`` and ingests chunk-by-chunk
        via :meth:`_advance_prefills`, interleaved with decode — the
        decode batch never waits out a monolithic long-prompt dispatch.
        The lane's persistent host row stays inert (scratch table, length
        0) until the final chunk seeds decode."""
        table_row = self._bm.table_row(
            alloc, len(req.prompt) + req.max_new_tokens, self.table_width)
        if req.handle.admitted_at is None:   # TTFT decomposition: queue_s
            req.handle.admitted_at = time.time()
        slot = _Slot(req, alloc, table_row)
        slot.idx = slot_idx
        # hierarchical KV cache: ingestion starts PAST the cached shared
        # run (chunked prefill already admits at arbitrary offsets — the
        # radix hit just moves the starting offset); clamped so the final
        # chunk computes at least the last prompt position, whose logits
        # seed decode
        slot.prefilled = min(alloc.cached_pages * self.page_size,
                             max(len(req.prompt) - 1, 0))
        req.handle.status = "running"
        self._slots[slot_idx] = slot
        self._admitting = None
        # _n_temp counts LIVE slots with temperature: incremented at
        # admission (not at go-live) so the retire paths' _clear_slot_row
        # decrement stays balanced whether or not ingestion completed
        if slot.temp > 0:
            self._n_temp += 1

    def _advance_prefills(self):
        """One scheduler iteration's chunked-prefill work: up to
        ``prefill_chunk_tokens`` prompt tokens across the mid-prefill
        slots, round-robin so concurrent long prompts share the budget
        fairly.  Cancelled/expired slots retire here — they must not wait
        for a decode lane they never reached."""
        if not self._chunk_tokens:
            return
        prefilling = [i for i, s in enumerate(self._slots)
                      if s is not None and s.prefilled is not None]
        if not prefilling:
            return
        start = self._prefill_rr
        order = sorted(prefilling,
                       key=lambda i: (i - start) % self.num_slots)
        budget = self._chunk_tokens
        for i in order:
            if budget <= 0:
                return
            s = self._slots[i]
            if s is None or s.prefilled is None:
                continue
            h = s.handle
            if h.cancelled or (s.deadline is not None
                               and time.time() > s.deadline):
                status = "cancelled" if h.cancelled else "expired"
                if status == "expired":
                    self._count_preemption(s.req, "deadline")
                self._release_lane(i, s)
                self._finish(h, status)
                continue
            budget -= self._prefill_chunk_step(i, s)
            self._prefill_rr = (i + 1) % self.num_slots

    def _prefill_chunk_step(self, i, slot):
        """Dispatch ONE chunk of slot ``i``'s prompt: tokens
        ``prefilled .. prefilled+C-1`` (right-padded on the last chunk)
        through the chunk cache variant at positions ``prefilled..``.
        Only the FINAL chunk's sampled token is anyone's: it seeds decode
        and the lane goes live; the others' results are never read (but
        for the guard's flag).  Returns the number of real prompt tokens
        ingested (the budget unit)."""
        req = slot.req
        C = self._chunk_tokens
        c0 = slot.prefilled
        nval = min(C, len(req.prompt) - c0)
        final = c0 + nval >= len(req.prompt)
        h = req.handle
        fl = _Flight("chunk", self._prefill_chunk_family(C), self._turn_no,
                     [(0, i, slot, slot.gen, int(final))],
                     self._m_prefill_chunk_seconds)

        def sent():
            slot.prefilled = c0 + nval
            if final:
                self._go_live(slot, i, fl.tok)

        self._dispatch(
            fl, _tracing.span("serving.prefill_chunk", trace_id=h.trace_id,
                              request_id=h.request_id, slot=i,
                              chunk_start=c0, chunk_tokens=nval),
            *self._ingest_program(req, i, slot.table_row, c0, nval, C, True),
            (h,), self._m_prefill_chunk_traces, sent)
        return nval

    def _step_key(self):
        """PRNG key for a decode dispatch.  A batch with no temperature
        rows never consumes randomness (the batched sampler/verifier
        returns argmax for ``temps <= 0`` rows), so the hot greedy path
        skips the per-step ``fold_in`` device dispatch and reuses the base
        key — one less host->device round trip per step."""
        return self._next_key() if self._n_temp else self._base_key

    def _step_once(self):
        # chaos site: an injected fn raising a TransientError here drives
        # the auto-restart + requeue path through the real scheduler
        # (covers BOTH the plain decode step and the speculative verify
        # step — a crash between verifies must requeue with exactly the
        # accepted-token state)
        _faults.maybe("serving.step_crash")
        _faults.maybe(self._site_step_crash)  # replica-scoped chaos site
        # mid-prefill chunked slots stay OUT of the decode batch: their
        # host rows are inert (scratch table, length 0) so the dispatch
        # computes a junk lane nobody reads
        active = [i for i, s in enumerate(self._slots)
                  if s is not None and s.prefilled is None]
        if self._spec_k:
            return self._verify_once(active)
        return self._plain_step(active)

    # ----------------------------------------------- multi-tenant hooks
    # Extension points MultiTenantEngine fills in; the base engine's
    # returns keep every dispatch signature and program family unchanged.
    def _prefill_family(self, s_pad):
        return f"prefill/{s_pad}{self._fam_suffix}{self._mp_suffix}"

    def _prefill_chunk_family(self, c):
        return f"prefill_chunk/{c}{self._fam_suffix}{self._mp_suffix}"

    def _prefill_cached_family(self, c, cached_pages):
        """Partial-prefix prefill attribution: the dispatch runs the
        chunk program at width ``c`` but only because ``cached_pages``
        leading pages were served from the hierarchical cache — a
        different roofline (tail-only compute) than a full prefill, so
        perf.is_cached_prefill_family can key hints on it."""
        return (f"prefill/{c}@cached{cached_pages}"
                f"{self._fam_suffix}{self._mp_suffix}")

    def _decode_family(self):
        return f"decode{self._fam_suffix}{self._mp_suffix}"

    def _verify_family(self):
        return f"verify/k{self._spec_k}{self._fam_suffix}{self._mp_suffix}"

    def _prefill_extra(self, req, slot_idx=None):
        """Host arrays appended to the prefill dispatch (adapter ids,
        grammar mask, adapter pools).  Where the adapter keeps a per-slot
        state, the slot's index: a one-request program's row is no slot
        (no request, as in a warm-up replay: the scratch row)."""
        if not self._slot_state:
            return ()
        return (np.asarray([self.num_slots if slot_idx is None
                            else slot_idx], np.int32),)

    def _step_extra(self):
        """Host arrays appended to the decode dispatch."""
        return ()

    def _host_makes_step_inputs(self):
        """Does the host make a decode step's inputs from the LAST step's
        sampled token (a drafter's proposals; a subclass's per-token
        masks)?  Then every dispatch is read back at once (depth 0)."""
        return bool(self._spec_k)

    def _verify_extra(self, active):
        """Host arrays appended to the verify dispatch (reads the draft
        buffers _h_ids/_h_dlen the caller just filled)."""
        return ()

    def _filter_draft(self, i, draft):
        """Trim a slot's n-gram draft before verification (a constrained
        row truncates at the first grammar-illegal token)."""
        return draft

    def _on_admitted(self, slot, i):
        """A request landed in decode lane ``i`` (persistent host rows
        already rebuilt)."""

    def _budget_status(self, slot):
        """Terminal status when ``max_new_tokens`` runs out.  The base
        engine's budget exhaustion IS completion; a grammar-constrained
        row cut off mid-document reports ``truncated`` instead
        (MultiTenantEngine)."""
        return "completed"

    # ------------------------------------------------ NaN-safe serving
    def _numeric_inject(self, B=None):
        """Trailing ``[B] f32`` inject vector for guarded dispatches: all
        zeros disarmed (the shape-stable no-op — the program adds it to
        the logits), NaN in lane :func:`~.numerics.nan_inject_row` when
        the ``numerics.nan_inject`` fault tripped since the last call."""
        if B is None:
            B = self.num_slots
        inj = np.zeros((B,), np.float32)
        v = _numerics.consume_nan_inject()
        if not np.isfinite(v):
            inj[_numerics.nan_inject_row() % B] = v
        return inj

    def _fail_numeric(self, i, slot):
        """Retire ``slot`` (decode lane ``i``'s, unless it left ahead) with
        a numeric fault: exactly this request errors (``status="error"``,
        ``handle._error`` a :class:`NumericFault`), its pages free and the
        lane backfills at the next admit — the batch's other rows are
        untouched."""
        h = slot.handle
        h._error = NumericFault(
            f"non-finite logits in decode lane {i}", site="logits",
            stream=f"serving/{self.replica}", step=self._iteration)
        self._m_numeric_faults.inc()
        self._release_lane(i, slot)
        self._finish(h, "error")

    def _quant_drift_tick(self):
        """Sampled quantization-drift gauge (quant engines): one
        Int8Linear per tick, dequantize its stored payload and measure
        the requantize-on-fresh-absmax roundtrip error — drift above the
        rounding floor means the frozen ``w_scale`` no longer matches
        the weights it quantized."""
        from ..quantization import Int8Linear

        layers = [m for m in self._model.sublayers()
                  if isinstance(m, Int8Linear)]
        if not layers:
            return
        m = layers[self._drift_idx % len(layers)]
        self._drift_idx += 1
        q = np.asarray(m.weight_int8._value, np.float32)
        w = q * m.w_scale
        amax = float(np.abs(w).max())
        if amax <= 0.0:
            self._m_quant_drift.set(0.0)
            return
        s2 = amax / m._qmax
        q2 = np.clip(np.rint(w / s2), -m._qmax, m._qmax)
        drift = float(np.mean(np.abs(q2 * s2 - w))) / amax
        self._m_quant_drift.set(drift)

    def _plain_step(self, active):
        """Enqueue ONE decode step over the lanes ``active`` and advance
        them on the host by what needs no sampled value (``length + 1``;
        a lane whose budget is now dispatched to the end leaves ahead),
        then read back what the previous turn left."""
        prog, traces = self._step_program()
        slots = [self._slots[i] for i in active]
        # one span per batched iteration, LINKING every active request's
        # trace id (a decode step serves many traces at once — the OTLP
        # links model, not one parent); the list is built only for a sink
        # that keeps it
        cm = _tracing.span(
            "serving.decode_step", self._links(slots),
            iteration=self._iteration, batch=len(active))
        fl = _Flight("step", self._decode_family(), self._turn_no,
                     [(i, i, s, s.gen, 1) for i, s in zip(active, slots)])
        fl.ahead = any(f.kind == "step" for f in self._pending)
        # the host advances its rows while the program may still read them
        # (a transfer in flight; the CPU backend takes numpy memory as it
        # is): each dispatch gets rows of its own
        rows = (self._h_table.copy(), self._h_lens.copy(),
                self._h_temps.copy())
        tail = (self._numeric_inject(),) if self._numeric_guard else ()
        args = (self._params, self._bufs, self._d_last, *self._pools, *rows,
                self._step_key(), *self._step_extra(), *tail)

        def sent():
            self._observe_dispatch(len(active))
            if fl.ahead:
                self._m_ahead.inc()
            if self._depth:
                self._d_last = _carry_last(fl.tok)
            for i, s in zip(active, slots):
                s.length += 1
                s.unread += 1
                self._h_lens[i] = s.length
                self._leave_if_spent(i, s)

        # first decode dispatch = XLA compile; every active request waits
        # out the whole stall, so the window bills each of their TTFTs
        self._dispatch(fl, cm, prog, traces, self._step_store_key(), args,
                       [s.handle for s in slots], self._m_step_traces, sent)

    @staticmethod
    def _links(slots):
        """The trace ids of the requests a batched step serves, as a
        span's lazy attributes (of the slots at dispatch: a lane may have
        left by the time a sink asks)."""
        return lambda: {"links": [s.handle.trace_id for s in slots]}

    def _observe_dispatch(self, lanes):
        """One decode / verify dispatch is made: the counts at that
        boundary (the pool's use before a lane that leaves ahead frees its
        pages)."""
        self._m_decode_batch.observe(lanes)
        self._m_step_page_util.observe(self._bm.utilization())
        self._iteration += 1

    def _verify_once(self, active):
        """One speculative iteration: draft up to k tokens per slot from
        the n-gram index, verify all of them (plus the pending last token)
        in ONE compiled multi-token dispatch, then consume the longest
        accepted prefix per slot + the bonus/resample token — 1..k+1
        tokens per slot per step, with retire/deadline/EOS checks applied
        per emitted token exactly like the single-token path."""
        K = self._spec_k
        drafts = {}
        for i in active:
            s = self._slots[i]
            self._h_ids[i, 0] = s.last
            self._h_ids[i, 1:] = 0
            # never draft past the request budget or the position cap: the
            # bonus token always lands, so at most remaining-1 drafts fit
            cap = min(K, s.max_new - s.produced - 1,
                      self.max_model_len - s.length - 1)
            d = self._drafter.propose(i, cap) if cap > 0 else []
            d = self._filter_draft(i, d)
            if d:
                self._h_ids[i, 1:1 + len(d)] = d
            self._h_dlen[i] = len(d)
            drafts[i] = d
        if not any(drafts.values()):
            # nothing drafted anywhere this iteration: the (k+1)-wide
            # verify dispatch would pay (k+1)x attention/FFN to emit one
            # token per slot — the plain step is the same result cheaper
            return self._plain_step(active)
        prog, traces = self._verify_program()
        n0 = traces[0]
        rkey = self._step_key()
        extra = self._verify_extra(active)
        guard = self._numeric_guard
        tail = (self._numeric_inject(),) if guard else ()
        fam = self._verify_family()
        if _perf.needs_cost(fam):
            _perf.register_cost_thunk(fam, _perf.jit_cost_thunk(
                prog, (self._params, self._bufs, self._h_ids, *self._pools,
                       self._h_table, self._h_lens, self._h_dlen,
                       self._h_temps, rkey, *extra, *tail)))
        cm = _tracing.span(
            "serving.verify_step",
            self._links([self._slots[i] for i in active]),
            iteration=self._iteration, batch=len(active), k=K,
            drafted=sum(len(drafts[i]) for i in active))
        win = _programs.ledger().compile_window(
            self._verify_store_key(K), family=fam, replica=self.replica,
            device=self._device_label(), store=self._store(),
            owner=self._model,
            handles=[self._slots[i].handle for i in active],
            engine=self, cold=n0 == 0)
        if n0 == 0:
            win.attach(prog, (self._params, self._bufs, self._h_ids,
                              *self._pools, self._h_table, self._h_lens,
                              self._h_dlen, self._h_temps, rkey,
                              *extra, *tail))
        t0 = time.perf_counter()
        bad = nstats = None
        try:
            with cm:
                with _tracing.span("serving.dispatch"):
                    if guard:
                        targets, accept, bad, nstats, *pools = prog(
                            self._params, self._bufs, self._h_ids,
                            *self._pools, self._h_table, self._h_lens,
                            self._h_dlen, self._h_temps, rkey, *extra, *tail)
                    else:
                        targets, accept, *pools = prog(
                            self._params, self._bufs, self._h_ids,
                            *self._pools, self._h_table, self._h_lens,
                            self._h_dlen, self._h_temps, rkey, *extra)
                    self._pools = tuple(pools)
                with _tracing.span("serving.device_wait"):
                    targets = np.asarray(targets)
                    accept = np.asarray(accept)
        finally:
            win.close(traced=traces[0] > n0)
            self._progress_t = time.monotonic()
        if traces[0] > n0:
            self._m_verify_traces.inc(traces[0] - n0)
        else:
            _perf.record(fam, time.perf_counter() - t0)
        self._m_step_seconds.observe(time.perf_counter() - t0)
        self._observe_dispatch(len(active))
        if guard:
            _numerics.submit(f"serving/{self.replica}", ("logits",), nstats,
                             step=self._iteration)
            bad = np.asarray(bad)
        proposed = accepted = 0
        with _tracing.span("serving.emit"):
            for i in active:
                s = self._slots[i]
                if guard and bad[i]:
                    self._fail_numeric(i, s)
                    continue
                d = drafts[i]
                a = 0
                while a < len(d) and accept[i, a]:
                    a += 1
                proposed += len(d)
                emitted = [int(t) for t in d[:a]] + [int(targets[i, a])]
                # pool state: positions length..length+a now hold the old
                # `last` + the a accepted drafts; rejected tail K/V sits past
                # the new length, where seq_lens masking hides it until the
                # next chunk write overwrites it (rollback = lens stays put)
                done = False
                emitted_n = 0
                for tok in emitted:
                    s.length += 1
                    s.produced += 1
                    s.last = tok
                    self._h_lens[i] = s.length
                    self._h_last[i, 0] = tok
                    self._emit_token(s, tok)
                    emitted_n += 1
                    if self._retire_if_done(i, s):
                        done = True
                        break
                # accepted = drafts that became OUTPUT tokens: early retirement
                # (EOS mid-draft, deadline, budget) discards the rest, and the
                # acceptance-rate gauge must not credit discarded tokens
                accepted += min(emitted_n, a)
                if not done:
                    self._drafter.extend(i, emitted)
        if proposed:
            self._m_spec_proposed.inc(proposed)
            self._spec_proposed_total += proposed
        if accepted:
            self._m_spec_accepted.inc(accepted)
            self._spec_accepted_total += accepted
        if self._spec_proposed_total:
            self._m_accept_rate.set(
                self._spec_accepted_total / self._spec_proposed_total)

    def _emit_token(self, slot, tok):
        h = slot.handle
        now = time.time()
        # QoS engines label the latency histograms per tier (the bench's
        # per-tier p95s); non-tiered requests keep the label-less children
        # so existing exact-match lookups stay resolvable
        tier = slot.req.tier
        if h.first_token_at is None:
            h.first_token_at = now
            h.first_token_iteration = self._iteration
            if tier is not None:
                self._m_ttft.observe(now - h.submitted_at, tier=tier)
            else:
                self._m_ttft.observe(now - h.submitted_at)
            if h.compile_s > 0.0:
                # compile-paying first token: parallel family (not a label
                # on serving.ttft_seconds — existing per-replica children
                # and their bucket alignment stay byte-identical) so p95
                # TTFT dashboards can subtract cold starts
                self._m_ttft_cold.observe(now - h.submitted_at)
        elif slot.last_token_t is not None:
            if tier is not None:
                self._m_itl.observe(now - slot.last_token_t, tier=tier)
            else:
                self._m_itl.observe(now - slot.last_token_t)
        slot.last_token_t = now
        h.token_ids.append(tok)
        h.token_times.append(now)
        h._events.put(("token", tok))
        self._m_tokens.inc()

    def _retire_if_done(self, i, slot):
        """After ``slot`` (lane ``i``'s, unless it left ahead) emitted a
        token: end its request if that token, its budget, a cancel or its
        deadline says so."""
        h = slot.handle
        status = None
        if h.cancelled:
            status = "cancelled"
        elif slot.eos is not None and slot.last == slot.eos:
            status = "completed"
        elif slot.produced >= slot.max_new:
            status = self._budget_status(slot)
        elif slot.deadline is not None and time.time() > slot.deadline:
            status = "expired"
            self._count_preemption(slot.req, "deadline")
        if status is None:
            return False
        self._release_lane(i, slot)
        self._finish(h, status)
        return True

    def _clear_slot_row(self, i, slot):
        """Reset slot ``i``'s persistent host-buffer row (and drafter
        state) after retirement — the row points at scratch again so the
        next dispatch treats the lane as inactive."""
        self._h_table[i, :] = self._scratch_row
        self._h_lens[i] = 0
        self._h_temps[i] = 0.0
        self._h_last[i, 0] = 0
        if self._spec_k:
            self._h_ids[i, :] = 0
            self._h_dlen[i] = 0
        if slot.temp > 0:
            self._n_temp -= 1
        if self._drafter is not None:
            self._drafter.release(i)

    def _reset_host_buffers(self):
        """Full reset (engine restart / stop): every lane inactive."""
        self._h_table[:] = self._scratch_row
        self._h_lens[:] = 0
        self._h_temps[:] = 0.0
        self._h_last[:] = 0
        self._d_last = self._h_last
        if self._spec_k:
            self._h_ids[:] = 0
            self._h_dlen[:] = 0
        self._n_temp = 0
        if self._drafter is not None:
            self._drafter.reset()

    def _finish(self, handle, status):
        self._release_hbm(handle)
        handle.status = status
        handle.finished_at = time.time()
        handle.finished_iteration = self._iteration
        if status == "completed":
            # completed-request duration EMA feeds deadline-aware shedding
            dur = handle.finished_at - handle.submitted_at
            self._ema_request_s = dur if self._ema_request_s is None \
                else 0.8 * self._ema_request_s + 0.2 * dur
            tier = getattr(handle, "tier", None)
            if tier is not None:
                # per-tier EMA: a slow batch request must not inflate the
                # realtime deadline estimate (see _check_deadline_meetable)
                prev = self._tier_ema.get(tier)
                self._tier_ema[tier] = dur if prev is None \
                    else 0.8 * prev + 0.2 * dur
        if self._slo is not None and status in ("completed", "expired") \
                and handle.mode == "generate":
            # expired = the deadline preempted it: an SLO miss by
            # definition, whatever its timeline says.  cancelled/stopped/
            # error requests are excluded — they measure the caller or the
            # engine, not the latency promise.
            self._slo.observe(handle, met_override=False
                              if status == "expired" else None)
        if self._tier_slo and status in ("completed", "expired") \
                and handle.mode == "generate":
            acct = self._tier_slo.get(getattr(handle, "tier", None))
            if acct is not None:
                acct.observe(handle, met_override=False
                             if status == "expired" else None)
        self._m_requests.inc(status=status)
        handle._events.put(("done", status))
        handle._done.set()

    def _update_gauges(self):
        # throttled: gauges are dashboards, not control flow — refreshing
        # six of them before EVERY decode dispatch was measurable host
        # overhead on the sub-ms step path (queue_depth is also refreshed
        # eagerly at submit/admit, where it actually changes)
        now = time.monotonic()
        if now - self._gauges_t < 0.05:
            return
        self._gauges_t = now
        n = sum(1 for s in self._slots if s is not None)
        self._m_queue_depth.set(len(self._queue))
        self._m_active.set(n)
        self._m_occupancy.set(n / self.num_slots)
        self._m_page_util.set(self._bm.utilization())
        self._m_pages_used.set(self._bm.used_pages)
        self._m_health.set(_HEALTH_CODE.get(self.health, 1))
        if self._qos is not None:
            for tname, depth in self._queue.depths().items():
                self._m_tier_depth.set(depth, tier=tname)
            active = dict.fromkeys(self._qos.names, 0)
            for s in self._slots:
                if s is not None and s.req.tier in active:
                    active[s.req.tier] += 1
            for tname, cnt in active.items():
                self._m_tier_active.set(cnt, tier=tname)
        if self.weight_dtype == "int8" and now - self._drift_t > 5.0:
            # quant drift is a slow dashboard (host-side weight walk):
            # one sampled layer every few seconds, never per step
            self._drift_t = now
            self._quant_drift_tick()
        if self._numeric_guard and now - self._npoll_t > 0.5:
            # resolve THIS replica's pending numerics table (one small
            # device sync) so the numerics.* gauges and /statusz stay
            # fresh; never raising — per-row failure is the guard's job,
            # an abort-level checker must not kill the scheduler thread
            self._npoll_t = now
            _numerics.poll(f"serving/{self.replica}", raise_on_fault=False)

    # --------------------------------------------------------------- health
    def health_state(self):
        """The health state machine surfaced on /healthz and /statusz:

        - ``healthy`` — scheduler progressing, queue under pressure limits;
        - ``degraded`` — serving, but queue pressure, a stalled scheduler,
          or a recent auto-restart says trouble (reasons list which);
        - ``draining`` — graceful rundown, no new admissions (503);
        - ``stopped`` / ``error`` — not serving.
        """
        if self._error is not None:
            return {"state": "error", "reasons": [repr(self._error)]}
        if self._draining:
            return {"state": "draining", "reasons": ["drain requested"]}
        if not self._started:
            return {"state": "stopped", "reasons": []}
        reasons = []
        qd = len(self._queue)
        if self._max_queue and qd >= max(1, int(0.8 * self._max_queue)):
            reasons.append(f"queue_pressure:{qd}/{self._max_queue}")
        stamp = self._progress_t
        busy = qd or self._pending \
            or any(s is not None for s in self._slots)
        if busy and stamp is not None and not self._compiling:
            age = time.monotonic() - stamp
            if age > self._degraded_stall_s:
                reasons.append(f"scheduler_stalled:{age:.2f}s")
        if self._last_restart_t is not None and \
                time.monotonic() - self._last_restart_t \
                < self._restart_cooldown_s:
            reasons.append(f"recent_restart:{self._engine_restarts}")
        if self._qos is not None:
            bo = self._brownout()
            if bo["level"]:
                # a brownout is degraded-but-serving: high tiers are fine
                # BY CONSTRUCTION of the shed, but operators must see it
                reasons.append(f"brownout:L{bo['level']}:{bo['state']}")
        return {"state": "degraded" if reasons else "healthy",
                "reasons": reasons}

    @property
    def health(self):
        return self.health_state()["state"]

    # -------------------------------------------------------------- insight
    @property
    def block_manager(self):
        return self._bm

    @property
    def slo_accountant(self):
        """The replica's SLO accountant (None unless ``slo=`` was set)."""
        return self._slo

    @property
    def acceptance_rate(self):
        """Lifetime speculative acceptance (None before any proposal)."""
        if not self._spec_proposed_total:
            return None
        return self._spec_accepted_total / self._spec_proposed_total

    def stats(self):
        st = {
            "replica": self.replica,
            "iteration": self._iteration,
            "queue_depth": len(self._queue),
            "active_slots": sum(1 for s in self._slots if s is not None),
            "num_slots": self.num_slots,
            "pages_in_use": self._bm.used_pages,
            "num_pages": self._bm.num_pages,
            "page_utilization": self._bm.utilization(),
            "step_traces": self.step_traces,
            # quantized-serving surface: what the pools are made of and
            # what a page/token costs in HBM (scale pools included)
            "kv_dtype": self.kv_dtype,
            "weight_dtype": self.weight_dtype,
            "pool_dtype": self._pool_dtype,
            # per-shard under mp (the per-chip capacity unit)
            "bytes_per_page": self._bytes_per_page,
            "kv_bytes_per_token": self._bytes_per_page / self.page_size,
            "mp": self._mp,
            "numeric_guard": self._numeric_guard,
            "prefill_chunk_tokens": self._chunk_tokens,
            "prefilling_slots": sum(
                1 for s in self._slots
                if s is not None and s.prefilled is not None),
        }
        if self._spec_k:
            st["speculative"] = {
                "k": self._spec_k,
                "proposed": self._spec_proposed_total,
                "accepted": self._spec_accepted_total,
                "acceptance_rate": self.acceptance_rate,
            }
        if self._prefix_sharing:
            # hierarchical-cache surface: hit/saved-token accounting (hit
            # TOKENS, not counts — the satellite fix) plus, in radix
            # mode, the resident-prefix summary the cluster's
            # deepest-match placement consumes via ReplicaPool.stats()
            bm_stats = self._bm.stats()
            st["prefix_cache"] = bm_stats.get("prefix_cache")
            summ = self.prefix_index_summary()
            if summ is not None:
                st["prefix_index"] = summ
        return st

    def _statusz(self):
        """/statusz provider: stats + the live slot table (diagnostic
        snapshot — reads race the scheduler thread benignly)."""
        st = self.stats()
        st["kv_cache"] = self._bm.stats()   # pool dtype + bytes/page live
        # memory observability: this replica's ledger owner rows (cheap —
        # no live-array walk; signal-path rule: no engine lock is held),
        # the pool tuple's actual per-dtype residency, and the admission
        # pre-flight state
        st["memory"] = {
            "owners": _obs_memory.ledger().owner_rows(replica=self.replica),
            "pool_bytes_by_dtype": self.pool_bytes_by_dtype(),
            # per-chip residency under mp (global // mp — the head dim
            # splits exactly; == pool_bytes_by_dtype at mp=1)
            "pool_shard_bytes_by_dtype": {
                dt: b // self._mp
                for dt, b in self.pool_bytes_by_dtype().items()},
            "fixed_bytes": self._fixed_bytes,
            "committed_pages": self._committed_pages,
            "hbm_budget_bytes": _obs_memory.hbm_budget_bytes(),
        }
        st["started"] = self._started
        st["error"] = repr(self._error) if self._error is not None else None
        st["health"] = self.health_state()
        st["engine_restarts"] = self._engine_restarts
        st["draining"] = self._draining
        st["typical_request_s"] = self._ema_request_s
        if self._slo is not None:
            st["slo"] = self._slo.summary()
        if self._qos is not None:
            # per-tier queue table + ladder rung: makes a brownout's shed
            # decisions attributable from the status page alone
            active = dict.fromkeys(self._qos.names, 0)
            for s in self._slots:
                if s is not None and s.req.tier in active:
                    active[s.req.tier] += 1
            st["qos"] = {
                "config": self._qos.to_dict(),
                "brownout": self._brownout(),
                "queue_by_tier": self._queue.depths(),
                "active_by_tier": active,
                "typical_request_s_by_tier": dict(self._tier_ema),
                "slo_by_tier": {name: acct.summary()
                                for name, acct in self._tier_slo.items()},
            }
        if self._progress_t is not None:
            st["last_progress_age_s"] = time.monotonic() - self._progress_t
        slots = []
        for i, s in enumerate(self._slots):
            if s is None:
                slots.append(None)
                continue
            slots.append({"slot": i, "request_id": s.handle.request_id,
                          "trace_id": s.handle.trace_id,
                          "status": s.handle.status, "length": s.length,
                          "produced": s.produced, "max_new": s.max_new,
                          "pages": len(s.alloc.pages),
                          "prefilled": s.prefilled})
        st["slots"] = slots
        return st
