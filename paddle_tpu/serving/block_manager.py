"""Paged KV block manager — the allocator side of the serving engine.

vLLM block-manager analog over this repo's page-pool layout: the engine
owns per-layer GLOBAL page pools ``[L, P, page_size, h, d]`` (see
``ops.paged_attention``); this module owns which of the ``P`` rows belong
to which live sequence.  Everything here is host-side Python — the device
only ever sees the ``[B, NP]`` page table the engine rebuilds from these
allocations.

Capacity-based admission control: :meth:`allocate` returns ``None`` when
the pool cannot cover a sequence's worst case (prompt + max_new_tokens),
and the engine keeps the request queued instead of admitting it — no
mid-flight page exhaustion, so no copy-out preemption path is needed.

Prefix sharing (``prefix_sharing=True``): pages FULLY covered by a prompt
are content-addressed by the token prefix they encode (K/V at position p
is a pure function of tokens 0..p and the weights, so the page for
positions ``[i*ps, (i+1)*ps)`` is keyed by ``prompt[:(i+1)*ps]``).  Two
live sequences with identical prompt prefixes share those physical pages
(refcounted); decode never writes them — a sequence's first generated
token lands at position ``len(prompt)``, which is always past the last
fully-covered page.  When the last holder retires, shared pages park in an
idle cache and are resurrected on the next identical prefix (or evicted
LRU when the free list runs dry).

Hierarchical KV cache (``radix=True``): exact-key matching is replaced by
the page-granular radix tree in :mod:`.prefix_index` — ``allocate``
reuses the *longest shared page run* (partial-prefix matches bump
refcounts on the shared run; only the divergent tail allocates fresh
pages) and reports how many leading pages already hold valid K/V
(``PageAllocation.cached_pages``), which is what lets the engine START
prefill at ``cached_pages * page_size`` tokens instead of recomputing
the shared run.  With a :class:`~paddle_tpu.serving.kv_spill.KVSpillTier`
attached, idle pages evicted to refill the free list spill their bytes to
host DRAM first, and a later allocate whose match ends where a spilled
prefix begins resurrects them into fresh device slots — still cached,
one PCIe copy instead of a forward pass.  In legacy mode memory sharing
is real but prefill compute still runs per sequence.
"""

from __future__ import annotations

import collections
import threading


class PageAllocation:
    """One live sequence's pages, in sequence order.  The first
    ``len(shared_keys)`` entries are refcounted prefix pages; the rest are
    private and return to the free list on :meth:`BlockManager.free`.
    ``cached_pages`` counts the LEADING shared pages whose K/V was already
    valid at allocate time (radix hit or spill resurrection) — the prompt
    tokens they cover need no prefill compute; it is always 0 in legacy
    (exact-key) mode, where sharing saves memory but not compute."""

    __slots__ = ("pages", "shared_keys", "cached_pages")

    def __init__(self, pages, shared_keys=(), cached_pages=0):
        self.pages = list(pages)
        self.shared_keys = tuple(shared_keys)
        self.cached_pages = int(cached_pages)

    @property
    def num_shared(self):
        return len(self.shared_keys)

    def __len__(self):
        return len(self.pages)


class BlockManager:
    def __init__(self, num_pages, page_size, prefix_sharing=False,
                 replica="0", bytes_per_page=None, pool_dtype=None,
                 shards=1, radix=False, spill=None, state_bytes_per_seq=0):
        if num_pages < 1:
            raise ValueError(f"num_pages must be >= 1, got {num_pages}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self.radix = bool(radix)
        self.prefix_sharing = bool(prefix_sharing) or self.radix
        self.replica = str(replica)
        # HBM accounting (quantized serving): what one page costs across
        # all layers, K+V, scale pools included, and what the pool rows
        # are made of — the engine fills these in so capacity math and the
        # /statusz slot table talk in bytes, not just page counts.
        # Tensor-parallel serving: ``shards`` records the mesh split of
        # the pools and ``bytes_per_page`` is then the PER-SHARD (per-chip)
        # cost — a 2-way-sharded pool holds 2x the resident sequences at
        # the same per-chip HBM budget, which is exactly what
        # :meth:`max_resident_sequences` with ``budget_bytes`` reports
        self.bytes_per_page = int(bytes_per_page) \
            if bytes_per_page is not None else None
        self.pool_dtype = str(pool_dtype) if pool_dtype is not None else None
        # what ONE resident sequence holds beside its pages: a fixed-size
        # recurrent state a slot (hybrid decoders), whatever its length
        self.state_bytes_per_seq = int(state_bytes_per_seq)
        self.shards = int(shards)
        self._free = collections.deque(range(self.num_pages))
        self._active = {}                       # prefix key -> [page, refs]
        self._idle = collections.OrderedDict()  # prefix key -> page (refs 0)
        self._index = None
        self._spill = None
        if self.radix:
            from .prefix_index import RadixPrefixIndex

            self._index = RadixPrefixIndex(self.page_size)
            self._spill = spill  # KVSpillTier or None (radix mode only)
        elif spill is not None:
            raise ValueError("the KV spill tier needs radix=True (spilled "
                             "pages are resurrected through the radix "
                             "index's content addresses)")
        # allocate/free are engine-lock-serialized in normal operation,
        # but the allocator must stay correct for any caller (the pfx
        # concurrency tests hammer it from threads) — one internal mutex
        self._mut = threading.Lock()
        # prefix-cache observability: hits = sharable pages whose key was
        # resident (active refcount bump, idle resurrection, or host-tier
        # re-page), misses = sharable pages allocated fresh, evictions =
        # idle prefix pages reclaimed because the free list ran dry,
        # saved_tokens = hit pages x page_size — the counter that weights
        # a 100-page hit 100x a 1-page hit.  Series carry replica= (the
        # engine's id) so N engines in one process stay distinct.
        from ..profiler import metrics as _metrics

        self._m_hits = _metrics.bind(_metrics.counter(
            "serving.prefix_cache_hits",
            "prefix-sharing pages reused from the active/idle cache"),
            replica=self.replica)
        self._m_misses = _metrics.bind(_metrics.counter(
            "serving.prefix_cache_misses",
            "sharable prefix pages that had to be allocated fresh"),
            replica=self.replica)
        self._m_evictions = _metrics.bind(_metrics.counter(
            "serving.prefix_cache_evictions",
            "idle prefix pages evicted LRU to refill the free list"),
            replica=self.replica)
        self._m_saved = _metrics.bind(_metrics.counter(
            "serving.prefix_cache_saved_tokens",
            "prompt tokens covered by prefix-cache page hits "
            "(hit pages x page_size)"),
            replica=self.replica)
        self._hits = 0
        self._misses = 0
        self._evictions = 0
        self._saved_tokens = 0
        self._resurrections = 0

    # ------------------------------------------------------------ accounting
    def pages_for(self, num_tokens):
        return -(-int(num_tokens) // self.page_size)

    @property
    def _idle_count(self):
        return self._index.idle_pages if self.radix else len(self._idle)

    @property
    def free_pages(self):
        """Pages obtainable right now (free list + evictable idle cache)."""
        return len(self._free) + self._idle_count

    @property
    def used_pages(self):
        return self.num_pages - self.free_pages

    def utilization(self):
        return self.used_pages / self.num_pages

    def stats(self):
        """Allocator snapshot, HBM-denominated when the engine supplied
        ``bytes_per_page``/``pool_dtype`` (quantized serving: the int8
        pool's bytes_per_page is ~half bf16's, which is exactly the
        resident-slot win)."""
        st = {
            "num_pages": self.num_pages,
            "page_size": self.page_size,
            "used_pages": self.used_pages,
            "free_pages": self.free_pages,
            "utilization": self.utilization(),
            "prefix_sharing": self.prefix_sharing,
            "bytes_per_page": self.bytes_per_page,
            "pool_dtype": self.pool_dtype,
            "shards": self.shards,
        }
        if self.bytes_per_page is not None:
            # per-shard (per-chip) bytes when the pools are mesh-sharded
            st["pool_bytes"] = self.num_pages * self.bytes_per_page
            st["used_bytes"] = self.used_pages * self.bytes_per_page
            st["kv_bytes_per_token"] = self.bytes_per_page / self.page_size
        if self.state_bytes_per_seq:
            st["state_bytes_per_seq"] = self.state_bytes_per_seq
        if self.prefix_sharing:
            # hit TOKENS, not just hit counts: saved_tokens is hit pages x
            # page_size, so a 100-page shared-run hit reads as 100x the
            # win of a 1-page hit (the hierarchical-cache satellite fix)
            pc = {
                "hits": self._hits,
                "misses": self._misses,
                "evictions": self._evictions,
                "saved_tokens": self._saved_tokens,
                "mode": "radix" if self.radix else "lru",
            }
            if self.radix:
                pc["resurrections"] = self._resurrections
                pc["index"] = self._index.stats()
                if self._spill is not None:
                    pc["spill"] = self._spill.stats()
            st["prefix_cache"] = pc
        st["fragmentation"] = self.fragmentation()
        return st

    def index_summary(self):
        """Resident-prefix digests for cross-replica placement (None in
        legacy mode) — exported through engine.stats() / ReplicaPool
        states so the PrefixAffinityRouter can find the replica with the
        deepest matching resident run (cluster/router.py)."""
        if not self.radix:
            return None
        with self._mut:
            return self._index.summary()

    def fragmentation(self):
        """Free-list fragmentation snapshot (memory observability): runs
        of CONTIGUOUS free page indices, their largest length, and a
        power-of-two run-length histogram, plus the evictable idle
        prefix pages sitting outside the free list.  Paged attention is
        indifferent to contiguity (any row works), so this is a
        diagnostic for allocator churn and for future contiguous-DMA
        kernels, not an admission input."""
        runs = []
        run = 0
        prev = None
        for p in sorted(self._free):
            if prev is not None and p == prev + 1:
                run += 1
            else:
                if run:
                    runs.append(run)
                run = 1
            prev = p
        if run:
            runs.append(run)
        hist = {}
        for r in runs:
            lo = 1 << (r.bit_length() - 1)
            key = f"{lo}" if lo == 1 else f"{lo}-{2 * lo - 1}"
            hist[key] = hist.get(key, 0) + 1
        return {
            "free_pages": len(self._free),
            "free_runs": len(runs),
            "largest_free_run": max(runs, default=0),
            "run_histogram": hist,
            "evictable_idle_pages": self._idle_count,
        }

    def max_resident_sequences(self, tokens_per_seq, budget_bytes=None):
        """Capacity math: how many sequences of ``tokens_per_seq`` worst
        case fit — in this pool, or in a hypothetical pool of
        ``budget_bytes`` HBM at this manager's bytes_per_page (the
        occupancy comparison the int8 acceptance test and the bench arm
        assert on).  ``budget_bytes`` is PER CHIP: with mesh-sharded
        pools (shards > 1) bytes_per_page is the per-shard cost, so the
        same budget admits ``shards``x the sequences of the unsharded
        engine — the mp HBM-capacity win, asserted by the mp tests."""
        per_seq = self.pages_for(tokens_per_seq)
        pages = self.num_pages
        if budget_bytes is not None:
            if self.bytes_per_page is None:
                raise ValueError("budget_bytes needs bytes_per_page")
            if self.state_bytes_per_seq:
                # a sequence costs its pages AND its per-slot state
                return int(budget_bytes) // (
                    per_seq * self.bytes_per_page + self.state_bytes_per_seq)
            pages = int(budget_bytes) // self.bytes_per_page
        return pages // per_seq

    # ------------------------------------------------------------ allocation
    def _pop_free(self):
        if self._free:
            return self._free.popleft()
        # free list dry: evict the least-recently-idled shared prefix page
        if self.radix:
            ev = self._index.evict_one()
            if ev is None:
                raise RuntimeError("page pool exhausted with nothing idle "
                                   "(admission plan should have refused)")
            key, page = ev
            self._m_evictions.inc()
            self._evictions += 1
            if self._spill is not None:
                # snapshot BEFORE the row is reused — the hierarchical
                # cache's device->host demotion
                self._spill.spill(key, page)
            return page
        _, page = self._idle.popitem(last=False)
        self._m_evictions.inc()
        self._evictions += 1
        return page

    def _prefix_hits(self, prompt_ids, n_sharable):
        """Longest run of already-resident prefix pages (legacy exact-key
        mode).  A miss at page i implies misses after it: whoever
        registered a longer prefix also registered every shorter one."""
        hits = []
        for i in range(n_sharable):
            key = tuple(prompt_ids[:(i + 1) * self.page_size])
            if key in self._active or key in self._idle:
                hits.append(key)
            else:
                break
        return hits

    def can_allocate(self, prompt_ids, num_tokens):
        with self._mut:
            return self._plan(prompt_ids, num_tokens) is not None

    def _plan(self, prompt_ids, num_tokens):
        need = self.pages_for(num_tokens)
        n_sharable = 0
        if self.prefix_sharing:
            # pages fully covered by the prompt; decode's first write goes
            # to position len(prompt), past all of them even when the
            # prompt ends exactly on a page boundary
            n_sharable = min(len(prompt_ids) // self.page_size, need)
        if self.radix:
            blocks = self._index.blocks_of(prompt_ids, n_sharable)
            depth, idle_matched = self._index.match_depth(
                prompt_ids, n_sharable)
            fresh = need - depth
            if fresh > len(self._free) + (self._index.idle_pages
                                          - idle_matched):
                return None
            return need, n_sharable, blocks
        hits = self._prefix_hits(prompt_ids, n_sharable) \
            if n_sharable else []
        fresh = need - len(hits)
        idle_hits = sum(1 for k in hits if k in self._idle)
        if fresh > len(self._free) + (len(self._idle) - idle_hits):
            return None
        return need, n_sharable, hits

    def _record_hits(self, pages, prompt_len):
        self._m_hits.inc(pages)
        self._hits += pages
        saved = pages * self.page_size
        if prompt_len is not None:
            saved = min(saved, max(int(prompt_len) - 1, 0))
        self._m_saved.inc(saved)
        self._saved_tokens += saved

    def allocate(self, prompt_ids, num_tokens):
        """Reserve pages covering ``num_tokens`` for a sequence with this
        prompt; ``None`` when the pool can't satisfy it (caller keeps the
        request queued).  ``num_tokens`` must include the prompt AND every
        token the sequence may generate."""
        prompt_ids = [int(t) for t in prompt_ids]
        if num_tokens < len(prompt_ids):
            raise ValueError("num_tokens must cover the prompt")
        with self._mut:
            plan = self._plan(prompt_ids, num_tokens)
            if plan is None:
                return None
            if self.radix:
                return self._allocate_radix(prompt_ids, plan)
            return self._allocate_legacy(prompt_ids, plan)

    def _allocate_radix(self, prompt_ids, plan):
        need, n_sharable, blocks = plan
        ps = self.page_size
        # tier 1 — device-resident radix match: pin the longest shared
        # run (splitting a mid-run divergence at the page boundary)
        pages, _, tip = self._index.acquire(blocks)
        cached = len(pages)
        # tier 2 — host-tier resurrection: extend the run with spilled
        # pages re-paged into fresh device slots (still valid K/V)
        new_blocks, new_pages = [], []
        while (self._spill is not None and cached < n_sharable
               and len(self._free) + self._index.idle_pages > 0):
            key = tuple(prompt_ids[:(cached + 1) * ps])
            if not self._spill.contains(key):
                break
            page = self._pop_free()
            if not self._spill.resurrect(key, page):
                # raced away (shouldn't happen under the mutex): the slot
                # holds junk — return it and fall through to the fresh
                # loop, which registers it as a to-be-written page
                self._free.appendleft(page)
                break
            new_blocks.append(blocks[cached])
            new_pages.append(page)
            cached += 1
            self._resurrections += 1
        if cached:
            self._record_hits(cached, len(prompt_ids))
        # tier 3 — recompute: fresh sharable pages for the divergent
        # tail (prefill will write them), then private non-sharable pages
        fresh_shar = n_sharable - cached
        if fresh_shar > 0:
            self._m_misses.inc(fresh_shar)
            self._misses += fresh_shar
            for i in range(cached, n_sharable):
                new_blocks.append(blocks[i])
                new_pages.append(self._pop_free())
        self._index.insert(tip, new_blocks, new_pages)
        pages = pages + new_pages
        keys = [tuple(prompt_ids[:(i + 1) * ps]) for i in range(n_sharable)]
        for _ in range(n_sharable, need):
            pages.append(self._pop_free())
        # cached counts pages whose K/V is already byte-valid on device;
        # resurrections included — the engine starts prefill past them
        return PageAllocation(pages, keys,
                              cached_pages=min(cached, n_sharable))

    def _allocate_legacy(self, prompt_ids, plan):
        need, n_sharable, hits = plan
        pages, keys = [], []
        if hits:
            self._record_hits(len(hits), len(prompt_ids))
        for key in hits:
            ent = self._active.get(key)
            if ent is not None:
                ent[1] += 1
            else:
                ent = self._active[key] = [self._idle.pop(key), 1]
            pages.append(ent[0])
            keys.append(key)
        for i in range(len(hits), need):
            key = tuple(prompt_ids[:(i + 1) * self.page_size]) \
                if i < n_sharable else None
            # idle keys are not prefix-closed (LRU eviction drops them
            # independently), so a key past the first hit-miss can still sit
            # idle: claim it here, or free() would later overwrite the idle
            # entry and orphan its page from the pool
            if key is not None and key in self._idle:
                page = self._idle.pop(key)
                self._record_hits(1, len(prompt_ids))
            else:
                page = self._pop_free()
                if key is not None:
                    self._m_misses.inc()
                    self._misses += 1
            pages.append(page)
            if key is not None:  # new shareable prefix page: register it
                self._active[key] = [page, 1]
                keys.append(key)
        # legacy exact-key sharing saves memory, never compute
        return PageAllocation(pages, keys, cached_pages=0)

    def free(self, alloc: PageAllocation):
        """Release a retired sequence's pages: private pages return to the
        free list; shared prefix pages decref and park in the idle cache
        when the last holder leaves."""
        with self._mut:
            if self.radix:
                if alloc.shared_keys:
                    full = alloc.shared_keys[-1]
                    self._index.release(self._index.blocks_of(
                        full, len(alloc.shared_keys)))
            else:
                for key in alloc.shared_keys:
                    ent = self._active[key]
                    ent[1] -= 1
                    if ent[1] == 0:
                        del self._active[key]
                        self._idle[key] = ent[0]
            for page in alloc.pages[alloc.num_shared:]:
                self._free.append(page)
            alloc.pages = []
            alloc.shared_keys = ()
            alloc.cached_pages = 0

    # ----------------------------------------------- passthrough run sharing
    def acquire_run(self, prompt_ids, limit=None):
        """Pin (and extend) the shared run for a PASSTHROUGH dispatch
        (multi-tenant ``mode="embed"|"score"``): the longest resident
        radix match is refcounted, spilled extensions resurrect, and —
        unlike :meth:`allocate` — the remaining sharable blocks register
        fresh pages only while the free list has slack (a passthrough
        warming the cache never evicts someone else's resident prefix).
        Returns ``(pages, cached_pages)`` covering ``len(pages)`` leading
        blocks, or ``None`` outside radix mode / for sub-page prompts.
        The caller MUST :meth:`release_run` the same prompt/depth after
        the dispatch; it holds real refcounts until then."""
        if not self.radix:
            return None
        prompt_ids = [int(t) for t in prompt_ids]
        n = len(prompt_ids) // self.page_size
        if limit is not None:
            n = min(n, int(limit))
        if n <= 0:
            return None
        with self._mut:
            blocks = self._index.blocks_of(prompt_ids, n)
            pages, _, tip = self._index.acquire(blocks)
            cached = len(pages)
            new_blocks, new_pages = [], []
            while (self._spill is not None and cached < n and self._free
                   and self._spill.contains(
                       tuple(prompt_ids[:(cached + 1) * self.page_size]))):
                page = self._free.popleft()
                key = tuple(prompt_ids[:(cached + 1) * self.page_size])
                if not self._spill.resurrect(key, page):
                    # entry raced away between contains and resurrect: the
                    # slot holds junk, so it must join the run as a FRESH
                    # (to-be-written) block, never a cached one
                    self._free.appendleft(page)
                    break
                self._resurrections += 1
                new_blocks.append(blocks[cached])
                new_pages.append(page)
                cached += 1
            if cached:
                self._record_hits(cached, None)
            while len(pages) + len(new_pages) < n and self._free:
                i = len(pages) + len(new_pages)
                new_blocks.append(blocks[i])
                new_pages.append(self._free.popleft())
                self._m_misses.inc()
                self._misses += 1
            self._index.insert(tip, new_blocks, new_pages)
            return pages + new_pages, cached

    def release_run(self, prompt_ids, depth):
        """Unpin a run :meth:`acquire_run` returned (``depth`` =
        ``len(pages)``); the run parks idle and stays resident for the
        next passthrough/generate sharing the prefix."""
        if not self.radix or depth <= 0:
            return
        prompt_ids = [int(t) for t in prompt_ids]
        with self._mut:
            self._index.release(self._index.blocks_of(prompt_ids, depth))
