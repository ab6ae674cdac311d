"""Per-program roofline attribution — WHO spent the device time, and WHY.

The PR-1/PR-3 layers can see wall time (op timers, spans) but cannot say
which *compiled program* spent it, nor whether that program is HBM-bound
or compute-bound — exactly the information ROADMAP item 3 (close the
0.255/0.379 MFU gap, beat 0.958x paged decode) needs to pick kernel
targets.  This module turns BENCH_r04's one-off roofline numbers into a
live table:

- every compiled-program family (the ``program_store`` families:
  ``prefill/<bucket>``, ``decode``, ``verify/k<k>`` — with an ``@int8``
  suffix when the engine serves quantized KV pools — ``generate.decode``,
  ``train_step/t<n>.v<i>`` — ``t<n>`` scopes per TrainStep instance, so
  two models training in one process never fold into one family)
  accumulates **calls** and **device seconds** as the dispatch sites
  record them (engine step/prefill/verify timers, ``decode_loop``,
  ``TrainStep.__call__``).  Engine families are deliberately COARSE:
  replicas over one model share compiled programs and should share a
  family; heterogeneous engines in one process (different models or pool
  shapes) fold together — pair such engines with their own process, or
  read the per-replica serving.* histograms instead;
- each family lazily attaches **XLA cost_analysis** flops/bytes (a
  re-lower+compile, so it runs on demand or on a background thread —
  never on the dispatch path, never inside a telemetry scrape);
- the table derives achieved TFLOP/s, achieved GB/s, arithmetic
  intensity, the **roofline regime** (bandwidth- vs compute-bound against
  ``PADDLE_PEAK_FLOPS`` and a measured-or-configured HBM ceiling,
  ``PADDLE_HBM_GBS``), and fraction-of-the-binding-peak.

Exported three ways: ``perf.program.*`` metrics in the PR-1 registry, a
``perf_programs`` section on ``/statusz`` (sorted by total device time),
and :func:`report` — a ``Profiler.summary()``-style text table naming the
top fusion/kernel candidates.

"Device seconds" here are host-observed dispatch-to-sync walls at the
recording sites (the engine syncs every iteration; ``decode_loop`` syncs
once per generate call) — the same convention every BENCH number uses, so
fractions-of-peak line up with the bench roofline.

Ceiling resolution order (both axes): explicit :func:`set_hbm_ceiling` /
``PADDLE_HBM_GBS`` env / datasheet-by-device-kind; ``PADDLE_PEAK_FLOPS``
env / bf16 datasheet.
"""

from __future__ import annotations

import os
import threading
from time import perf_counter  # noqa: F401  (recording sites' clock)

# bf16 datasheet peaks per chip generation (BENCH convention: the v5e int8
# TOPS line is NOT the bf16 peak).  Override with PADDLE_PEAK_FLOPS
# (FLOP/s) — required on the CPU test mesh.  TrainStep's MFU gauge reads
# the same table via peak_flops().
PEAK_BF16_FLOPS = {"v6": 918e12, "v5p": 459e12, "v5 lite": 197e12,
                   "v5e": 197e12, "v4": 275e12, "v3": 123e12, "v2": 45e12}

# HBM bandwidth datasheet lines (bytes/s) by chip generation;
# PADDLE_HBM_GBS / set_hbm_ceiling() records a measured ceiling instead.
HBM_GBS = {"v6": 1640e9, "v5p": 2765e9, "v5 lite": 819e9, "v5e": 819e9,
           "v4": 1228e9, "v3": 900e9, "v2": 700e9}

_hbm_override = None  # set_hbm_ceiling() value (bytes/s)


def _device_kind():
    import jax

    try:
        return jax.devices()[0].device_kind.lower()
    except Exception:
        return None


def peak_flops():
    """Device peak FLOP/s: PADDLE_PEAK_FLOPS override, else the bf16
    datasheet number for the visible chip kind, else None (CPU mesh)."""
    env = os.environ.get("PADDLE_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            return None  # malformed override must not kill the caller
    kind = _device_kind()
    if kind:
        for k, v in PEAK_BF16_FLOPS.items():
            if k in kind:
                return v
    return None


def hbm_ceiling():
    """HBM ceiling in bytes/s: set_hbm_ceiling() > PADDLE_HBM_GBS env >
    datasheet by device kind > None."""
    if _hbm_override is not None:
        return _hbm_override
    env = os.environ.get("PADDLE_HBM_GBS")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            return None
    kind = _device_kind()
    if kind:
        for k, v in HBM_GBS.items():
            if k in kind:
                return v
    return None


def set_hbm_ceiling(gbs):
    """Record a MEASURED HBM ceiling (GB/s) — e.g. the bench roofline
    section's number — overriding env/datasheet.  ``None`` clears it."""
    global _hbm_override
    _hbm_override = None if gbs is None else float(gbs) * 1e9


def classify(flops_per_call, bytes_per_call, peak=None, hbm=None):
    """Roofline regime of a program: its arithmetic intensity (FLOP/byte)
    against the machine ridge point ``peak_flops / hbm_bytes_per_s``.
    Below the ridge the program cannot reach peak FLOP/s no matter how
    good the kernels are — HBM feeds it too slowly (bandwidth-bound);
    above it, compute is the wall."""
    peak = peak if peak is not None else peak_flops()
    hbm = hbm if hbm is not None else hbm_ceiling()
    if not flops_per_call or not bytes_per_call or not peak or not hbm:
        return "unknown"
    ridge = peak / hbm
    intensity = flops_per_call / bytes_per_call
    return "bandwidth-bound" if intensity < ridge else "compute-bound"


#: serving-engine program families whose bytes are dominated by the paged
#: KV cache — the ones int8 pools (kv_dtype="int8") directly shrink
_KV_BOUND_FAMILIES = ("decode", "prefill/", "prefill_chunk/", "verify/")


def is_quantized_family(family):
    """True for the quantized serving program families — the engine
    attributes its int8-pool programs as ``decode@int8``,
    ``prefill/<bucket>@int8``, ``verify/k<k>@int8``."""
    return "@int8" in family


def is_lora_family(family):
    """True for the multi-tenant LoRA program families — the engine
    attributes them as ``decode@lora-r<r>``, ``prefill/<bucket>@lora-r<r>``
    (rank-bucket suffix; adapter count never appears)."""
    return "@lora-r" in family


def is_encode_family(family):
    """True for the embed/score passthrough families
    (``prefill/<bucket>@embed`` / ``@score``)."""
    return "@embed" in family or "@score" in family


def is_mp_family(family):
    """True for the tensor-parallel serving families — a mesh-sharded
    engine attributes its programs as ``decode@mp<N>``,
    ``prefill/<bucket>@mp<N>``, ``verify/k<k>@mp<N>`` (the suffix composes
    after ``@int8``: one SPMD program per family, dispatched
    over the ``model`` axis)."""
    return "@mp" in family


def mp_degree(family):
    """Model-parallel degree parsed from the ``@mp<N>`` suffix (1 when
    the family is unsharded)."""
    for part in family.split("@"):
        if part.startswith("mp") and part[2:].isdigit():
            return int(part[2:])
    return 1


def is_cached_prefill_family(family):
    """True for the prefix-cached prefill/encode families — the engine
    attributes a dispatch that reused ``p`` resident radix pages as
    ``prefill/<bucket>@cached<p>`` (``prefill/<bucket>@embed@cached<p>``
    for passthrough encodes): the family rides the chunked-prefill
    program shape but starts at the cached token offset, so its
    device-time per prompt token is already the minimum the cache can
    buy."""
    return "@cached" in family


def is_chunked_prefill_family(family):
    """True for the chunked-prefill ingestion families — the engine
    attributes them as ``prefill_chunk/<chunk_tokens>`` (plus the usual
    ``@int8`` / ``@lora-r<r>`` suffixes).  NOT a ``prefill/`` family:
    scratch is already O(chunk), so the 'chunk the prefill' capacity hint
    must never fire for these."""
    return family.split("@")[0].startswith("prefill_chunk/")


def _multi_chip_host():
    """More than one accelerator visible — an unsharded serving family
    here is leaving mesh capacity on the table, which flips the
    bandwidth-bound hint toward ``ServingEngine(mesh=...)``."""
    try:
        import jax

        return jax.device_count() > 1
    except Exception:
        return False


def candidate_hint(family, regime, temp_bytes=None, pool_bytes=None,
                   prefix_stats=None):
    """The regime-driven recommendation :meth:`ProgramTable.report` prints
    for a top device-time program.  Recognizes the quantized serving
    families: a bandwidth-bound UNQUANTIZED serving program's first lever
    is int8 KV pools (dequant fuses into the paged kernel — the
    serving.quant subsystem); an ``@int8`` family has already pulled it,
    so the hint points at the remaining byte traffic instead.  Also the
    multi-tenant families: ``@lora-r<r>`` programs carry the per-row
    paged adapter gather, ``@embed``/``@score`` are prefill-shaped
    one-shot encodes.

    Memory attribution (``temp_bytes`` from the family's
    ``memory_analysis``, ``pool_bytes`` = the ledger's KV pool total):
    a prefill family whose peak scratch dwarfs the whole paged cache is
    capacity-bound before it is time-bound — the hint becomes 'chunk the
    prefill', whatever the roofline regime says.

    Prefix-cache attribution (``prefix_stats`` = the registry's
    ``serving.prefix_cache_*`` / ``serving.kv_spill_*`` totals): a plain
    prefill family dominating device time while sharable pages mostly
    MISS means the workload recomputes prefixes the radix index would
    have kept resident — skipping the compute beats any bytes/flops
    lever, so that hint wins; a spill tier resurrecting pages about as
    fast as the cache hits is thrashing host<->device and wants a bigger
    ``PADDLE_KV_SPILL_BUDGET_BYTES``."""
    quant = is_quantized_family(family)
    mp = is_mp_family(family)
    serving = family.split("@")[0].startswith(_KV_BOUND_FAMILIES)
    if temp_bytes and pool_bytes \
            and is_chunked_prefill_family(family) \
            and temp_bytes > pool_bytes:
        return ("chunked prefill already active, yet peak temp bytes "
                f"({temp_bytes / 1e6:.1f} MB) still dwarf the paged KV "
                f"pools ({pool_bytes / 1e6:.1f} MB): lower "
                "prefill_chunk_tokens so per-chunk scratch shrinks "
                "further")
    if temp_bytes and pool_bytes \
            and family.split("@")[0].startswith("prefill/") \
            and temp_bytes > pool_bytes:
        return (f"prefill peak temp bytes ({temp_bytes / 1e6:.1f} MB) dwarf "
                f"the paged KV pools ({pool_bytes / 1e6:.1f} MB): chunk the "
                "prefill — ServingEngine(prefill_chunk_tokens=N) runs the "
                "prompt through the chunked cache variant in N-token "
                "slices so scratch stays O(chunk), and long prompts stop "
                "spiking HBM at admission")
    if prefix_stats:
        hits = int(prefix_stats.get("hits") or 0)
        misses = int(prefix_stats.get("misses") or 0)
        res = int(prefix_stats.get("resurrections") or 0)
        prefill_like = family.split("@")[0].startswith(
            ("prefill/", "prefill_chunk/"))
        if prefill_like and not is_cached_prefill_family(family) \
                and misses >= 8 and misses > 4 * max(hits, 1):
            return ("prefill dominates while sharable prefix pages miss "
                    f"{misses}:{hits} against the cache: enable the radix "
                    "prefix index (ServingEngine(prefix_cache=\"radix\")) "
                    "— partial-prefix matches reuse the longest shared "
                    "page run and prefill starts past the cached tokens, "
                    "skipping that compute entirely")
        if res >= 8 and res * 2 >= max(hits, 1):
            return ("KV spill tier is thrashing: "
                    f"{res} resurrections against {hits} cache hits "
                    "means hot prefix pages keep falling to host and "
                    "re-paging back — raise PADDLE_KV_SPILL_BUDGET_BYTES "
                    "(or shrink the working set) so resident prefixes "
                    "stay on-device")
    if regime == "bandwidth-bound":
        if is_lora_family(family):
            if quant:
                return ("HBM-bound int8 multi-LoRA program: KV dequant "
                        "fused; the remaining levers are the adapter "
                        "pools — fewer/lower rank buckets, fewer LoRA "
                        "targets, or bf16 adapter pools")
            return ("HBM-bound multi-LoRA serving program: the per-row "
                    "adapter gather rides the decode bytes — shrink rank "
                    "buckets / targets, then quantize the KV pools "
                    "(kv_dtype=\"int8\")")
        if is_encode_family(family):
            return ("HBM-bound embed/score encode: prefill-shaped one-shot "
                    "— batch more rows per dispatch or share prefix "
                    "compute with generate admissions")
        if mp and serving:
            n = mp_degree(family)
            if quant:
                return (f"HBM-bound mp{n} int8 serving program: KV pools "
                        "sharded over the model axis AND dequant fused — "
                        "per-shard bytes are the floor; remaining levers "
                        "are int8 weights (weight_dtype=\"int8\") and "
                        "batch occupancy")
            return (f"HBM-bound mp{n} serving program: already sharded "
                    "over the model axis, so each chip sweeps 1/"
                    f"{n} of the KV heads — cut the per-shard bytes next "
                    "with int8 pools (kv_dtype=\"int8\")")
        if quant:
            return ("HBM-bound int8 serving program: KV dequant already "
                    "fused in-kernel — cut the remaining bytes (int8 "
                    "weights via weight_dtype, larger pages, more slots "
                    "per dispatch)")
        if serving and _multi_chip_host():
            return ("HBM-bound serving program with UNSHARDED pools on a "
                    "multi-chip host: shard the KV pools and weights over "
                    "the mesh (ServingEngine(mesh=...)) — each chip then "
                    "sweeps only its KV-head slice, ~1/mp the bytes/call "
                    "— then int8 pools (kv_dtype=\"int8\")")
        if serving:
            return ("HBM-bound serving program: quantize the KV pools "
                    "(kv_dtype=\"int8\" — dequant fuses into the paged "
                    "kernel, ~2x fewer cache bytes/call), fuse producers, "
                    "raise arithmetic intensity")
        return ("HBM-bound: cut bytes/call — fuse producers into the "
                "kernel, quantize operands, raise arithmetic intensity")
    if regime == "compute-bound":
        return ("compute-bound: raise matmul utilization — tile for the "
                "MXU, overlap with transfers")
    if quant:
        return ("regime unknown (resolve cost_analysis first); int8 "
                "serving program — KV dequant already fused in-kernel")
    return "regime unknown: resolve cost_analysis first"


class _ProgStats:
    __slots__ = ("family", "calls", "device_seconds", "flops_per_call",
                 "bytes_per_call", "memory_per_call", "cost_thunk",
                 "cost_error")

    def __init__(self, family):
        self.family = family
        self.calls = 0
        self.device_seconds = 0.0
        self.flops_per_call = None
        self.bytes_per_call = None
        self.memory_per_call = None  # XLA memory_analysis dict (or None)
        self.cost_thunk = None   # lazy () -> (flops, bytes[, memory])
        self.cost_error = None   # last thunk failure (kept, not retried)


class ProgramTable:
    """The live per-program attribution table (one per process by
    default — :func:`table`).  ``record`` is the hot-path entry: one dict
    lookup, two float adds under a per-table lock, two counter bumps."""

    def __init__(self, registry=None):
        from ..profiler import metrics as _metrics

        reg = registry if registry is not None else _metrics.get_registry()
        self._stats: dict[str, _ProgStats] = {}
        self._lock = threading.Lock()
        self._resolver = None
        self._m_calls = reg.counter(
            "perf.program.calls", "compiled-program dispatches, by family")
        self._m_seconds = reg.counter(
            "perf.program.device_seconds",
            "device seconds attributed to the family (dispatch-to-sync)")
        self._m_tflops = reg.gauge(
            "perf.program.achieved_tflops",
            "cost_analysis flops * calls / device seconds")
        self._m_gbs = reg.gauge(
            "perf.program.achieved_gbs",
            "cost_analysis bytes * calls / device seconds")
        self._m_frac = reg.gauge(
            "perf.program.frac_of_peak",
            "achieved rate over the BINDING peak (HBM when "
            "bandwidth-bound, FLOP/s when compute-bound)")
        # per-program memory attribution (memory_analysis, resolved off
        # the dispatch path exactly like the cost thunks)
        self._m_peak_bytes = reg.gauge(
            "perf.program.peak_bytes",
            "XLA memory_analysis peak bytes per call (argument + output "
            "+ temp - aliased)")
        self._m_temp_bytes = reg.gauge(
            "perf.program.temp_bytes",
            "XLA memory_analysis temp (scratch) bytes per call")

    # -------------------------------------------------------------- recording
    def _get(self, family):
        st = self._stats.get(family)
        if st is None:
            with self._lock:
                st = self._stats.setdefault(family, _ProgStats(family))
        return st

    def record(self, family, seconds, calls=1):
        """Attribute ``seconds`` of device time (``calls`` dispatches) to
        a program family.  Recording sites skip compile dispatches — a
        trace+compile wall is not device time."""
        st = self._get(family)
        with self._lock:
            st.calls += calls
            st.device_seconds += seconds
        self._m_calls.inc(calls, program=family)
        self._m_seconds.inc(seconds, program=family)

    def needs_cost(self, family):
        """True while the family has neither cost numbers nor a pending
        thunk — dispatch sites use this to capture arg shapes only once."""
        st = self._stats.get(family)
        return st is None or (st.flops_per_call is None
                              and st.cost_thunk is None
                              and st.cost_error is None)

    def set_cost(self, family, flops_per_call, bytes_per_call, memory=None):
        st = self._get(family)
        with self._lock:
            st.flops_per_call = float(flops_per_call)
            st.bytes_per_call = float(bytes_per_call)
            if memory is not None:
                st.memory_per_call = dict(memory)
            st.cost_thunk = None

    def register_cost_thunk(self, family, thunk):
        """Attach a lazy ``() -> (flops, bytes_accessed)`` (usually an XLA
        re-lower+compile+cost_analysis — seconds of work, so it never runs
        here; see :meth:`resolve_costs`)."""
        st = self._get(family)
        with self._lock:
            if st.flops_per_call is None and st.cost_thunk is None:
                st.cost_thunk = thunk

    def resolve_costs(self):
        """Run every pending cost thunk SYNCHRONOUSLY (tests, report,
        bench).  A failing thunk records its error and is not retried."""
        for st in list(self._stats.values()):
            with self._lock:
                thunk = st.cost_thunk
            if thunk is None:
                continue
            try:
                res = thunk()
                # jit_cost_thunk returns (flops, bytes, memory_analysis);
                # external 2-tuple thunks stay valid
                mem = res[2] if len(res) > 2 else None
                self.set_cost(st.family, res[0], res[1], memory=mem)
            except Exception as e:  # cost analysis is best-effort
                with self._lock:
                    st.cost_error = repr(e)
                    st.cost_thunk = None

    def _resolve_costs_async(self):
        """Kick cost resolution on a daemon thread (telemetry scrapes must
        stay bounded — a scrape never compiles)."""
        with self._lock:
            if self._resolver is not None and self._resolver.is_alive():
                return
            if not any(st.cost_thunk is not None
                       for st in self._stats.values()):
                return
            self._resolver = threading.Thread(
                target=self.resolve_costs, name="paddle-perf-cost-resolver",
                daemon=True)
            self._resolver.start()

    # -------------------------------------------------------------- reading
    def snapshot(self, resolve=False):
        """Table rows sorted by total device time (descending), derived
        rates and roofline regime included; refreshes the ``perf.program``
        gauges.  ``resolve=True`` first runs pending cost thunks (slow —
        never from a scrape; the /statusz provider instead kicks the
        background resolver and shows what is already known)."""
        if resolve:
            self.resolve_costs()
        peak, hbm = peak_flops(), hbm_ceiling()
        rows = []
        with self._lock:
            stats = [(st.family, st.calls, st.device_seconds,
                      st.flops_per_call, st.bytes_per_call, st.cost_error,
                      st.cost_thunk is not None, st.memory_per_call)
                     for st in self._stats.values()]
        for family, calls, secs, flops, nbytes, err, pending, mem in stats:
            row = {"program": family, "calls": calls,
                   "device_seconds": secs,
                   "flops_per_call": flops, "bytes_per_call": nbytes,
                   "achieved_tflops": None, "achieved_gbs": None,
                   "intensity_flop_per_byte": None,
                   "regime": "unknown", "frac_of_peak": None,
                   "argument_bytes": None, "output_bytes": None,
                   "temp_bytes": None, "peak_bytes": None}
            if mem:
                for k in ("argument_bytes", "output_bytes", "temp_bytes",
                          "peak_bytes"):
                    row[k] = mem.get(k)
                if row["peak_bytes"] is not None:
                    self._m_peak_bytes.set(row["peak_bytes"], program=family)
                if row["temp_bytes"] is not None:
                    self._m_temp_bytes.set(row["temp_bytes"], program=family)
            if pending:
                row["cost"] = "pending"
            elif err is not None:
                row["cost"] = f"error: {err}"
            if flops and nbytes and secs > 0 and calls:
                fps = flops * calls / secs
                bps = nbytes * calls / secs
                row["achieved_tflops"] = fps / 1e12
                row["achieved_gbs"] = bps / 1e9
                row["intensity_flop_per_byte"] = flops / nbytes
                row["regime"] = classify(flops, nbytes, peak, hbm)
                if row["regime"] == "bandwidth-bound" and hbm:
                    row["frac_of_peak"] = bps / hbm
                elif row["regime"] == "compute-bound" and peak:
                    row["frac_of_peak"] = fps / peak
                self._m_tflops.set(row["achieved_tflops"], program=family)
                self._m_gbs.set(row["achieved_gbs"], program=family)
                if row["frac_of_peak"] is not None:
                    self._m_frac.set(row["frac_of_peak"], program=family)
            rows.append(row)
        rows.sort(key=lambda r: -r["device_seconds"])
        return rows

    def statusz(self):
        """/statusz ``perf_programs`` provider: the table plus the
        ceilings it was judged against.  A scrape NEVER compiles: with
        ``PADDLE_PERF_COST=1`` pending costs resolve on a background
        thread kicked here; otherwise they stay "pending" until someone
        calls :func:`resolve_costs` / ``report()`` explicitly (a hidden
        background XLA compile per scrape is real CPU stolen from the
        serving process — opt in deliberately)."""
        if os.environ.get("PADDLE_PERF_COST", "").lower() \
                not in ("", "0", "false", "no"):
            self._resolve_costs_async()
        peak, hbm = peak_flops(), hbm_ceiling()
        return {
            "peak_tflops": peak / 1e12 if peak else None,
            "hbm_gbs": hbm / 1e9 if hbm else None,
            "ridge_flop_per_byte": (peak / hbm) if peak and hbm else None,
            "programs": self.snapshot(resolve=False),
        }

    def report(self, top=3, resolve=True):
        """Profiler.summary()-style text table + the top fusion/kernel
        candidates (largest device-time programs, with the roofline-driven
        recommendation: cut bytes when bandwidth-bound, cut/overlap flops
        when compute-bound)."""
        rows = self.snapshot(resolve=resolve)
        head = (f"{'program':<24}{'calls':>8}{'dev s':>10}{'TFLOP/s':>10}"
                f"{'GB/s':>9}{'I(F/B)':>9}{'of peak':>9}{'peak MB':>9}"
                "  regime")
        lines = ["Per-program roofline attribution", head, "-" * len(head)]

        def fmt(v, nd=2):
            return f"{v:.{nd}f}" if v is not None else "-"

        for r in rows:
            peak_mb = r["peak_bytes"] / 1e6 \
                if r.get("peak_bytes") is not None else None
            lines.append(
                f"{r['program']:<24}{r['calls']:>8}"
                f"{r['device_seconds']:>10.3f}"
                f"{fmt(r['achieved_tflops']):>10}{fmt(r['achieved_gbs'], 1):>9}"
                f"{fmt(r['intensity_flop_per_byte'], 1):>9}"
                f"{fmt(r['frac_of_peak'], 3):>9}{fmt(peak_mb, 1):>9}"
                f"  {r['regime']}")
        cands = [r for r in rows if r["device_seconds"] > 0][:top]
        if cands:
            # the memory ledger's KV pool total is the denominator for the
            # chunk-the-prefill hint (best-effort: no ledger, no hint)
            try:
                from . import memory as _memory

                pool_bytes = _memory.ledger().kv_pool_bytes()
            except Exception:
                pool_bytes = None
            # prefix-cache workload evidence for the radix/spill hints
            # (best-effort: zero everywhere -> no evidence -> no hint)
            try:
                from ..profiler import metrics as _pm

                prefix_stats = {
                    "hits": _pm.counter(
                        "serving.prefix_cache_hits").total() or 0,
                    "misses": _pm.counter(
                        "serving.prefix_cache_misses").total() or 0,
                    "resurrections": _pm.counter(
                        "serving.kv_spill_resurrections").total() or 0,
                }
                if not any(prefix_stats.values()):
                    prefix_stats = None
            except Exception:
                prefix_stats = None
            lines.append("")
            lines.append("Top kernel/fusion candidates (by device time):")
            for i, r in enumerate(cands, 1):
                hint = candidate_hint(r["program"], r["regime"],
                                      temp_bytes=r.get("temp_bytes"),
                                      pool_bytes=pool_bytes,
                                      prefix_stats=prefix_stats)
                lines.append(f"  {i}. {r['program']} "
                             f"({r['device_seconds']:.3f}s over "
                             f"{r['calls']} calls) — {hint}")
        return "\n".join(lines)

    def drop_prefix(self, prefix):
        """Evict every family under ``prefix`` (``prefix`` itself or
        ``prefix.*``/``prefix/*``).  TrainStep registers this as a
        weakref finalizer on its per-instance tag, so a process that
        constructs TrainSteps in a loop does not grow the table without
        bound (already-rendered ``perf.program.*`` registry series stay,
        like any labelled metric's)."""
        with self._lock:
            for fam in [f for f in self._stats
                        if f == prefix or f.startswith(prefix + ".")
                        or f.startswith(prefix + "/")]:
                del self._stats[fam]

    def reset(self):
        with self._lock:
            self._stats.clear()


# ------------------------------------------------------- process-wide table
_TABLE = None
_TABLE_LOCK = threading.Lock()
_PROVIDER_REGISTERED = False


def table() -> ProgramTable:
    global _TABLE
    if _TABLE is None:
        with _TABLE_LOCK:
            if _TABLE is None:
                _TABLE = ProgramTable()
    return _TABLE


def _ensure_provider():
    """Register the /statusz ``perf_programs`` section once, lazily on
    first record — a process that never dispatches never grows the key."""
    global _PROVIDER_REGISTERED
    if _PROVIDER_REGISTERED:
        return
    with _TABLE_LOCK:
        if _PROVIDER_REGISTERED:
            return
        from . import telemetry as _telemetry

        _telemetry.add_status_provider("perf_programs",
                                       lambda: table().statusz())
        _PROVIDER_REGISTERED = True


def record(family, seconds, calls=1):
    """Module-level spelling of :meth:`ProgramTable.record` on the process
    table (the one dispatch sites use)."""
    _ensure_provider()
    table().record(family, seconds, calls)


def needs_cost(family):
    return table().needs_cost(family)


def register_cost_thunk(family, thunk):
    table().register_cost_thunk(family, thunk)


def snapshot(resolve=False):
    return table().snapshot(resolve=resolve)


def resolve_costs():
    table().resolve_costs()


def report(top=3, resolve=True):
    return table().report(top=top, resolve=resolve)


def reset():
    """Tests: drop accumulated attribution (the table object and its
    registered provider survive)."""
    if _TABLE is not None:
        _TABLE.reset()


def metric_quantile(name, q, **labels):
    """Reservoir quantile of one registry histogram child, or None when
    the series is absent or empty.  The read half of the latency-SLO
    story (bench arms and the QoS report use it for per-tier TTFT/ITL
    p95s): serving series carry ``replica=`` labels — and on QoS engines
    ``tier=`` — so the child is addressed by exact label match."""
    from ..profiler import metrics as _metrics

    h = _metrics.get_registry().get(name)
    c = h.labels(**labels) if h is not None else None
    return (c.quantile(q) if c is not None and c.count else None)


# ------------------------------------------------- cost-thunk construction
def _shape_struct(v):
    import jax

    shape = getattr(v, "shape", None)
    dtype = getattr(v, "dtype", None)
    if shape is None or dtype is None:
        return v
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _memory_analysis_dict(comp):
    """One compiled program's ``memory_analysis()`` as a plain dict
    (argument/output/temp/alias/generated-code bytes + a derived peak:
    XLA's CompiledMemoryStats has no explicit peak field on every
    backend, but arguments + outputs + temp − aliased is the live set a
    dispatch holds at once).  Best-effort: ``None`` when the backend
    doesn't expose it."""
    try:
        ma = comp.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None

    def g(name):
        try:
            return float(getattr(ma, name))
        except Exception:
            return 0.0

    arg = g("argument_size_in_bytes")
    out = g("output_size_in_bytes")
    temp = g("temp_size_in_bytes")
    alias = g("alias_size_in_bytes")
    peak = getattr(ma, "peak_memory_in_bytes", None)
    if peak is None:
        peak = max(0.0, arg + out + temp - alias)
    return {"argument_bytes": arg, "output_bytes": out, "temp_bytes": temp,
            "alias_bytes": alias,
            "generated_code_bytes": g("generated_code_size_in_bytes"),
            "peak_bytes": float(peak)}


def jit_cost_thunk(jitted, args):
    """Build a lazy cost thunk for a ``jax.jit``-ed callable from the
    concrete args of one dispatch: shapes/dtypes are captured NOW (cheap;
    donated buffers keep their metadata), the re-lower+compile+
    cost_analysis+memory_analysis runs only when the table resolves
    costs.

    The program is held by WEAKREF: the process-wide table outlives any
    one engine/model, and a pending thunk must not pin a dead model's
    params (the jitted closure reaches them) until someone happens to
    resolve costs."""
    import weakref

    import jax

    shapes = jax.tree_util.tree_map(_shape_struct, args)
    ref = weakref.ref(jitted)

    def thunk():
        fn = ref()
        if fn is None:
            raise RuntimeError(
                "compiled program was garbage-collected before its "
                "cost_analysis resolved")
        comp = fn.lower(*shapes).compile()
        ca = comp.cost_analysis()
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)),
                _memory_analysis_dict(comp))

    return thunk


def jit_analysis_thunk(jitted, args):
    """:func:`jit_cost_thunk` for the program ledger: flops /
    bytes-accessed / executable size / memory analysis — one dict per
    program, resolved lazily (never on a scrape).  It lowers and compiles
    the program a second time, so it reports no seconds: the build that
    really happened is timed by JAX's own events, on the ledger's row
    (``observability/programs.py`` ``BuildRecord``).  Same weakref
    discipline as :func:`jit_cost_thunk`: a pending thunk must not pin a
    dead model."""
    import weakref

    import jax

    shapes = jax.tree_util.tree_map(_shape_struct, args)
    ref = weakref.ref(jitted)

    def thunk():
        fn = ref()
        if fn is None:
            raise RuntimeError(
                "compiled program was garbage-collected before its "
                "analysis resolved")
        comp = fn.lower(*shapes).compile()
        ca = comp.cost_analysis()
        mem = _memory_analysis_dict(comp)
        return {"flops": float(ca.get("flops", 0.0)),
                "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
                "executable_bytes": (mem or {}).get("generated_code_bytes"),
                "memory": mem}

    return thunk
