"""Entry driver ``serve``: ``serving.ServingEngine`` under a traffic mix.

One thread of the benchmark sends (``engine.submit``) and watches the
handles; the engine's own thread serves.  Set-up builds the engine from
seeded weights, sends one request per compiled shape the mix uses (they
compile), starts the mix and lets it ramp; then the window opens.  At its
close a traced run goes on for a few seconds under the profiler; then no
more is sent and what is in flight drains.  Once the engine is stopped and
freed, the plain reference runs over a sample of the served requests.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from .. import compare, program_counters, spans, stats


class _Driver:
    def __init__(self, engine, source, poll_s):
        self.engine, self.source, self.poll_s = engine, source, poll_s
        self.live = {}
        self.records = []       # (stats.Request, prompt, handle ids)

    def _collect(self):
        for client, (h, due, prompt, max_new) in list(self.live.items()):
            if not h.done:
                continue
            del self.live[client]
            ok = h.status == "completed" and len(h.token_ids) == max_new
            req = stats.Request(due, h.submitted_at, h.admitted_at,
                                h.token_times, h.finished_at, ok,
                                prompt_len=len(prompt), n_out=max_new)
            self.records.append((req, prompt, list(h.token_ids)))
            self.source.done(client, time.time())

    def pump(self, until, drain_s=60.0):
        """Send what is due and collect what finished, until ``until``; with
        ``until=None``, until nothing is in flight, waiting ``drain_s`` at
        the most: an answer that comes late is late, one that never comes is
        for ``failed``."""
        give_up = time.time() + drain_s
        while True:
            now = time.time()
            if now >= (give_up if until is None else until):
                return
            for due, client, prompt, max_new in self.source.due(now):
                with spans.span("bench.submit"):
                    h = self.engine.submit(prompt, max_new_tokens=max_new)
                self.live[client] = (h, due, prompt, max_new)
            self._collect()
            if until is None and not self.live:
                return
            with spans.span("bench.idle_generator"):
                time.sleep(self.poll_s)


def _engine(wl, model):
    from paddle_tpu.serving import ServingEngine

    e = wl["engine"]
    return ServingEngine(
        model, num_slots=int(e["num_slots"]), page_size=int(e["page_size"]),
        max_model_len=int(e["max_model_len"]), num_pages=e.get("num_pages"),
        kv_dtype=e.get("kv_dtype"),
        prefill_chunk_tokens=e.get("prefill_chunk_tokens"),
        numeric_guard=bool(e.get("numeric_guard", False)),
        replica="chipbench")


def _warm(engine, prompt_lengths, page_size, chunk_tokens, vocab,
          new_tokens=4):
    """Set-up's compilations.  The benchmark copies no bucketing rule of the
    program: it sends one request for every number of pages that a prompt
    of the mix can fill (the shortest such prompt), so that whatever
    programs the engine keys on a prompt's padded length exist before the
    window.  Where the cell has the engine ingest prompts longer than
    ``chunk_tokens`` in chunks of that many (its own parameter, handed to
    the engine), those all run one program: the mix's longest prompt stands
    for them.  The decode step compiles with the first of them."""
    by_pages = {}
    for n in prompt_lengths:
        if chunk_tokens and n > chunk_tokens:
            n = max(prompt_lengths)
        by_pages.setdefault(-(-n // page_size), n)
    rng = np.random.default_rng(0)
    handles = [engine.submit(rng.integers(1, vocab, n, dtype=np.int64),
                             max_new_tokens=new_tokens)
               for n in sorted(by_pages.values())]
    for h in handles:
        h.result(timeout=1200)
    return len(handles)


def _sample(records, t_open, t_close, seed, n):
    """The served requests the reference runs over: drawn from the seed
    among those of the window that finished, the longest always in it."""
    done = [r for r in records
            if r[0].ok and t_open <= r[0].due < t_close]
    if not done:
        return []
    longest = max(range(len(done)),
                  key=lambda i: len(done[i][1]) + len(done[i][2]))
    rng = np.random.default_rng(int(seed) % (1 << 62))
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:max(n - 1, 0)]]


def _decode_contexts(requests, t0, t1):
    """Cached length attended by every token DECODED in ``[t0, t1)``: token
    j >= 1 of a request sees its prompt and the j tokens before it (token 0
    comes out of the prefill)."""
    return [r.prompt_len + j for r in requests
            for j, t in enumerate(r.token_times) if j and t0 <= t < t1]


def _flops(costs, cfg, requests, t0, t1):
    """Operations the window's tokens require: a prompt counts where its
    first token falls, a decoded token where it is stamped."""
    total = 0
    for r in requests:
        if r.token_times and t0 <= r.token_times[0] < t1:
            total += costs.prefill_flops(cfg, r.prompt_len)
    return total + sum(costs.decode_flops(cfg, c)
                       for c in _decode_contexts(requests, t0, t1))


def run(ctx):
    import jax.numpy as jnp

    import paddle_tpu as paddle

    cfg, wl, seed = ctx.config, ctx.workload, ctx.seed
    family = ctx.module("models", cfg["family"])
    ref = ctx.module("reference", cfg["family"])
    paddle.seed(seed % (1 << 31))

    dtype = wl["dtype"]
    params = ref.init_params(seed, cfg, dtype=jnp.dtype(dtype),
                             shape=wl.get("weights"))
    model = family.build(cfg, params, ref, dtype=dtype).eval()
    del params
    engine = _engine(wl, model)
    source = ctx.module("traffic", wl["kind"]).Source(
        wl, seed, cfg["vocab_size"])
    driver = _Driver(engine, source, float(wl.get("poll_s", 0.001)))
    engine.start()
    try:
        # ---- every shape the mix can use, before the window ------------
        ctx.host["warm_requests"] = _warm(
            engine, source.prompt_lengths(), int(wl["engine"]["page_size"]),
            wl["engine"].get("prefill_chunk_tokens"), int(cfg["vocab_size"]))
        # ---- ramp: the mix starts inside set-up --------------------------
        source.start(time.time())
        driver.pump(time.time() + float(wl["ramp_seconds"]))
        programs0 = engine.program_traces()
        compiles0 = ctx.compiles.n
        counters0 = program_counters.snapshot()

        # ---- the window --------------------------------------------------
        t_open = ctx.open_window()
        driver.pump(t_open + ctx.seconds)
        t_close = time.time()
        counters = program_counters.delta(counters0,
                                          program_counters.snapshot())
        new_programs = int(engine.program_traces() != programs0)
        compiles = ctx.compiles.n - compiles0
        if ctx.start_trace():
            ctx.host["trace_t0"] = time.time()
            with spans.span("bench.window"):
                driver.pump(time.time() + ctx.trace_seconds)
            ctx.host["trace_t1"] = time.time()
            ctx.stop_trace()
        # ---- no more is sent; what is in flight drains -------------------
        source.stop()
        driver.pump(None)
        ctx.read_memory_peak()
    finally:
        engine.stop()
    records = driver.records
    requests = [r[0] for r in records] + [
        stats.Request(due, h.submitted_at, h.admitted_at, h.token_times,
                      None, False, len(prompt), max_new)
        for h, due, prompt, max_new in driver.live.values()]
    e2e, window = stats.serve_metrics(requests, t_open, t_close)
    in_window = [r for r in requests if t_open <= r.due < t_close]
    failed = sum(1 for r in in_window if not r.ok)
    costs = ctx.module("costs", cfg["family"])
    ctx.host.update(
        flops_in_window=_flops(costs, cfg, requests, t_open, t_close),
        t_close=t_close, window_s=t_close - t_open, counters=counters,
        queue_ms=window["queue_ms"], tokens_in_window=window["tokens"],
        requests_in_window=len(in_window), traffic=source.describe(),
        compiles_in_window=compiles, new_programs_in_window=new_programs,
        prompt_tokens_in_window=sum(r.prompt_len for r in in_window),
        serve_tok_s=e2e.get("serve_tok_s"))
    if "trace_t0" in ctx.host:
        ctx.host["traced_decode_contexts"] = _decode_contexts(
            requests, ctx.host["trace_t0"], ctx.host["trace_t1"])

    # ---- free the program, then the reference ----------------------------
    sample = _sample(records, t_open, t_close, seed,
                     int(wl["compared_requests"]))
    del engine, model, driver
    gc.collect()
    served = [(prompt, np.asarray(out, np.int64)) for _, prompt, out in sample]
    numbers = compare.serve_numbers(ref, cfg, seed, dtype, served,
                                    shape=wl.get("weights"))
    ctx.kept.update(served=served, numbers=numbers)
    ctx.host["compared"] = numbers
    checks = [
        compare.exact("compiles_in_window", compiles),
        compare.exact("new_programs_in_window", new_programs),
        compare.exact("engine_restarts",
                      int(counters.get("serving.engine_restarts", 0))),
        compare.exact("numeric_faults",
                      int(counters.get("serving.numeric_faults", 0))),
        compare.exact("no_request_compared", int(not served)),
        *compare.judge(numbers, wl["limits"])]
    return {"end_to_end": e2e, "attempted": len(in_window), "failed": failed,
            "checks": checks}


# ---- what `tools/readings.py` puts in the program's place ----------------
def stand_ins(ctx, kept):
    """Numbers of the control and of the fault this cell can have, on the
    requests that the run behind ``kept`` compared: ``{name: numbers}``.

    - ``control``: the reference in the precision below the one the cell
      states; it need not decode: at the positions the program is judged on,
      of the same prompts and tokens, the gap is read of the token the lower
      precision puts first.
    - ``token_altered``: one served token of the longest request replaced,
      as a fault where tokens are produced would."""
    cfg, wl = ctx.config, ctx.workload
    ref = ctx.module("reference", cfg["family"])
    below = compare.sibling(ref, "lower_precision").BELOW[wl["dtype"]]
    served, shape = kept["served"], wl.get("weights")
    prompt, out = served[0]
    wrong = out.copy()
    wrong[len(wrong) // 2] = (wrong[len(wrong) // 2] + 1) % cfg["vocab_size"]
    return {
        "control": compare.serve_numbers(ref, cfg, ctx.seed, wl["dtype"],
                                         served, mm=below, shape=shape),
        "token_altered": compare.serve_numbers(
            ref, cfg, ctx.seed, wl["dtype"], [(prompt, wrong)] + served[1:],
            shape=shape)}
