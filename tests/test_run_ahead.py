"""The decode loop one step ahead of the host (``serving/engine.py``: a turn
enqueues its programs, THEN reads back what the turn before left): the ids,
their order and every status are the synchronous engine's, for a toy GPT,
the toy hybrid and the toy looped decoder; what the host cannot know ahead
(an EOS hit, a cancel, a deadline, a non-finite row, a preemption) is found
one step late and the token computed meanwhile is dropped, touching no page
of anyone else; a crash, ``stop()`` and ``quiescent`` with results in
flight; and the engines whose next inputs are made on the host stay at
depth 0.  Every engine names a ``replica=`` of its own."""

import importlib
import threading

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.observability import faults, numerics
from paddle_tpu.profiler import metrics as prof_metrics
from paddle_tpu.resilience.retry import NumericFault, TransientError
from paddle_tpu.serving import ServingEngine
from paddle_tpu.text.models import GPTForCausalLM
from paddle_tpu.text.models.lfm2 import published_layer_types

PS, MAXLEN, VOCAB = 4, 64, 96

#: the two served families that are not ``.gpt``, at toy widths (the files
#: of tests/test_lfm2.py and tests/test_ouro.py)
HYBRID = {"family": "lfm2", "hidden_size": 32, "intermediate_size": 48,
          "moe_intermediate_size": 8, "num_hidden_layers": 6,
          "layer_types": published_layer_types(6), "num_dense_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
          "routed_scaling_factor": 1, "use_expert_bias": True,
          "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
          "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
          "max_position_embeddings": 128000, "vocab_size": VOCAB,
          "tie_word_embeddings": True, "initializer_range": 0.02,
          "norm_topk_eps": 1e-6, "serve_positions": MAXLEN}
LOOPED = {"family": "ouro", "hidden_size": 32, "intermediate_size": 48,
          "num_hidden_layers": 3, "num_attention_heads": 4,
          "num_key_value_heads": 4, "head_dim": 8, "hidden_act": "silu",
          "max_position_embeddings": 65536, "rms_norm_eps": 1e-6,
          "rope_theta": 1000000, "rope_scaling": None,
          "tie_word_embeddings": False, "total_ut_steps": 4,
          "early_exit_threshold": 1, "vocab_size": VOCAB,
          "initializer_range": 0.02, "serve_positions": MAXLEN}


def _gpt():
    paddle.seed(0)
    return GPTForCausalLM(vocab_size=VOCAB, hidden_size=32,
                          num_hidden_layers=2, num_attention_heads=2,
                          max_position_embeddings=MAXLEN).eval()


def _stated(toy):
    name = toy["family"]
    ref = importlib.import_module(f"chipbench.reference.{name}")
    models = importlib.import_module(f"chipbench.models.{name}")
    return models.build(toy, ref.init_params(2 ** 31 + 7, toy), ref,
                        dtype="float32").eval()


@pytest.fixture(scope="module")
def models():
    built = {}

    def get(name):
        if name not in built:
            built[name] = _gpt() if name == "gpt" else _stated(
                {"hybrid": HYBRID, "looped": LOOPED}[name])
        return built[name]

    return get


def _prompt(n, seed):
    return np.random.RandomState(seed).randint(1, VOCAB, (n,)).tolist()


class _Gate:
    """Holds ``eng``'s scheduler at the top of the given loop iterations
    (1-based) until released: what is submitted meanwhile is one queue."""

    def __init__(self, eng, trips):
        self._held, self._go = threading.Semaphore(0), threading.Semaphore(0)
        faults.inject(f"serving.scheduler_wedge@{eng.replica}",
                      at_trips=set(trips), fn=self._hold)

    def _hold(self):
        self._held.release()
        self._go.acquire(timeout=60)

    def wait(self):
        assert self._held.acquire(timeout=60)

    def release(self):
        self._go.release()


def _queue(eng, prompt, **kw):
    """Queue a request on an engine whose turns the test runs itself."""
    eng._started = True
    try:
        return eng.submit(prompt, _autostart=False, **kw)
    finally:
        eng._started = False


def _count(name, replica, **labels):
    return prof_metrics.counter(name).get(replica=replica, **labels) or 0


# ------------------------------------------- depth 1 against depth 0, whole
#: (prompt tokens, max_new_tokens): seven requests over three lanes, so
#: lanes leave and join at different turns; with chunks of 8, four prompts
#: are ingested in chunks and three whole
MIX = ((21, 6), (5, 3), (33, 9), (9, 1), (16, 12), (40, 4), (3, 7))


@pytest.mark.parametrize("family, temperature, guard, chunk", [
    ("gpt", 0.0, False, 8), ("gpt", 0.8, True, None),
    ("hybrid", 0.8, False, 8), ("hybrid", 0.0, True, None),
    ("looped", 0.0, True, 8), ("looped", 0.8, False, None)],
    ids=lambda v: {0.0: "greedy", 0.8: "temperature", False: "plain",
                   True: "guarded", 8: "chunked",
                   None: "monolithic"}.get(v, v))
def test_ids_and_statuses_equal_the_synchronous_engines(
        models, family, temperature, guard, chunk):
    model = models(family)

    def run(depth):
        replica = f"ahead-{family}-{temperature}-{guard}-{chunk}-{depth}"
        eng = ServingEngine(model, num_slots=3, page_size=PS,
                            max_model_len=MAXLEN, numeric_guard=guard,
                            prefill_chunk_tokens=chunk, replica=replica)
        assert eng._depth == 1
        eng._depth = depth
        # everything queued before the first turn: the schedule is the
        # queue's alone; every other request samples, the rest are greedy
        gate = _Gate(eng, {1})
        try:
            with eng:
                gate.wait()
                handles = [eng.submit(_prompt(n, 50 + j), max_new_tokens=new,
                                      temperature=temperature * (j % 2))
                           for j, (n, new) in enumerate(MIX)]
                gate.release()
                out = [(h.result(timeout=600), h.status) for h in handles]
        finally:
            gate.release()
            faults.clear()
        assert eng.block_manager.used_pages == 0 and not eng._pending
        steps = sum(r["value"] for r in prof_metrics.get_registry().collect()
                    if r["name"] == "serving.decode_batch_size_count"
                    and r["labels"].get("replica") == replica)
        return out, _count("serving.steps_dispatched_ahead", replica), \
            _count("serving.tokens_discarded", replica), steps

    ahead, n_ahead, dropped, steps = run(1)
    sync, n_sync, _, steps0 = run(0)
    assert ahead == sync
    assert [len(ids) for ids, _ in ahead] == [new for _, new in MIX]
    assert {st for _, st in ahead} == {"completed"}
    # the same schedule, to the step; all but the first of a busy stretch
    # were enqueued behind an unread one
    assert steps == steps0 and n_sync == 0 and dropped == 0
    assert n_ahead >= steps - 2 > 0


# ------------------------------------------------ what is found a step late
def _drive(eng, until=lambda: False):
    """The scheduler's turns on this thread, until ``until()`` or until
    nothing is queued, held or in flight."""
    for _ in range(2000):
        if until() or not (eng._queue or eng._pending
                           or any(s is not None for s in eng._slots)):
            return
        eng._turn()
    raise AssertionError("the engine did not settle")


def _rows(eng):
    return [np.asarray(p) for p in eng._pools]


@pytest.mark.parametrize("kind", ["eos", "cancel", "deadline", "nan",
                                  "preempt"])
def test_found_a_step_late_ends_as_the_synchronous_engine(models, kind):
    model = models("gpt")
    a, b, c = _prompt(7, 1), _prompt(5, 2), _prompt(12, 3)
    with ServingEngine(model, num_slots=1, page_size=PS,
                       max_model_len=MAXLEN, replica="late-ref") as ref:
        calm = ref.generate(a, max_new_tokens=20, timeout=600)
    k = next(j for j in range(3, 20) if calm[j] not in calm[:j])

    def run(depth):
        replica = f"late-{kind}-{depth}"
        eng = ServingEngine(
            model, num_slots=2, page_size=PS, max_model_len=MAXLEN,
            numeric_guard=kind == "nan", replica=replica,
            **({"qos": True} if kind == "preempt"
               else {"prefix_cache": "radix"}))
        eng._depth = depth
        tier = {"tier": "batch"} if kind == "preempt" else {}
        # a finished request's prompt pages stay behind (in the radix
        # index, where there is one)
        model.eval()
        hc = _queue(eng, c, max_new_tokens=4, **tier)
        eng._turn()
        kept = list(eng._slots[0].alloc.pages[:len(c) // PS])
        _drive(eng)
        ha = _queue(eng, a, max_new_tokens=20,
                    eos_token_id=calm[k] if kind == "eos" else None, **tier)
        hb = _queue(eng, b, max_new_tokens=20, **tier)
        eng._turn()
        sa, sb = eng._slots
        assert sa.handle is ha and sb.handle is hb
        # the fault rides a DISPATCH, the others follow an emitted token
        _drive(eng, lambda: sa.produced + (sa.unread if kind == "nan"
                                           else 0) >= 3)
        assert bool(eng._pending) == bool(depth)
        mine = set(sa.alloc.pages) | {eng._scratch}
        theirs, b_len = list(sb.alloc.pages), sb.length - sb.unread
        before = _rows(eng)
        hr = None
        if kind == "cancel":
            ha.cancel()
        elif kind == "deadline":
            sa.deadline = 0.0
        elif kind == "nan":
            numerics.set_nan_inject_row(0)
            faults.inject("numerics.nan_inject", times=1)
        elif kind == "preempt":
            hr = _queue(eng, _prompt(6, 4), max_new_tokens=3,
                        tier="realtime")
        try:
            _drive(eng, lambda: ha.done and kind != "preempt")
        finally:
            faults.clear()
        after = _rows(eng)
        for p0, p1 in zip(before, after):
            # the finished request's pages, and every row the bystander
            # had written: as they were
            assert np.array_equal(p0[:, kept], p1[:, kept])
            for pos in range(b_len):
                page, row = theirs[pos // PS], pos % PS
                assert np.array_equal(p0[:, page, row], p1[:, page, row])
            if kind != "preempt":       # nor any page but the two lanes'
                rest = [p for p in range(p0.shape[1])
                        if p not in mine and p not in theirs]
                assert np.array_equal(p0[:, rest], p1[:, rest])
        _drive(eng)
        assert eng.block_manager.used_pages == 0 and not eng._pending
        assert hc.status == "completed" and hb.status == "completed"
        return ([ha.token_ids, hb.token_ids, hr and hr.token_ids],
                ha.status, ha.preemptions,
                _count("serving.tokens_discarded", replica))

    ids1, status1, evicted1, dropped1 = run(1)
    ids0, status0, evicted0, dropped0 = run(0)
    assert ids1 == ids0 and status1 == status0 and evicted1 == evicted0
    assert dropped0 == 0 and dropped1 >= 1
    want = {"eos": ("completed", calm[:k + 1]), "cancel": ("cancelled", None),
            "deadline": ("expired", None), "nan": ("error", None),
            "preempt": ("completed", calm)}[kind]
    assert status1 == want[0] and evicted1 == (kind == "preempt")
    assert want[1] is None or ids1[0] == want[1]
    assert ids1[0] == calm[:len(ids1[0])]


def test_a_crash_with_results_in_flight_requeues_to_the_same_ids(models):
    model = models("gpt")
    prompts = [_prompt(n, 70 + j) for j, n in enumerate((19, 6, 11))]

    def run(crash):
        eng = ServingEngine(model, num_slots=2, page_size=PS,
                            max_model_len=MAXLEN, prefill_chunk_tokens=8,
                            replica=f"crash-{crash}")
        seen = []

        def boom():
            seen.append(len(eng._pending))
            raise TransientError("injected")

        gate = _Gate(eng, {1})
        if crash:
            faults.inject(f"serving.step_crash@{eng.replica}", fn=boom,
                          at_trips={4})
        try:
            with eng:
                gate.wait()
                handles = [eng.submit(p, max_new_tokens=9) for p in prompts]
                gate.release()
                out = [h.result(timeout=600) for h in handles]
        finally:
            gate.release()
            faults.clear()
        return out, seen, _count("serving.engine_restarts", eng.replica)

    calm, _, none = run(False)
    shaken, seen, restarts = run(True)
    assert none == 0 and restarts == 1 and seen and seen[0] >= 1
    assert shaken == calm


def test_stop_and_quiescent_with_a_result_in_flight(models):
    model = models("gpt")
    eng = ServingEngine(model, num_slots=2, page_size=PS,
                        max_model_len=MAXLEN, replica="stop-in-flight")
    # held at the top of turn 3 with turn 2's step unread; stop() is asked
    # for there, so turn 3 is the last: it dispatches the short request's
    # last step (the lane leaves AHEAD) and reads turn 2's
    gate = _Gate(eng, {1, 3})
    try:
        eng.start()
        gate.wait()
        short = eng.submit(_prompt(5, 80), max_new_tokens=4)
        long = eng.submit(_prompt(6, 81), max_new_tokens=30)
        gate.release()
        gate.wait()
        assert len(short.token_ids) == 2 and eng._pending
        assert not eng.quiescent and eng.health == "healthy"
        stopper = threading.Thread(target=eng.stop)
        stopper.start()
        assert eng._stop_evt.wait(60)
        gate.release()
        stopper.join(60)
        assert not stopper.is_alive()
    finally:
        gate.release()
        faults.clear()
    # the short request's last token was in flight and no slot held it:
    # nobody is left waiting all the same, and nothing is left held
    assert len(short.token_ids) == 3 and len(long.token_ids) == 3
    assert short.status == "stopped" and long.status == "stopped"
    assert not eng._pending and eng.block_manager.used_pages == 0
    assert eng.quiescent


def test_engines_whose_inputs_the_host_makes_stay_at_depth_0(models):
    from paddle_tpu.serving.multitenant import MultiTenantEngine

    model = models("gpt")
    spec = ServingEngine(model, num_slots=2, page_size=PS,
                         max_model_len=MAXLEN, speculative_k=2,
                         replica="depth0-spec")
    tenant = MultiTenantEngine(model, num_slots=2, page_size=PS,
                               max_model_len=MAXLEN, replica="depth0-mt")
    plain = ServingEngine(model, num_slots=2, page_size=PS,
                          max_model_len=MAXLEN, replica="depth1-plain")
    outs = []
    for eng in (spec, tenant, plain):
        with eng:
            outs.append(eng.generate(_prompt(9, 90), max_new_tokens=12,
                                     timeout=600))
    assert outs[0] == outs[1] == outs[2]
    assert (spec._depth, tenant._depth, plain._depth) == (0, 0, 1)
    for eng in (spec, tenant):
        assert _count("serving.steps_dispatched_ahead", eng.replica) == 0
    assert _count("serving.steps_dispatched_ahead", plain.replica) >= 9
