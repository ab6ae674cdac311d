"""``correct`` has been shown to fail: the control (the reference in the
precision below the one the cell states) and every fault a cell can have
come out as not correct at a size a test run can hold, and the sound
program comes out correct.  The faults are planted underneath the harness,
in the program's own classes; the harness's look for a chip is skipped."""

import time

import jax
import pytest

from chipbench import compare, run
from chipbench.tools import readings


def _run(toy_spec, name, seed=2 ** 31 + 3, kept=None):
    cell = toy_spec.cell(name)
    return cell, run.run_cell(toy_spec, cell, seed, 1.0, False,
                              t0=time.time(), kept=kept)


def _failing(result):
    return {k for k, v in result["compared"].items()
            if not (v["value"] is not None and v["value"] <= v["limit"])}


@pytest.fixture(scope="module")
def sound_train(toy_spec):
    kept = {}
    cell, result = _run(toy_spec, "toy_gpt.toy_batches", kept=kept)
    return cell, result, kept


@pytest.fixture(scope="module")
def sound_serve(toy_spec):
    kept = {}
    cell, result = _run(toy_spec, "toy_gpt.toy_closed", kept=kept)
    return cell, result, kept


def _stand_ins(toy_spec, cell, kept):
    ctx = run.RunContext(toy_spec, cell, 2 ** 31 + 3, 0.5, False, time.time())
    return toy_spec.module("entries", cell.workload["entry"]).stand_ins(ctx, kept)


def test_training_control_and_fault_fail_where_the_program_passes(
        toy_spec, sound_train):
    cell, result, kept = sound_train
    assert result["correct"] is True and not _failing(result)
    limits = cell.workload["limits"]
    got = _stand_ins(toy_spec, cell, kept)
    for name in ("control", "half_batch"):
        over = [k for k, lim in limits.items() if got[name][k] > lim]
        assert over, f"{name} failed none of the cell's numbers"
        row = readings.judged(cell, 1, name, got[name])
        assert row["correct"] is False
        assert sorted(row["failed_numbers"]) == sorted(over)
        # and by a margin: three times the program's own reading or more
        assert any(got[name][k] >= 3 * max(kept["numbers"][k], 1e-12)
                   for k in over)
    assert got["half_batch"]["grad_norm_gap"] > 10 * limits["grad_norm_gap"]


def test_an_open_loop_cell_is_data_only(toy_spec):
    """The same entry under the other arrival process: a mix file with
    ``kind: open_loop`` and a cell file, no code."""
    _, result = _run(toy_spec, "toy_gpt.toy_open")
    assert result["correct"] is True and not _failing(result)
    assert result["attempted"] >= 4 and result["failed"] == 0


def test_a_state_left_unchanged_reads_one():
    want = {"w": [2.0, 3.0], "b": [0.5]}
    still = {"w": [0.0, 0.0], "b": [0.0]}
    assert compare.worst_leaf_gap(still, want)[0] == pytest.approx(1.0)
    double = {"w": [4.0, 6.0], "b": [1.0]}
    assert compare.worst_leaf_gap(double, want)[0] == pytest.approx(1.0)
    # a leaf that is all but zero is measured against the median leaf
    tiny = {"w": [2.0, 3.0], "b": [1e-9]}
    gap, where = compare.worst_leaf_gap({"w": [2.0, 3.0], "b": [2e-9]}, tiny)
    assert gap < 1e-8
    assert compare.near_zero_leaves(tiny) == {("b", 0)}


def test_serving_control_and_fault_fail_where_the_program_passes(
        toy_spec, sound_serve):
    cell, result, kept = sound_serve
    assert result["correct"] is True and not _failing(result)
    assert kept["numbers"]["tokens_compared"] >= 100
    limit = cell.workload["limits"]["logit_gap_max"]
    got = _stand_ins(toy_spec, cell, kept)
    assert got["control"]["logit_gap_max"] > 3 * limit
    assert got["token_altered"]["logit_gap_max"] > 100 * limit
    # the control is read where the program is judged, and nowhere else
    assert got["control"]["tokens_compared"] == \
        kept["numbers"]["tokens_compared"]
    # and held to the cell's limits by the run's own comparison
    for name in ("control", "token_altered"):
        row = readings.judged(cell, 1, name, got[name])
        assert row["correct"] is False and row["failed_numbers"]
    # the longest request of the window is always among those compared
    lengths = [len(p) + len(o) for p, o in kept["served"]]
    assert lengths[0] == max(lengths)


def _break_train_step(monkeypatch, how):
    from paddle_tpu.jit import TrainStep

    sound = TrainStep.__call__

    def state_unchanged(self, *batch):
        before = jax.tree_util.tree_map(jax.numpy.copy, self.state_dict())
        loss = sound(self, *batch)
        self.set_state_dict(before)
        return loss

    def half_batch(self, *batch):
        if len(batch) == 1:                      # one dict of named inputs
            return sound(self, {k: v[:v.shape[0] // 2]
                                for k, v in batch[0].items()})
        return sound(self, *(v[:v.shape[0] // 2] for v in batch))

    monkeypatch.setattr(TrainStep, "__call__",
                        {"state_unchanged": state_unchanged,
                         "half_batch": half_batch}[how])


@pytest.mark.parametrize("cell_name, how, caught_by", [
    ("toy_gpt.toy_batches", "state_unchanged", "update_norm_gap"),
    ("toy_gpt.toy_batches", "half_batch", "grad_norm_gap")])
def test_a_broken_train_step_is_not_correct(toy_spec, monkeypatch, cell_name,
                                            how, caught_by):
    _break_train_step(monkeypatch, how)
    _, result = _run(toy_spec, cell_name)
    assert result["correct"] is False
    assert caught_by in _failing(result)
    if how == "state_unchanged":
        assert result["compared"]["update_norm_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-3)
        assert "loss_step1" not in _failing(result)


def test_a_token_altered_where_it_is_produced_is_not_correct(
        toy_spec, monkeypatch):
    from paddle_tpu.serving import engine as _engine

    class Tampered(list):
        def append(self, tok):
            super().append((tok + 1) % 2039 if len(self) == 2 else tok)

    sound = _engine.RequestHandle.__init__

    def init(self, *a, **k):
        sound(self, *a, **k)
        self.token_ids = Tampered()

    monkeypatch.setattr(_engine.RequestHandle, "__init__", init)
    _, result = _run(toy_spec, "toy_gpt.toy_closed")
    assert result["correct"] is False
    assert _failing(result) == {"logit_gap_max", "logit_gap_mean"}


def test_a_request_that_never_finishes_is_failed_not_wrong(
        toy_spec, monkeypatch):
    """An answer that never comes is for ``failed`` and so for ``correct``;
    the drain gives up after its limit instead of hanging."""
    serve = toy_spec.module("entries", "serve")
    sound = serve._Driver.pump

    def impatient(self, until, drain_s=60.0):
        if until is None:
            for client in list(self.live)[:1]:    # one handle never completes
                h, due, prompt, n = self.live[client]
                self.live[client] = (_Never(h), due, prompt, n)
            return sound(self, until, drain_s=1.0)
        return sound(self, until, drain_s)

    class _Never:
        done = False

        def __init__(self, h):
            self.__dict__["_h"] = h

        def __getattr__(self, k):
            return getattr(self._h, k)

    monkeypatch.setattr(serve._Driver, "pump", impatient)
    t0 = time.time()
    _, result = _run(toy_spec, "toy_gpt.toy_closed")
    assert result["failed"] == 1 and result["correct"] is False
    assert not _failing(result)            # late or lost, not wrong
    assert time.time() - t0 < 60
