"""(e) the generators are deterministic in the seed; every seed sends the
same sizes in another order; the open loop times from the due instant."""

import numpy as np
import pytest

from chipbench.traffic import closed_loop, lengths, open_loop

MIX = {"lengths_seed": 3, "pool_size": 32, "max_total": 64,
       "prompt": {"median": 20, "sigma": 0.6, "min": 4, "max": 48},
       "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 32},
       "clients": 3, "stagger_s": 0.3,
       "rate_per_s": 50.0, "burst": 1, "schedule_seed": 11}


def _drain(source, n):
    return [source.requests.get(i) for i in range(n)]


def test_lengths_fit_and_are_the_mixes_own():
    pool = lengths.length_pool(MIX)
    assert pool == lengths.length_pool(dict(MIX))
    assert len(pool) == 32
    for p, o in pool:
        assert 4 <= p <= 48 and 1 <= o <= 32 and p + o <= 64
    assert lengths.length_pool(dict(MIX, lengths_seed=4)) != pool


def test_a_prompt_that_leaves_no_room_is_refused():
    with pytest.raises(ValueError):
        lengths.length_pool(dict(MIX, max_total=10))


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_requests_are_deterministic_in_the_seed(seed):
    a, b = (lengths.Requests(MIX, seed, 101) for _ in range(2))
    for i in (0, 5, 40):
        (pa, oa), (pb, ob) = a.get(i), b.get(i)
        assert oa == ob and np.array_equal(pa, pb)
        assert pa.min() >= 1 and pa.max() < 101 and pa.dtype == np.int64


def test_seeds_send_the_same_sizes_and_other_ids():
    a, b = lengths.Requests(MIX, 1, 101), lengths.Requests(MIX, 2, 101)
    pool = lengths.length_pool(MIX)
    for r in (a, b):
        assert [(len(r.get(i)[0]), r.get(i)[1]) for i in range(40)] == \
            [pool[i % 32] for i in range(40)]
    assert not np.array_equal(a.get(0)[0][:4], b.get(0)[0][:4])
    assert not np.array_equal(a.get(0)[0], a.get(32)[0])     # fresh ids
    assert a.prompt_lengths() == sorted({p for p, _ in pool})


def _starts(seed):
    src = closed_loop.Source(MIX, seed, 101)
    src.start(100.0)
    return src, [(d, c) for d, c, _, _ in src.due(100.3)]


def test_closed_loop_sends_on_completion_only():
    src, first = _starts(5)
    # every client starts inside its own third of the stagger
    assert [c for _, c in first] == [0, 1, 2]
    for due, c in first:
        assert 100.0 + 0.1 * c <= due < 100.0 + 0.1 * (c + 1)
    assert src.due(500.0) == []                  # every client is waiting
    src.done(1, 101.5)
    again = src.due(101.6)
    assert [(d, c) for d, c, _, _ in again] == [(101.5, 1)]
    src.stop()
    src.done(2, 102.0)
    assert src.due(103.0) == []
    assert src.describe()["sent"] == 4 and src.lateness() is None


def test_closed_loop_starts_are_the_seeds_own():
    (a, first), (b, again), (_, other) = _starts(5), _starts(5), _starts(6)
    assert first == again and first != other
    a.start(100.0), b.start(100.0)
    assert np.array_equal(a.due(100.3)[0][2], b.due(100.3)[0][2])    # ids


def test_open_loop_times_from_the_due_instant():
    offs = open_loop.arrival_offsets(MIX, 10.0)
    assert offs == open_loop.arrival_offsets(dict(MIX), 10.0)
    assert offs == sorted(offs) and 350 < len(offs) < 650     # 50/s for 10 s
    src = open_loop.Source(MIX, 9, 101, horizon_s=10.0)
    src.start(1000.0)
    # the generator is held up for half a second: what was due meanwhile is
    # sent late, but each is stamped with the instant it was due
    late = src.due(1000.5)
    assert len(late) == sum(1 for t in offs if t <= 0.5)
    for (due, _, _, _), t in zip(late, offs):
        assert due == pytest.approx(1000.0 + t) and due <= 1000.5
    how_late = src.lateness()
    assert how_late["max_s"] == pytest.approx(0.5 - offs[0])
    assert 0 < how_late["mean_s"] < how_late["max_s"]
    src.done(0, 1000.6)                          # completions send nothing
    assert src.due(1000.5) == []
    # another seed: the same instants and sizes, other ids
    other = open_loop.Source(MIX, 10, 101, horizon_s=10.0)
    assert other.offsets == src.offsets
    bursty = open_loop.arrival_offsets(dict(MIX, burst=4), 10.0)
    assert len(bursty) % 4 == 0 and bursty[0] == bursty[3] != bursty[4]
    assert 0.5 < len(bursty) / len(offs) < 1.5   # the same mean rate
