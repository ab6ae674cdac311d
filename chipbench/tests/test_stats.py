"""(f) rates and tails are taken over the whole window: one stall moves
``serve_tok_s``, ``itl_p95_ms``, ``ttft_p95_ms`` and ``train_ips``."""

import pytest

from chipbench import stats


def test_percentile_is_numpys():
    import numpy as np

    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 50, 95, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([], 95) is None
    assert stats.percentile([4], 95) == 4.0


def _requests(stall_at=None, stall_s=0.0, n=20, tokens=50, gap=0.01):
    """``n`` requests sent together at t=0, a token every ``gap`` seconds;
    from token ``stall_at`` on, everything is ``stall_s`` later."""
    out = []
    for _ in range(n):
        times = [gap * (j + 1) + (stall_s if stall_at is not None
                                  and j >= stall_at else 0.0)
                 for j in range(tokens)]
        out.append(stats.Request(0.0, 0.0, 0.001, times, times[-1], True,
                                 prompt_len=8, n_out=tokens))
    return out


def test_serving_window_without_a_stall():
    m, w = stats.serve_metrics(_requests(), 0.0, 1.0)
    assert w["tokens"] == 20 * 50 and m["serve_tok_s"] == 1000.0
    assert m["itl_p95_ms"] == pytest.approx(10.0)
    assert m["ttft_p95_ms"] == pytest.approx(10.0)
    assert w["queue_ms"] == pytest.approx([1.0] * 20)


def test_one_stall_moves_rate_and_tail():
    """A 0.6 s stall in a 1 s window: the tokens it pushes past the close
    are not counted, and one gap in 49 of every request is 610 ms, which is
    over the 5% that the p95 leaves out only if it is in the pool: the
    pooled gaps hold it 20 times in 980."""
    m, w = stats.serve_metrics(_requests(stall_at=30, stall_s=0.6), 0.0, 1.0)
    assert w["tokens"] == 20 * 39                 # tokens 0..38 land inside
    assert m["serve_tok_s"] == 780.0
    gaps = sorted(w["itl_ms"])
    assert gaps[-1] == pytest.approx(610.0) and len(gaps) == 20 * 38
    assert m["itl_p95_ms"] == pytest.approx(10.0)  # 20 of 760: under 5%
    # a stall that hits one gap in ten of every request does reach the tail
    m2, _ = stats.serve_metrics(
        _requests(stall_at=5, stall_s=0.3, tokens=10, gap=0.01), 0.0, 1.0)
    assert m2["itl_p95_ms"] == pytest.approx(310.0)
    # and a stall before the first token moves the time to first token
    m3, _ = stats.serve_metrics(_requests(stall_at=0, stall_s=0.25), 0.0, 1.0)
    assert m3["ttft_p95_ms"] == pytest.approx(260.0)


def test_a_request_without_a_first_token_counts_as_the_worst():
    reqs = _requests(n=10) + [stats.Request(0.5, 0.5, None, [], None, False)]
    m, w = stats.serve_metrics(reqs, 0.0, 1.0)
    assert len(w["ttft_ms"]) == 11 and max(w["ttft_ms"]) == pytest.approx(10.0)
    nobody = [stats.Request(0.1, 0.1, None, [], None, False)]
    m, w = stats.serve_metrics(nobody, 0.0, 2.0)
    assert w["ttft_ms"] == [2000.0] and m["serve_tok_s"] == 0.0


def test_requests_due_outside_the_window_give_tokens_but_no_ttft():
    early = stats.Request(-1.0, -1.0, -0.9, [-0.5, 0.2, 0.4], 0.4, True)
    m, w = stats.serve_metrics([early], 0.0, 1.0)
    assert w["ttft_ms"] == [] and w["tokens"] == 2
    assert w["itl_ms"] == pytest.approx([700.0, 200.0])
    assert "ttft_p95_ms" not in m


def test_train_rate_counts_the_stall():
    even = [0.1 * (i + 1) for i in range(100)]
    assert stats.train_metrics(even, 4, 0.0)["train_ips"] == pytest.approx(40.0)
    stalled = even[:50] + [t + 2.5 for t in even[50:]]
    assert stats.train_metrics(stalled, 4, 0.0)["train_ips"] == \
        pytest.approx(400 / 12.5)
    assert stats.train_metrics([], 4, 0.0) == {}
