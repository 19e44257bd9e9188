"""Window arithmetic: tails over all requests and gaps of the window,
rates over the window only, and a stall that shows in both tails."""
import numpy as np
import pytest

from chipbench import window as W


def steady(stall_at=None, stall_s=0.0, n=100, tick=0.02, out=20):
    """n requests due every 0.1 s, each served 0.05 s after it is due
    and then one token a tick; a stall pushes back every token emitted
    after ``stall_at``."""
    recs = []
    for i in range(n):
        due = 0.1 * i
        r = W.RequestRecord(i, due, 10, out, np.zeros(10, np.int32))
        for k in range(out):
            t = due + 0.05 + k * tick
            if stall_at is not None and t >= stall_at:
                t += stall_s
            r.token_times.append(t)
        r.finish_reason = "length"
        recs.append(r)
    return recs


WIN = W.Window(2.0, 8.0)


def test_percentiles_over_the_window():
    recs = steady()
    assert W.percentile(W.ttfts(recs, WIN), 90) == pytest.approx(0.05)
    assert W.percentile(W.token_gaps(recs, WIN), 95) == pytest.approx(0.02)
    assert len(W.ttfts(recs, WIN)) == 60
    assert W.percentile([], 90) is None


def test_stall_moves_both_tails():
    base = steady()
    hit = steady(stall_at=5.0, stall_s=0.5)
    assert W.percentile(W.ttfts(hit, WIN), 90) > \
        W.percentile(W.ttfts(base, WIN), 90) + 0.1
    assert W.percentile(W.token_gaps(hit, WIN), 99.9) >= 0.5
    # a stall of a few ticks per request moves the 95th percentile of gaps
    many = steady(stall_at=None)
    for r in many[20:80]:
        r.token_times[10:] = [t + 0.3 for t in r.token_times[10:]]
        r.token_times[15:] = [t + 0.3 for t in r.token_times[15:]]
    assert W.percentile(W.token_gaps(many, WIN), 95) > 0.1


def test_tokens_only_inside_the_window():
    recs = steady()
    inside = sum(1 for r in recs for t in r.token_times if 2.0 <= t < 8.0)
    assert W.tokens_in(recs, WIN) == inside
    late = steady(stall_at=0.0, stall_s=100.0)
    assert W.tokens_in(late, WIN) == 0


def test_requests_still_waiting_count_as_missing():
    recs = steady()
    recs[30].token_times.clear()
    recs[40].finish_reason = "rejected"
    att = W.attainment(recs, WIN, ttft_s=1.0, mean_gap_ms=100)
    assert att == pytest.approx(58 / 60)
    slow = W.attainment(recs, WIN, ttft_s=0.01, mean_gap_ms=100)
    assert slow == 0.0
