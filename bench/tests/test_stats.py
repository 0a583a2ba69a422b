"""Rates and tails are taken over every sample, so a stall moves them."""
import pytest

from bench import stats
from bench.drive import Served, Window
from bench.traffic import Request


def _window(times_per_request, t_open=0.0, t_close=10.0, dues=None):
    w = Window(t_open=t_open, t_close=t_close)
    for i, times in enumerate(times_per_request):
        s = Served(Request(i, [1], len(times), None), i,
                   dues[i] if dues else t_open, t_open)
        s.times = list(times)
        s.tokens = [1] * len(times)
        w.served.append(s)
    return w


def test_itl_tail_sees_one_stall():
    steady = [[0.1 * k for k in range(1, 100)]]
    stalled = [[0.1 * k for k in range(1, 50)]
               + [0.1 * k + 2.0 for k in range(50, 100)]]
    g0 = stats.itl_gaps(steady, 0.0, 20.0)
    g1 = stats.itl_gaps(stalled, 0.0, 20.0)
    assert len(g0) == len(g1) == 98
    assert max(g1) == pytest.approx(2.1)
    assert stats.percentile(g1, 100) > stats.percentile(g0, 100)


def test_itl_tail_over_all_gaps_not_chunks():
    # 6% of gaps are slow: the p95 of all gaps is a slow one
    times, t = [], 0.0
    for k in range(100):
        t += 1.0 if k % 16 == 0 else 0.01
        times.append(t)
    gaps = stats.itl_gaps([times], 0.0, 1e9)
    assert stats.percentile(gaps, 95) > 0.5


def test_itl_counts_only_gaps_inside_the_window():
    gaps = stats.itl_gaps([[0.5, 1.5, 2.5, 3.5]], 1.0, 3.0)
    assert gaps == [pytest.approx(1.0)]


def test_tokens_per_s_counts_a_stall_in_the_window():
    from bench import spec

    tps = spec.load_module("metrics", "tokens_per_s")

    class Ctx:
        pass

    ctx = Ctx()
    ctx.window = _window([[1.0 + 0.01 * k for k in range(100)]])
    full = tps.read(ctx)
    ctx.window = _window([[1.0 + 0.01 * k for k in range(50)]])
    assert tps.read(ctx) == pytest.approx(full / 2)
    assert full == pytest.approx(10.0)


def test_ttft_counts_requests_still_waiting():
    v = stats.ttfts([1.0, 2.0, 9.0], [1.5, None, None], 0.0, 10.0)
    assert v == [pytest.approx(0.5), pytest.approx(8.0), pytest.approx(1.0)]
    # a request due before the window is not the window's
    assert stats.ttfts([-1.0], [0.5], 0.0, 10.0) == []


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
