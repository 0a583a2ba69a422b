"""The generator: the same seed gives the same requests, every seed the
same work, and the stated distributions."""
import itertools

import numpy as np
import pytest

from bench import spec, traffic


def _take(mix, seed, n, vocab=1000):
    return list(itertools.islice(traffic.requests(mix, seed, vocab), n))


@pytest.mark.parametrize("name", ["batch", "chat"])
def test_same_seed_same_requests(name):
    mix = spec.resolve_cell(spec.load_benchmark(), {
        "batch": "minicpm_2b-exact.batch",
        "chat": "minicpm_2b-exact.chat"}[name]).traffic
    a, b = _take(mix, 2**31 + 5, 40), _take(mix, 2**31 + 5, 40)
    assert [(r.prompt, r.out_len, r.offset_s) for r in a] == \
        [(r.prompt, r.out_len, r.offset_s) for r in b]
    c = _take(mix, 2**31 + 6, 40)
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_every_seed_gets_the_same_work_per_block():
    mix = {"loop": "open", "rate_per_s": 4.0,
           "prompt": {"dist": "loguniform", "lo": 32, "hi": 512},
           "output": {"dist": "loguniform", "lo": 16, "hi": 128}}
    n = 4 * traffic.BLOCK
    runs = [_take(mix, s, n) for s in (1, 2, 3_000_000_000)]
    for rs in runs[1:]:
        assert sorted(len(r.prompt) for r in rs) == \
            sorted(len(r.prompt) for r in runs[0])
        assert sorted(r.out_len for r in rs) == \
            sorted(r.out_len for r in runs[0])
        assert rs[-1].offset_s == pytest.approx(runs[0][-1].offset_s)
    assert [r.out_len for r in runs[0]] != [r.out_len for r in runs[1]]


def test_uniform_lengths_cover_the_range_evenly():
    q = traffic.quantiles({"dist": "uniform", "lo": 192, "hi": 320}, 16)
    assert q.min() >= 192 and q.max() <= 320
    assert np.mean(q) == pytest.approx((192 + 320) / 2, abs=1)


def test_loguniform_lengths_are_uniform_in_log():
    q = traffic.quantiles({"dist": "loguniform", "lo": 32, "hi": 512}, 1000)
    assert q.min() == 32 and q.max() == 512
    # half the mass below the geometric mean
    assert np.median(q) == pytest.approx(np.sqrt(32 * 513), rel=0.02)


def test_poisson_gaps_have_the_stated_rate():
    mix = {"loop": "open", "rate_per_s": 4.0,
           "prompt": {"dist": "uniform", "lo": 1, "hi": 2},
           "output": {"dist": "uniform", "lo": 1, "hi": 2}}
    rs = _take(mix, 9, 100 * traffic.BLOCK)
    gaps = np.diff([0.0] + [r.offset_s for r in rs])
    assert np.mean(gaps) == pytest.approx(0.25, rel=0.01)
    # exponential: the standard deviation equals the mean
    assert np.std(gaps) == pytest.approx(0.25, rel=0.1)


def test_closed_loop_requests_have_no_due_time():
    mix = {"loop": "closed", "prompt": {"dist": "uniform", "lo": 16, "hi": 64},
           "output": {"dist": "uniform", "lo": 192, "hi": 320}}
    rs = _take(mix, 3, 32)
    assert all(r.offset_s is None for r in rs)
    assert all(16 <= len(r.prompt) <= 64 for r in rs)
    assert all(1 <= t < 1000 for r in rs for t in r.prompt)


def test_a_schedule_seed_fixes_lengths_and_arrivals():
    mix = {"loop": "open", "rate_per_s": 0.8, "schedule_seed": 12345,
           "prompt": {"dist": "loguniform", "lo": 32, "hi": 512},
           "output": {"dist": "loguniform", "lo": 16, "hi": 128}}
    a, b = _take(mix, 1, 50), _take(mix, 3_000_000_000, 50)
    assert [(len(r.prompt), r.out_len, r.offset_s) for r in a] == \
        [(len(r.prompt), r.out_len, r.offset_s) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_a_schedule_is_an_independent_poisson_sample():
    """With a schedule_seed, gaps and lengths are independent draws, not
    a block's mid-quantiles: arrival counts vary from block to block and
    the longest gaps pass the 1 - 1/32 quantile."""
    mix = {"loop": "open", "rate_per_s": 4.0, "schedule_seed": 7,
           "prompt": {"dist": "loguniform", "lo": 32, "hi": 512},
           "output": {"dist": "uniform", "lo": 16, "hi": 128}}
    rs = _take(mix, 1, 200 * traffic.BLOCK)
    gaps = np.diff([0.0] + [r.offset_s for r in rs])
    assert np.mean(gaps) == pytest.approx(0.25, rel=0.05)
    assert np.std(gaps) == pytest.approx(0.25, rel=0.1)
    assert np.max(gaps) > 0.25 * np.log(32) * 1.5
    # arrivals in windows of 16 mean gaps: Poisson counts, variance ~ mean
    counts = np.bincount((np.array([r.offset_s for r in rs]) // 4.0)
                         .astype(int))[:-1]
    assert np.var(counts) == pytest.approx(np.mean(counts), rel=0.3)
    blocks = [sorted(r.out_len for r in rs[i:i + traffic.BLOCK])
              for i in range(0, 4 * traffic.BLOCK, traffic.BLOCK)]
    assert len({tuple(b) for b in blocks}) == len(blocks)
    assert np.median([len(r.prompt) for r in rs]) == \
        pytest.approx(np.sqrt(32 * 513), rel=0.1)
