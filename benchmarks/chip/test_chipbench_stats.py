"""Percentile and rate arithmetic over request records, failed requests
and the drain after the window included."""
import math

import numpy as np
import pytest

from chipbench.stats import (Record, end_to_end, gaps_s, percentile,
                             tokens_in, ttft_s)


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    for n in (1, 2, 7, 100, 151):
        v = rng.exponential(size=n).tolist()
        for p in (50, 90, 95, 99):
            assert percentile(v, p) == pytest.approx(np.percentile(v, p))


def test_inf_stays_in_the_tail():
    v = [1.0] * 9 + [math.inf]
    assert percentile(v, 50) == 1.0
    assert percentile(v, 95) == math.inf
    assert math.isnan(percentile([], 90))


def _rec(due, times, max_new=None, failed=False):
    r = Record(0, 0, 8, max_new or len(times), due)
    r.token_times = list(times)
    r.tokens = [1] * len(times)
    r.failed = failed
    return r


def test_failed_request_is_late():
    ok = _rec(0.0, [0.1, 0.2])
    bad = _rec(0.0, [0.3], failed=True)
    assert ttft_s(ok) == pytest.approx(0.1)
    assert ttft_s(bad) == math.inf
    assert not bad.finished and ok.finished
    short = _rec(0.0, [0.1], max_new=2)
    assert not short.finished


def test_window_rate_leaves_out_the_drain():
    # window [10, 20): a request due at 19 whose tokens land at 19.5,
    # 20.5 and 21 (the drain) counts one token towards the rate
    recs = [_rec(10.0, [10.5, 11.0, 11.5]), _rec(19.0, [19.5, 20.5, 21.0])]
    assert tokens_in(recs, 10.0, 20.0) == 4
    e = end_to_end(recs, 10.0, 10.0)
    assert e["tokens_per_s"] == pytest.approx(0.4)
    # the drained request's gaps and first token still count
    assert sorted(gaps_s(recs)) == pytest.approx([0.5, 0.5, 0.5, 1.0])
    assert e["ttft_p90_ms"] == pytest.approx(500.0)
    assert e["itl_p95_ms"] == pytest.approx(
        np.percentile([500, 500, 500, 1000], 95))


def test_tokens_delivered_together_have_zero_gap():
    r = _rec(0.0, [0.2, 0.2, 0.3])
    assert gaps_s([r]) == pytest.approx([0.0, 0.1])


def test_sample_holds_longest_and_enough_tokens():
    """The check's sample: the longest finished request, ``per_tenant``
    of each tenant, more where outputs are short, never an unfinished
    one, and the same draw from the same seed."""
    from chipbench.check import sample
    recs = []
    for i in range(40):
        r = Record(i, i % 2, 100 + i, 5, 0.0)
        r.tokens = [1] * (5 if i % 7 else 2)      # every 7th unfinished
        recs.append(r)
    base = sample(recs, 2, 3, 2 ** 33 + 5)
    assert [len(v) for v in base.values()] == [3, 3]
    longest = max((r for r in recs if r.finished),
                  key=lambda r: r.prompt_len)
    assert any(r is longest for v in base.values() for r in v)
    more = sample(recs, 2, 3, 2 ** 33 + 5, min_tokens=80)
    for t, v in more.items():
        assert v[:3] == base[t]
        assert sum(len(r.tokens) for r in v) >= 40
        assert all(r.finished and r.tenant == t for r in v)
        assert len({r.rid for r in v}) == len(v)
    assert sample(recs, 2, 3, 2 ** 33 + 5, min_tokens=80) == more
