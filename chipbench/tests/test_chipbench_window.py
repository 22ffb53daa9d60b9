"""Window arithmetic: TTFT from the due time, failures as misses,
nearest-rank percentiles and their sample counts."""
from types import SimpleNamespace as NS

import pytest

from chipbench import window


def rec(due, first=None, later=(), finished=True, in_window=True,
        limit=1.0):
    req = None
    if first is not None:
        req = NS(first_token_time=first, token_times=list(later))
    return NS(due=due, req=req, finished=finished, in_window=in_window,
              arrival=NS(ttft_limit_s=limit))


def test_nearest_rank():
    xs = list(range(1, 101))
    assert window.nearest_rank(xs, 90) == 90
    assert window.nearest_rank(xs, 95) == 95
    assert window.nearest_rank([3.0], 90) == 3.0
    assert window.nearest_rank([5, 1, 4, 2, 3], 50) == 3
    with pytest.raises(ValueError):
        window.nearest_rank([], 50)


def test_ttft_from_due_and_misses():
    open_e = 10.0
    recs = [rec(0.0, 10.2, [10.25, 10.30]),          # ttft 0.2 s
            rec(1.0, 11.5, [11.6], limit=1.0),        # ttft 0.5 s
            rec(2.0, None, finished=False),           # never served
            rec(-1.0, 9.2, [10.1, 10.2], in_window=False)]
    out = window.summarize(recs, open_e, 5.0, stop_e=20.0, gap_limit_s=0.1)
    assert out["attempted"] == 3 and out["failed"] == 1
    assert out["ttft_samples"] == 3
    # the unserved request waited 20 - 12 = 8 s when the run stopped
    assert out["ttft_p90_ms"] == pytest.approx(8000.0)
    assert out["ttft_p50_ms"] == pytest.approx(500.0)
    assert out["gap_samples"] == 3
    # two met both limits; the unserved one missed
    assert out["slo_attainment"] == pytest.approx(200 / 3)
    assert out["ttft_attainment"] == pytest.approx(200 / 3)
    # tokens inside [10, 15): 10.2, 10.25, 10.3, 11.5, 11.6 and the
    # earlier request's 10.1, 10.2
    assert out["output_tokens"] == 7
    assert out["output_tok_s"] == pytest.approx(7 / 5.0)


def test_slow_gaps_miss_the_limit():
    recs = [rec(0.0, 10.1, [10.4, 10.7])]            # mean gap 0.3 s
    out = window.summarize(recs, 10.0, 5.0, 20.0, gap_limit_s=0.1)
    assert out["slo_attainment"] == 0.0
    assert out["ttft_attainment"] == 100.0
    assert out["itl_p95_ms"] == pytest.approx(300.0)
