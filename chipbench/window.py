"""Window arithmetic: the end-to-end metrics from what the requests saw.

Requests are timed from their due time on the engine's clock.  The
population is every request due inside the window, served or not:

- TTFT: first token minus due time.  A request refused, failed or not
  drained has no first token; it enters the tail with the time it had
  waited when the run stopped (a lower bound), and misses the limits.
- Gaps: every gap between consecutive tokens of those requests.
- Output tokens per second: every token emitted inside the window, by
  any request, over the window's length.
- SLO attainment: the share of the population whose TTFT met its limit
  and whose mean gap met the gap limit; TTFT attainment, the share
  whose TTFT alone met it.

Percentiles are nearest-rank: the smallest sample with at least that
share of the samples at or below it.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence


def nearest_rank(values: Sequence[float], q: float) -> float:
    if not values:
        raise ValueError("no samples")
    xs = sorted(values)
    k = max(math.ceil(q / 100.0 * len(xs)), 1)
    return xs[k - 1]


def token_times(req) -> List[float]:
    if req is None or not req.first_token_time:
        return []
    return [req.first_token_time] + list(req.token_times)


def summarize(records, open_e: float, window_s: float, stop_e: float,
              gap_limit_s: float) -> Dict[str, float]:
    """``open_e`` and ``stop_e``: the engine-clock times the window
    opened and the run stopped following requests."""
    pop = [r for r in records if r.in_window]
    ttft, gaps, met, met_ttft, failed = [], [], 0, 0, 0
    for r in pop:
        due = r.due + open_e
        times = token_times(r.req)
        if not r.finished:
            failed += 1
        if times:
            ttft.append(times[0] - due)
        else:
            ttft.append(stop_e - due)
        g = [b - a for a, b in zip(times, times[1:])]
        gaps.extend(g)
        mean_gap = sum(g) / len(g) if g else 0.0
        if r.finished and ttft[-1] <= r.arrival.ttft_limit_s:
            met_ttft += 1
            met += mean_gap <= gap_limit_s
    close_e = open_e + window_s
    emitted = sum(1 for r in records for t in token_times(r.req)
                  if open_e <= t < close_e)
    out = {"attempted": len(pop), "failed": failed,
           "ttft_samples": len(ttft), "gap_samples": len(gaps),
           "output_tokens": emitted}
    if ttft:
        out["ttft_p90_ms"] = nearest_rank(ttft, 90) * 1e3
        out["ttft_p50_ms"] = nearest_rank(ttft, 50) * 1e3
        out["slo_attainment"] = 100.0 * met / len(pop)
        out["ttft_attainment"] = 100.0 * met_ttft / len(pop)
    if gaps:
        out["itl_p95_ms"] = nearest_rank(gaps, 95) * 1e3
        out["itl_p50_ms"] = nearest_rank(gaps, 50) * 1e3
    out["output_tok_s"] = emitted / window_s
    return out
