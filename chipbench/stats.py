"""End-to-end metrics of one window, from the harness's host-clock records.

All times are seconds since the window opened. A request's time to first
token runs from when it was due (not from when it was submitted); a
request due in the window with no first token by its end enters with its
wait so far. Inter-token gaps are every gap between consecutive output
tokens of a request inside the window, over all requests.
"""
from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def ttfts(served, seconds: float):
    return [(s.token_times[0] if s.token_times else seconds) - s.due
            for s in served]


def gaps(served):
    return [b - a for s in served
            for a, b in zip(s.token_times, s.token_times[1:])]


def end_to_end(served, seconds: float, setup_s: float) -> dict:
    """Every end-to-end metric this harness knows, by name."""
    out = {"setup_s": setup_s}
    first = ttfts(served, seconds)
    if first:
        out["ttft_p90_ms"] = 1e3 * percentile(first, 90)
    itl = gaps(served)
    if itl:
        out["itl_p50_ms"] = 1e3 * percentile(itl, 50)
        out["itl_p95_ms"] = 1e3 * percentile(itl, 95)
    out["output_tokens_per_s"] = sum(len(s.token_times)
                                     for s in served) / seconds
    return out
