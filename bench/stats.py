"""End-to-end statistics over every sample of the window.

No statistic is taken from medians of chunks: a stall anywhere in the
window moves the rate and the tails.
"""
from __future__ import annotations

import numpy as np

__all__ = ["percentile", "itl_gaps", "ttfts"]


def percentile(values, q: float) -> float:
    """The q-th percentile of all values (linear interpolation)."""
    v = np.asarray(list(values), np.float64)
    if v.size == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(v, q))


def itl_gaps(token_times, t_open: float, t_close: float):
    """Gaps between consecutive tokens of each request, both inside the
    window.  ``token_times``: one list of emission times per request."""
    gaps = []
    for times in token_times:
        inside = [t for t in times if t_open <= t <= t_close]
        gaps.extend(b - a for a, b in zip(inside, inside[1:]))
    return gaps


def ttfts(due_times, first_token_times, t_open: float, t_close: float):
    """First token minus due time for each request due in the window; a
    request still waiting at the close counts with what it has waited."""
    out = []
    for due, first in zip(due_times, first_token_times):
        if due is None or not t_open <= due <= t_close:
            continue
        out.append((first if first is not None and first <= t_close
                    else t_close) - due)
    return out
