"""Client-side arithmetic on the harness's own clock.

The engine stamps each token with the ``now`` its step was *given*, before
the step ran.  A client sees the token when the step *returns*, so every
``now`` is mapped to the return time of the step that received it.
"""

from __future__ import annotations

import math


def percentile(sorted_vals: list, q: float) -> float:
    """Linear interpolation between closest ranks of a pre-sorted list
    (the arithmetic of ``repro.obs.metrics.percentile``)."""
    if not sorted_vals:
        return math.nan
    if len(sorted_vals) == 1:
        return float(sorted_vals[0])
    pos = (len(sorted_vals) - 1) * q
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    frac = pos - lo
    return float(sorted_vals[lo] * (1.0 - frac) + sorted_vals[hi] * frac)


def returned_times(token_times: list[float], step_return: dict) -> list[float]:
    """Map each token's ``now`` stamp to the return time of its step."""
    return [step_return[t] for t in token_times]


def ttfts(due: dict, first: dict, end: float) -> list[float]:
    """Time to first token of every request due in the window, from when it
    was due.  A request whose first token never came counts at ``end - due``:
    it is never dropped."""
    return sorted(first.get(rid, end) - t for rid, t in due.items())


def gaps(times_by_rid: dict) -> list[float]:
    """Every gap between consecutive tokens of every request."""
    out = []
    for ts in times_by_rid.values():
        out.extend(b - a for a, b in zip(ts, ts[1:]))
    return sorted(out)


def rate(work: float, window_s: float) -> float:
    """All the work in the window over the whole window."""
    return work / window_s
