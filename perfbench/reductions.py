"""The benchmark's arithmetic: percentiles, interval unions and the small
fixed set of reductions a per-layer metric may name. Kept here so that every
PR computes the same number in the same way."""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

REDUCTIONS = (
    "count",
    "sum",
    "p50",
    "p95",
    "per_era",
    "count_per_era",
    "share_of_window",
    "share_of_count",
    "share_of_source",
    "last",
)


def percentile(values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default), on a
    copy; q in [0, 100]."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint, sorted cover of the given intervals."""
    out: List[List[float]] = []
    for lo, hi in sorted(i for i in intervals if i[1] > i[0]):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [
        (max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)
    ]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """What [lo, hi] holds that the disjoint sorted cover `busy` does not."""
    out = []
    at = lo
    for a, b in clip(busy, lo, hi):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_times(
    parents: Sequence[Interval], children: Iterable[Interval]
) -> List[float]:
    """For each parent interval, its length minus the part its children
    cover (the choosing-metrics guide's self time)."""
    cover = union(children)
    return [(hi - lo) - total(clip(cover, lo, hi)) for lo, hi in parents]


def reduce(
    name: str,
    durations: Sequence[float],
    *,
    eras: int = 0,
    window_s: float = 0.0,
    population: int = 0,
    source_total: float = 0.0,
) -> Optional[float]:
    """One of REDUCTIONS over a list of durations (or plain values). Returns
    None where there is nothing to reduce: the harness then leaves the
    metric out of the line."""
    if name not in REDUCTIONS:
        raise ValueError(f"unknown reduction {name!r} (have {REDUCTIONS})")
    if name == "count":
        return float(len(durations))
    if name == "count_per_era":
        return len(durations) / eras if eras else None
    if name == "share_of_count":
        return len(durations) / population if population else None
    if not durations:
        return None
    if name == "sum":
        return float(sum(durations))
    if name == "last":
        return float(durations[-1])
    if name == "p50":
        return percentile(durations, 50)
    if name == "p95":
        return percentile(durations, 95)
    if name == "per_era":
        return sum(durations) / eras if eras else None
    if name == "share_of_window":
        return sum(durations) / window_s if window_s > 0 else None
    # share_of_source: what is left of the source after `less`, over the source
    return sum(durations) / source_total if source_total > 0 else None
