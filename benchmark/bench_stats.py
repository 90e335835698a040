"""The statistics the metric readers and the run's log share."""

from __future__ import annotations

import math


def median(values: list[float]) -> float | None:
    if not values:
        return None
    s = sorted(values)
    mid = len(s) // 2
    return s[mid] if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def percentile(values: list[float], share: float) -> float | None:
    """Nearest-rank percentile: the smallest value with `share` of all at or below it."""
    if not values:
        return None
    s = sorted(values)
    return s[max(0, math.ceil(share * len(s)) - 1)]


def counter_delta(counters: dict, series: str) -> float:
    return counters["after"]["counters"].get(series, 0.0) - counters["before"]["counters"].get(series, 0.0)


def convoy_times(records: list[dict]) -> list[float]:
    """When each counted convoy was first fetched, ascending.  A convoy's members
    carry the same float `engine_s` (its wall time over its width), which tells
    them from every other convoy's."""
    done: dict[float, float] = {}
    for r in records:
        if r.get("engine_s") and r.get("fetched_s") is not None:
            done[r["engine_s"]] = min(r["fetched_s"], done.get(r["engine_s"], r["fetched_s"]))
    return sorted(done.values())
