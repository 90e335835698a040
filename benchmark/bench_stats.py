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


def counted(record: dict) -> bool:
    """A ceremony that was fetched and compared: it carries its convoy's `engine_s`."""
    return bool(record.get("engine_s")) and record.get("fetched_s") is not None


def convoy_times(records: list[dict]) -> list[float]:
    """When each counted convoy was first fetched, ascending.  A convoy's members
    carry the same float `engine_s` (its wall time over its width), which tells
    them from every other convoy's."""
    done: dict[float, float] = {}
    for r in records:
        if counted(r):
            done[r["engine_s"]] = min(r["fetched_s"], done.get(r["engine_s"], r["fetched_s"]))
    return sorted(done.values())


def tenths(records: list[dict], seconds: float) -> list[int]:
    """Counted ceremonies fetched in each tenth of the window."""
    counts = [0] * 10
    for r in records:
        if counted(r):
            counts[min(9, int(r["fetched_s"] / seconds * 10))] += 1
    return counts


def pace_median_per_s(records: list[dict], seconds: float) -> float | None:
    """The median tenth's ceremonies as a rate.  A run that lost one stretch reads
    its whole-window rate under this by the hole's share; one that was slower
    throughout reads both low."""
    counts = tenths(records, seconds)
    return median(counts) * 10.0 / seconds if any(counts) else None


def open_loop_load(records: list[dict], seconds: float) -> dict:
    """What says whether an open-loop run held its rate: the requests without an
    answer, by status; the mean latency (fetched - due, seconds) of the answered
    requests due in each fifth of the window, where a backlog that grows shows as a
    last fifth far above the second; and how late the generator sent (sent - due),
    the window's first second apart."""
    load: dict = {s: sum(1 for r in records if r["status"] == s) for s in ("refused", "unsent", "unfinished")}
    fifths: list[list[float]] = [[] for _ in range(5)]
    for r in records:
        if r.get("fetched_s") is not None:
            fifths[min(4, int(r["due_s"] / seconds * 5))].append(r["fetched_s"] - r["due_s"])
    load["latency_by_fifth_s"] = [sum(f) / len(f) if f else None for f in fifths]
    late = [r["sent_s"] - r["due_s"] for r in records if r.get("sent_s") is not None and r["due_s"] >= 1.0]
    load["late_p99_s"], load["late_max_s"] = percentile(late, 0.99), max(late, default=None)
    return load
