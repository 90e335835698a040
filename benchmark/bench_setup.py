"""Seconds the program booked during set-up, for the readers of `setup_s`'s parts.

Set-up ends where the window opens, and the window builds and loads nothing (or the
run is not `correct`), so everything a set-up series holds when the window closes was
booked before it opened: the readers take the `after` snapshot whole, not a delta."""

from __future__ import annotations

from bench_spans import hist_delta


def booked_before_the_window(
    counters: dict, series: tuple[str, ...], any_of: tuple[str, ...]
) -> float | None:
    """Sum of the histograms `series` over all their labels in the snapshot taken when
    the window closed; None where the program has none of `any_of` (it predates them)."""
    whole = {"before": {}, "after": counters["after"]}
    names = {s.partition("{")[0] for s in whole["after"].get("histograms", {})}
    if not names & set(any_of):
        return None
    return sum(hist_delta(whole, name)[0] for name in series)
