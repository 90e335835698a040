"""What the readers of `fleet_mix.saturated` share: the scheduler's series by
bucket (`service_convoy_seconds{bucket,width}`, `service_queue_wait_seconds{bucket}`)
around the window.  The mix's buckets are (16,5), light and stacked to width 8;
(32,8), thin, its convoys of ragged width; (64,16), heavy, every request a
convoy of its own (`service/buckets.py` `WIDTH_CAP_N`).  A program that books
`service_convoy_seconds` without the bucket (the parent of the PR that added the
label) has no series to match: the readers return None and the metric is left out."""

from __future__ import annotations

from bench_spans import hist_delta

LIGHT, MID, HEAVY = "16x5", "32x8", "64x16"
WIDTHS = (8, 4, 2, 1)  # the scheduler's ladder, `service/buckets.py`


def mean_ms(counters: dict, name: str, bucket: str) -> float | None:
    """Mean milliseconds of the observations histogram `name` took for `bucket` in the window."""
    seconds, count = hist_delta(counters, name, bucket=bucket)
    return seconds / count * 1e3 if count else None


def width_mean(counters: dict, bucket: str) -> float | None:
    """Ceremonies a convoy of `bucket` finished in the window: one observation of
    `service_convoy_seconds` a convoy, under its width."""
    by_width = {w: hist_delta(counters, "service_convoy_seconds", bucket=bucket, width=str(w))[1] for w in WIDTHS}
    convoys = sum(by_width.values())
    return sum(w * c for w, c in by_width.items()) / convoys if convoys else None
