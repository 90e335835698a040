"""service/scheduler.py: ceremonies a convoy of the thin bucket (32,8): 80 requests in
1000, about five among 64 outstanding, so its convoys are truncated to ladder widths
under 8 (observations of `service_convoy_seconds{bucket="32x8",width}` by width)."""

from bench_mix import MID, width_mean


def read(ctx: dict) -> float | None:
    return width_mean(ctx["counters"], MID)
