"""service/scheduler.py: median of (fetched - due) less the ceremony's own
convoy seconds: what a request waits before and after its convoy runs."""

from bench_stats import median


def read(ctx: dict) -> float | None:
    waits = [
        (r["fetched_s"] - r["due_s"] - r["convoy_s"]) * 1e3
        for r in ctx["records"]
        if r["status"] == "done" and r.get("convoy_s")
    ]
    return median(waits)
