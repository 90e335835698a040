"""95th percentile of (fetched - due) over every request due in the window.
One that failed, expired, was refused or never finished counts as the worst:
it is given the longest wait the harness allows, window plus drain."""

from bench_stats import percentile


def read(ctx: dict) -> float | None:
    worst = ctx["seconds"] + float(ctx["cell"].get("drain_s", 60.0))
    waits = [
        r["fetched_s"] - r["due_s"] if r["status"] == "done" and r["ok"] else worst
        for r in ctx["records"]
    ]
    p95 = percentile(waits, 0.95)
    return None if p95 is None else p95 * 1e3
