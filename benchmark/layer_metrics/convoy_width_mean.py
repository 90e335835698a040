"""service/scheduler.py: ceremonies done per convoy popped, from the program's
own counters around the window (how well same-bucket requests stack)."""

from bench_stats import counter_delta


def read(ctx: dict) -> float | None:
    convoys = counter_delta(ctx["counters"], "service_convoys_total")
    done = counter_delta(ctx["counters"], 'service_completed_total{status="done"}')
    return done / convoys if convoys else None
