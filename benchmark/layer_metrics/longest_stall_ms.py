"""service/scheduler.py: the longest stretch of the window in which at least one request
was admitted and unfinished and no request completed (`benchmark/bench_timeline.py`
`stalls`, from the members' stamps in `tracing.TIMELINE`).  The log line says when it
was and what every worker slot was in for most of it."""

from bench_timeline import note, slots_during, stalls, window


def read(ctx: dict) -> float | None:
    cutout = window(ctx)
    if cutout is None:
        return None
    records, t_from, t_to = cutout
    found = stalls(records, t_from, t_to)
    if not found:
        return None
    a, b = max(found, key=lambda g: g[1] - g[0])
    note(f"longest stall {(b - a) * 1e3:.3f} ms at +{a - t_from:.3f}s of {len(found)} stretches: {slots_during(records, a, b)}")
    return (b - a) * 1e3
