"""service/scheduler.py: the program's own reading of what `latency_p95_ms` reads from
the client's side: the 95th percentile (nearest rank) of `completed - admitted` over the
requests that finished `done` inside the window, from the convoy records of
`tracing.TIMELINE` (`benchmark/bench_timeline.py`).  It leaves out what the client adds:
the harness's 2 ms poll and, in an open loop, how late the generator sent.  The line it
logs puts the client-side p95 of the same run beside it, because a traced run computes
no end-to-end metric."""

from bench_stats import percentile
from bench_timeline import mean, note, split, window
from end_to_end.latency_p95_ms import read as client_p95_ms


def read(ctx: dict) -> float | None:
    cutout = window(ctx)
    if cutout is None:
        return None
    rows = split(*cutout)
    p95 = percentile([r["latency"] for r in rows], 0.95)
    if p95 is None:
        return None
    note(
        f"latency p95, program: {p95 * 1e3:.3f} ms over {len(rows)} requests done in the window "
        f"(mean {mean(rows, 'latency') * 1e3:.3f}); client side, the same run: {client_p95_ms(ctx)} ms "
        f"over {len(ctx['records'])} records"
    )
    return p95 * 1e3
