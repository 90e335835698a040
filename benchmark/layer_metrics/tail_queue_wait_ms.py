"""service/scheduler.py: over the requests at or above the window's p95 latency
(`latency_p95_program_ms`), mean milliseconds from admission to the pop that took the
request into a convoy (`popped - admitted` of its convoy's record in `tracing.TIMELINE`).
With `tail_hold_ms`, `tail_device_wait_ms`, `tail_host_ms` and `tail_rest_ms` it adds up to
the tail's mean latency (`benchmark/bench_timeline.py` `parts`)."""

from bench_timeline import tail_part_ms


def read(ctx: dict) -> float | None:
    return tail_part_ms(ctx, "queue", ("queue",))
