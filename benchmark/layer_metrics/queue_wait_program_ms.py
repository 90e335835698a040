"""service/scheduler.py: mean milliseconds from a request's admission to the pop
that takes it into a convoy, as the scheduler itself records it
(`service_queue_wait_seconds`), over the requests popped in the window."""

from bench_spans import hist_delta


def read(ctx: dict) -> float | None:
    seconds, popped = hist_delta(ctx["counters"], "service_queue_wait_seconds")
    return seconds / popped * 1e3 if popped else None
