"""service/scheduler.py: as `convoy_hold_ms`, in the cell whose end-to-end metric
is the latency: milliseconds a dispatched convoy waits for its worker
(`convoy.hold`), per convoy held in the window."""

from bench_spans import HOLD_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], HOLD_STAGES)
