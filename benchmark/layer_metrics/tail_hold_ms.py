"""service/scheduler.py: over the requests at or above the window's p95 latency, mean
milliseconds their convoy spent in `convoy.hold` (dispatched, waiting for its worker to
come back from the convoy before it), from the convoy's spans in `tracing.TIMELINE`."""

from bench_spans import HOLD_STAGES
from bench_timeline import tail_part_ms


def read(ctx: dict) -> float | None:
    return tail_part_ms(ctx, "hold", HOLD_STAGES)
