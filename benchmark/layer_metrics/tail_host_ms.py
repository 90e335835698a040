"""service/engine.py: over the requests at or above the window's p95 latency, mean
milliseconds of their convoy's host stages (`bench_spans.HOST_STAGES`: `draw`, the
dispatches, `rho_fold`, `blame`, `encode`), from the convoy's spans in `tracing.TIMELINE`;
the log line gives each stage, the tail beside the window's mean."""

from bench_spans import HOST_STAGES
from bench_timeline import tail_part_ms


def read(ctx: dict) -> float | None:
    return tail_part_ms(ctx, "host", HOST_STAGES)
