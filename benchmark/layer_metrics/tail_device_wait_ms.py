"""service/engine.py: over the requests at or above the window's p95 latency, mean
milliseconds their convoy's worker was blocked on the device (`convoy.deal_wait`,
`digest_wait`, `verify_wait`, `finalise_wait`), from the convoy's spans in
`tracing.TIMELINE`; the log line gives each stage, the tail beside the window's mean."""

from bench_spans import WAIT_STAGES
from bench_timeline import tail_part_ms


def read(ctx: dict) -> float | None:
    return tail_part_ms(ctx, "device_wait", WAIT_STAGES)
