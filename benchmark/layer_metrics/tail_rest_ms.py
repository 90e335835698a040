"""service/scheduler.py: over the requests at or above the window's p95 latency, mean
milliseconds of `completed - admitted` that no stage and no queue wait holds: the pop to
the convoy's first span, the gaps between spans, `_finish_outcomes` up to the member's
own completion.  It is what the other four `tail_*` leave, so the five add up to the
tail's mean latency by construction."""

from bench_timeline import tail_part_ms


def read(ctx: dict) -> float | None:
    return tail_part_ms(ctx, "rest", ("rest",))
