"""service/scheduler.py: milliseconds a dispatched convoy waits for its worker to
come back from finishing the convoy before it (`convoy.hold`, from
`start_convoy`'s return to `finish_convoy`'s entry), from the program's
`dkg_phase_seconds` histogram around the window, per convoy held in it."""

from bench_spans import HOLD_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], HOLD_STAGES)
