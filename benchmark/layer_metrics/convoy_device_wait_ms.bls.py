"""service/engine.py: as `convoy_device_wait_ms.closed`, in the BLS12-381 G1 cell:
milliseconds a convoy's worker is blocked on device results (`convoy.*_wait`), per
convoy that passed each in the window."""

from bench_spans import WAIT_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], WAIT_STAGES)
