"""service/engine.py: milliseconds of a convoy in which its worker was blocked
in `np.asarray` on a device result: the stage spans `convoy.deal_wait`,
`digest_wait`, `verify_wait` and `finalise_wait`, from the program's
`dkg_phase_seconds` histogram around the window, per convoy that passed each."""

from bench_spans import WAIT_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], WAIT_STAGES)
