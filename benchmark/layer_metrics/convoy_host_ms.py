"""service/engine.py: milliseconds of a convoy in which its worker ran host code:
the stage spans `convoy.draw`, `deal_dispatch`, `digest_dispatch`, `rho_fold`,
`verify_dispatch`, `blame`, `finalise_dispatch` and `encode`, from the program's
`dkg_phase_seconds` histogram around the window, per convoy that passed each.
On the TPU `encode` is mostly a wait: `gd.encode_batch` runs a device program."""

from bench_spans import HOST_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], HOST_STAGES)
