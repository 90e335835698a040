"""service/engine.py: as `convoy_host_ms.closed`, in the BLS12-381 G1 cell: milliseconds
of a convoy (here one (1024,341) request) in which its worker ran host code
(`convoy.draw`, `*_dispatch`, `rho_fold`, `blame`, `encode`), per convoy that passed
each in the window."""

from bench_spans import HOST_STAGES, stage_ms_per_convoy


def read(ctx: dict) -> float | None:
    return stage_ms_per_convoy(ctx["counters"], HOST_STAGES)
