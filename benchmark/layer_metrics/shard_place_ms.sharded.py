"""service/engine.py on the sharded route: milliseconds a request spends moving its two
coefficient tensors from the host onto the four chips, dealer-sharded and waited for
(`mesh_place_seconds`, booked by `parallel.mesh.place_coeffs` inside `convoy.deal_dispatch`),
the mean over the requests placed in the window.  None on a program without the series."""

from bench_spans import hist_delta


def read(ctx: dict) -> float | None:
    seconds, placed = hist_delta(ctx["counters"], "mesh_place_seconds")
    return seconds / placed * 1e3 if placed else None
