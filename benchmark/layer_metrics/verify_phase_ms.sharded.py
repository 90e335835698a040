"""parallel/mesh.py programs: milliseconds of a request's verify+finalise phase on the mesh,
`mesh_collective_seconds{op="verify_finalise"}`: from the fold of rho to the batch check's
result on the host, so the dispatch of `jit_mesh_verify_finalise`, the program on the four
chips (the point-RLC over a shard's dealers, the gathered columns, the chunked `all_to_all`
loop, the batch check, the aggregation) and the fetch of `ok`; the mean over the requests
the window served (`bench_collectives.phase_ms`).  The program's span and not the trace's
module: the traced slice is shorter than a request, so it never holds this program whole,
and the longer of its cut pieces would read where the slice fell, not what the program
took (PERF.md section 6, PR 44).  None on a program without the series."""

from bench_collectives import phase_ms


def read(ctx: dict) -> float | None:
    return phase_ms(ctx["counters"], "verify_finalise")
