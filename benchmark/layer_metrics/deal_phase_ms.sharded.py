"""parallel/mesh.py programs: milliseconds of a request's dealing on the mesh,
`mesh_collective_seconds{op="deal_commitments"}` + `{op="deal_shares"}`: from the dispatch
of the two `shard_map` programs (`jit_mesh_deal_commitments`, then `jit_mesh_deal_shares`,
1024 dealers a chip) to their outputs on the four chips, the mean over the requests the
window served (`bench_collectives.phase_ms`: the program's spans, because the traced slice
is shorter than a request).  None on a program without the series."""

from bench_collectives import phase_ms


def read(ctx: dict) -> float | None:
    return phase_ms(ctx["counters"], "deal_commitments", "deal_shares")
