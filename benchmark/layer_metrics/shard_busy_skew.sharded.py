"""parallel/mesh.py programs: how evenly the four shards work: least over greatest device
seconds of the planes' executions of the programs that hold no collective
(`jit_mesh_deal_commitments`, `jit_mesh_deal_shares`, `jit_mesh_digest_rows`), over the
executions the slice holds whole (`bench_collectives.busy_skew`); 1.0 where every shard
takes as long as the slowest.  Where the slice holds none of a program whole, its
executions at the slice's edge.  None without a trace or where none of the three ran in it."""

from bench_collectives import busy_skew


def read(ctx: dict) -> float | None:
    return busy_skew(ctx["trace"])
