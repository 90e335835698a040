"""parallel/mesh.py collectives: share of the four planes' busy time spent inside
collective operations (`all-to-all`, `all-gather`, read by name from the reduced trace:
`bench_collectives.collective_seconds`), in percent.  An operation of that name also holds
the wait for the slowest shard to reach it.  None without a trace or where the slice holds no collective."""

from bench_collectives import time_share


def read(ctx: dict) -> float | None:
    return time_share(ctx["trace"])
