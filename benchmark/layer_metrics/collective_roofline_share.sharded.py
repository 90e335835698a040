"""parallel/mesh.py collectives: the roofline share of the sharded ceremony's collectives,
in percent: the least time one chip's interconnect could take for the bytes it must send
in the collective operations the slice holds (`bench_collectives.roofline_share`: the
operations counted by name and held against `collective_calls` and `collective_bytes` a
request, so a cut request counts by the part of it that is there; the v5e's published
200 GB/s a chip), over the seconds a plane spent inside collective operations in the
slice, waits for the slowest shard included.  It reads low, never high.  None without a
trace or where the slice holds no collective."""

from bench_collectives import roofline_share


def read(ctx: dict) -> float | None:
    if ctx["trace"] is None:
        return None
    import jax

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.parallel import mesh as pm

    (shape,) = ctx["config"]["mix"]
    cfg = ce.CeremonyConfig(ctx["config"]["curve"], int(shape["n"]), int(shape["t"]))
    cs = cfg.cs
    devices = max(1, ctx["trace"]["devices"])
    return roofline_share(
        ctx["trace"], ctx["config"], jax.devices()[0].device_kind, cs.scalar.limbs,
        cs.ncoords * cs.field.limbs, pm._verify_chunk_default(cfg, cfg.n // devices),
    )
