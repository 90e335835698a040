"""ops/pallas_* kernels: the roofline share of the point kernels in the ristretto255 cell,
in percent: the least time this device could take for the `pt_*` launches of the
`jit_deal`, `jit_verify_batch` and `jit_master_key_from_bare` executions the slice holds
whole (operations and bytes from the program's schedule, `bench_roofline.py`, where the
peaks and the bound that binds are written down), over the seconds the trace books to
`pt_*[tpu_custom_call]` operations in the whole slice.  It reads low, never high.  None
without a trace or on a program that runs the composed tier."""

from bench_roofline import roofline_share


def read(ctx: dict) -> float | None:
    import jax

    return roofline_share(ctx["trace"], ctx["config"], jax.devices()[0].device_kind)
