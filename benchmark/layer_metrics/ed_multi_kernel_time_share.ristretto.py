"""ops/pallas_* kernels, read by name: share of the device's busy time inside the two
multi-operation point kernels, `pt_window_step` (4 doublings and an addition a launch)
and `pt_ladder_mul_add` (the whole Horner ladder a launch), in percent, from the reduced
trace's operations (`bench_roofline.kernel_seconds`).  It says which tier of the point
kernels ran, from the device's side: 0 where the window step is composed of `pt_double`
and `pt_add` launches and the ladder of XLA operations (ristretto255 before PR 42), over 0
on the fused tier.  None without a trace."""

from bench_roofline import MULTI_OP, kernel_seconds


def read(ctx: dict) -> float | None:
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    by_kernel = kernel_seconds(trace)
    multi = sum(by_kernel.get(k, 0.0) for k in MULTI_OP)
    return 100.0 * multi / (trace["busy_s"] * max(1, trace["devices"]))
