"""ops/pallas_* kernels: as `pallas_time_share.closed`, in the BLS12-381 G1 cell (the
kernels at 24 limbs): share of the device's busy time spent inside Mosaic custom
calls, in percent, from the trace."""


def read(ctx: dict) -> float | None:
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
