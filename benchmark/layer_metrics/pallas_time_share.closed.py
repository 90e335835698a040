"""ops/pallas_* kernels: as `pallas_time_share`, in the cell whose end-to-end metric
is the latency: share of the device's busy time spent inside Mosaic custom calls, in
percent, from the trace."""


def read(ctx: dict) -> float | None:
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * trace["pallas_s"] / trace["busy_s"]
