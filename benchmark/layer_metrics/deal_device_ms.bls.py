"""dkg/ceremony.py programs: as `deal_device_ms.closed`, in the cell of one large
BLS12-381 G1 ceremony at a time (24-limb base field): device milliseconds of one
execution of the width-1 `deal` program, the mean over the executions of the XLA
module `jit_deal` that the traced slice holds whole (none whole: the longer cut one,
a lower bound; the run's `trace reduced` line says which)."""

from bench_trace import module_ms


def read(ctx: dict) -> float | None:
    return module_ms(ctx["trace"], "jit_deal")
