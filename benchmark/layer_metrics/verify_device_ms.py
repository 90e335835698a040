"""dkg/ceremony.py programs: device milliseconds of one execution of the
`verify` program (one convoy): the mean over the executions of the XLA module
`jit__verify_stack` that the traced slice holds whole (none whole: the longer cut one)."""

from bench_trace import module_ms


def read(ctx: dict) -> float | None:
    return module_ms(ctx["trace"], "jit__verify_stack")
