"""Process start to the first timed request: JAX start, tables, executables
loaded or baked, one convoy of every shape the cell can form."""


def read(ctx: dict) -> float:
    return ctx["setup_s"]
