"""Test only: a per-layer metric added as a file alone."""


def read(ctx):
    return float(len(ctx["records"]))
