"""Ceremonies fetched inside the window and equal to the reference, over the
window's seconds: all the work and all the time."""


def read(ctx: dict) -> float:
    return sum(1 for r in ctx["records"] if r["ok"]) / ctx["seconds"]
