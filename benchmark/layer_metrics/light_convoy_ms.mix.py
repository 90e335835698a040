"""service/scheduler.py: mean milliseconds of a convoy of the light bucket (16,5),
from its pop to its outcomes (`service_convoy_seconds{bucket="16x5"}`; width 8 while
the queue is full), over the convoys finished in the window."""

from bench_mix import LIGHT, mean_ms


def read(ctx: dict) -> float | None:
    return mean_ms(ctx["counters"], "service_convoy_seconds", LIGHT)
