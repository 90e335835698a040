"""service/scheduler.py: mean milliseconds of a convoy of the heavy bucket (64,16),
from its pop to its outcomes (`service_convoy_seconds{bucket="64x16"}`; width 1 by
`WIDTH_CAP_N`), over the convoys finished in the window."""

from bench_mix import HEAVY, mean_ms


def read(ctx: dict) -> float | None:
    return mean_ms(ctx["counters"], "service_convoy_seconds", HEAVY)
