"""service/scheduler.py: mean milliseconds a request of the heavy bucket (64,16) waits
from admission to the pop that takes it (`service_queue_wait_seconds{bucket="64x16"}`):
the head of the queue picks it only when it is the oldest, behind the light stacks."""

from bench_mix import HEAVY, mean_ms


def read(ctx: dict) -> float | None:
    return mean_ms(ctx["counters"], "service_queue_wait_seconds", HEAVY)
