"""`tail_device_wait_ms`'s own reader in `fleet_mix_reduced.saturated`, the one cell that is
judged on `ceremonies_per_s`: at 64 outstanding the rate is 64 over the mean latency, so
what lengthens a request there lowers it."""

from layer_metrics.tail_device_wait_ms import read  # noqa: F401
