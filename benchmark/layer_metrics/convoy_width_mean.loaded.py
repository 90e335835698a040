"""`convoy_width_mean`'s own reader in `fleet_mix_reduced.loaded`, where the widths
are what the arrivals form and the end-to-end metric is the latency."""

from layer_metrics.convoy_width_mean import read  # noqa: F401
