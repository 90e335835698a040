"""ops/pallas_* kernels: `pallas_time_share`'s own reader (share of the device's busy time
inside Mosaic custom calls, in percent, from the trace), in the cell of the whole mix."""

from layer_metrics.pallas_time_share import read  # noqa: F401
