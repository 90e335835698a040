"""service/engine.py: `convoy_device_wait_ms`'s own reader (milliseconds a convoy's worker
is blocked on device results, `convoy.*_wait`), in the cell of the whole mix: over convoys
of all three buckets, each behind the other workers' programs of other shapes."""

from layer_metrics.convoy_device_wait_ms import read  # noqa: F401
