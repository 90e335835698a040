"""service/scheduler.py: `convoy_hold_ms`'s own reader (milliseconds a dispatched convoy
waits for its worker, `convoy.hold`), in the cell of the whole mix: over convoys of all
three buckets, so a light stack held behind a heavy convoy's finish shows here."""

from layer_metrics.convoy_hold_ms import read  # noqa: F401
