"""The served path from the client's side: `ceremonies_per_s`'s own reader (ceremonies
fetched in the window and equal to the reference, over its seconds), in the cell of the
whole mix, where it has no bound: at 64 outstanding the mean latency is 64 over it."""

from end_to_end.ceremonies_per_s import read  # noqa: F401
