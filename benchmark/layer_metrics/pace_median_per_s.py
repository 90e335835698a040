"""service/engine.py: the median over the window's ten tenths of the ceremonies
counted in the tenth, as a rate.  Beside `ceremonies_per_s` it says whether a run
that reads low lost one stretch (this stays, the whole window reads under it by
the hole's share) or was slower throughout (both low).  The run's `pace` line
prints the tenths themselves."""

from bench_stats import pace_median_per_s


def read(ctx: dict) -> float | None:
    return pace_median_per_s(ctx["records"], ctx["seconds"])
