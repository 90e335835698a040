"""groups/precompute.py: seconds of set-up spent on the fixed-base tables
(`fixed_base_table_seconds{curve,source}`: `compose` on the device, `disk` for a
validated load of the host table, `build` where the cache had none; a process-cache hit
books nothing), summed over every table and source, from the registry's snapshot taken
when the window closed.  None on a program without the
series (the parent of the PR that added it)."""

from bench_setup import booked_before_the_window

SERIES = ("fixed_base_table_seconds",)


def read(ctx: dict) -> float | None:
    return booked_before_the_window(ctx["counters"], SERIES, any_of=SERIES)
