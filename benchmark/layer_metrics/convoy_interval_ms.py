"""service/engine.py: milliseconds from one convoy's completion to the next, as
the client fetches them: (last - first completion) / (convoys - 1) over the
convoys counted in the window.  The rate without the window's edges: where
`ceremonies_per_s` moves in steps of one convoy, this does not."""

from bench_stats import convoy_times


def read(ctx: dict) -> float | None:
    times = convoy_times(ctx["records"])
    return (times[-1] - times[0]) / (len(times) - 1) * 1e3 if len(times) > 1 else None
