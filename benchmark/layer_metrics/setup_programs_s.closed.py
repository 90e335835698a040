"""service/aot.py and the digest leg: seconds of set-up spent building stored
programs (`aot_build_stage_seconds`: trace, lower, compile, serialize of each),
loading them (`aot_load_seconds`) and tracing the digest leg outside the store
(`digest_leg_first_call_seconds`), from the registry's snapshot taken when the window
closed (all of it was booked before the window opened: the window builds nothing, or
the run is not `correct`).  None on a program without the build-stage series."""

from bench_spans import hist_delta

SERIES = ("aot_build_stage_seconds", "aot_load_seconds", "digest_leg_first_call_seconds")


def read(ctx: dict) -> float | None:
    whole = {"before": {}, "after": ctx["counters"]["after"]}
    names = {s.partition("{")[0] for s in whole["after"].get("histograms", {})}
    if "aot_build_stage_seconds" not in names and "digest_leg_first_call_seconds" not in names:
        return None
    return sum(hist_delta(whole, name)[0] for name in SERIES)
