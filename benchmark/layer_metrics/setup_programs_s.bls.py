"""service/aot.py and the digest leg: as `setup_programs_s.closed`, in the BLS12-381 G1
cell: seconds of set-up spent building stored programs (`aot_build_stage_seconds`),
loading them (`aot_load_seconds`) and tracing the digest leg outside the store
(`digest_leg_first_call_seconds`), from the registry's snapshot taken when the window
closed.  None on a program without the build-stage series."""

from bench_setup import booked_before_the_window

SERIES = ("aot_build_stage_seconds", "aot_load_seconds", "digest_leg_first_call_seconds")


def read(ctx: dict) -> float | None:
    return booked_before_the_window(
        ctx["counters"], SERIES, any_of=("aot_build_stage_seconds", "digest_leg_first_call_seconds")
    )
