"""service/aot.py on the sharded route: as `setup_programs_s.closed`: seconds of set-up spent
building the stored mesh programs (`aot_build_stage_seconds{kind,stage}`) and loading them
(`aot_load_seconds`), from the registry's snapshot taken when the window closed.  The
digest on the mesh is a stored program too, so nothing is traced outside the store, and a
run that found everything stored books loads alone: that is a reading here (the `.closed`
and `.bls` readers ask for a build or the digest leg's first call, which the mesh never
books).  None on a program without either series."""

from bench_setup import booked_before_the_window

SERIES = ("aot_build_stage_seconds", "aot_load_seconds")


def read(ctx: dict) -> float | None:
    return booked_before_the_window(ctx["counters"], SERIES, any_of=SERIES)
