"""Shared by the benchmark's tests: load `benchmark/run.py` by path, and break
the timed path underneath it (the control that `correct` has to catch)."""

import contextlib
import importlib.util
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
MANIFEST = ROOT / "BENCHMARK.json"
TEST_MANIFEST = DATA / "manifest.json"


def bench_run():
    """`benchmark/run.py` as a module (it puts `benchmark/` on sys.path for its siblings)."""
    if "benchmark_run" not in sys.modules:
        spec = importlib.util.spec_from_file_location("benchmark_run", ROOT / "benchmark" / "run.py")
        module = importlib.util.module_from_spec(spec)
        sys.modules["benchmark_run"] = module
        spec.loader.exec_module(module)
    return sys.modules["benchmark_run"]


@contextlib.contextmanager
def broken_engine(what: str):
    """Every convoy the scheduler finishes comes back off by one limb:
    `share` adds 1 to limb 0 of every final share, `master` flips one bit of
    the encoded master key.  The scheduler, the harness and the window run as
    they are; only the answer is altered where it is produced."""
    from dkg_tpu.service import scheduler

    sound = scheduler.finish_convoy

    def finish(runtime, fl):
        outs = sound(runtime, fl)
        for out in outs:
            if what == "share":
                shares = out.final_shares.copy()
                shares[:, 0] = (shares[:, 0] + 1) & 0xFFFF
                out.final_shares = shares
            else:
                out.master = bytes([out.master[0], out.master[1] ^ 1]) + out.master[2:]
        return outs

    scheduler.finish_convoy = finish
    try:
        yield
    finally:
        scheduler.finish_convoy = sound
