#!/usr/bin/env python
"""Run the test suite one pytest process per file, with crash retry.

Why not plain ``pytest tests/``: this box's XLA:CPU compiler segfaults
sporadically inside ``backend_compile_and_load`` on long-lived processes
that compile many large limb-arithmetic graphs (observed twice mid-suite
with the compilation cache OFF; single-file
runs of the same tests pass).  Until that jaxlib flake is gone, process-
per-file isolation keeps one crash from voiding a 40-minute run: a file
(or shard — see SHARDS) whose process dies on a signal is retried up to
twice, and only three consecutive crashes or a genuine test failure
fails the suite.

Usage: python scripts/run_tests.py [-m MARKEXPR] [pytest args...]
Exit code 0 iff every file passed (or was fully deselected).
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_TESTS_COLLECTED = 5

# Files whose single-process run compiles enough large graphs that the
# XLA:CPU flake's crash probability becomes near-certain late in the
# file (round 4: test_ceremony.py died at the same late test twice,
# then every piece passed in isolation).  Shard them into N consecutive
# pytest processes over the collected test ids.  Round 5 moved the
# compile-heavy breadth tests to the slow tier, so the DEFAULT tier no
# longer needs sharding (each shard re-ran the module fixture's full
# engine compile — 3x the fixture cost); the slow tier keeps it.
SHARDS: dict[str, int] = {}
SLOW_SHARDS: dict[str, int] = {"test_ceremony.py": 4}

# Files with no (or tiny) XLA compiles: batched into ONE pytest process
# in the default tier.  A fresh interpreter + jax import costs ~3 s per
# process on this 1-core box — across 16 light files that is ~50 s of
# pure overhead, and their combined compile load is far below the level
# where the XLA:CPU crash flake appears (crash isolation still guards
# them: the whole batch retries as one unit).  Heavy (compile-bearing)
# files keep process-per-file isolation.
LIGHT_BATCH = {
    "test_committee.py",
    "test_complaint_storm.py",
    "test_complaints_batch.py",
    "test_crypto.py",
    "test_curve_extension.py",
    "test_device_hash.py",
    "test_errors.py",
    "test_groups_device.py",
    "test_groups_host.py",
    "test_import_hygiene.py",
    "test_memproof.py",
    "test_native.py",
    "test_net.py",
    "test_pallas_field.py",
    "test_pallas_point.py",
    "test_serde.py",
    "test_tracing.py",
}


def _env() -> dict:
    env = dict(os.environ)
    # CPU-only env (see .claude/skills/verify/SKILL.md)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONPATH"] = REPO
    return env


def collect_ids(path: str, extra: list[str]) -> list[str]:
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", path, "-q", "--collect-only", *extra],
        cwd=REPO, env=_env(), capture_output=True, text=True,
    )
    if proc.returncode not in (0, NO_TESTS_COLLECTED):
        # crashed/partial collection: sharding on a truncated id list
        # would silently skip tests — caller falls back to one process
        return []
    # Test-id lines start with the file's repo-relative path and contain
    # "::"; match on that prefix (NOT on absence-of-spaces — parametrized
    # ids may legally contain spaces) so no collected test is dropped.
    rel = os.path.relpath(path, REPO)
    return [
        ln.strip()
        for ln in proc.stdout.splitlines()
        if ln.strip().startswith(rel) and "::" in ln
    ]


def run_file(path: str, extra: list[str], targets: list[str] | None = None) -> int:
    cmd = [sys.executable, "-m", "pytest", *(targets or [path]), "-q", *extra]
    return subprocess.call(cmd, cwd=REPO, env=_env())


def run_with_retry(path: str, extra: list[str], targets: list[str] | None, label: str) -> int:
    """THE retry policy: rerun up to twice when the process died on a
    signal (the sporadic XLA:CPU compiler crash); real test failures
    are never retried."""
    rc = run_file(path, extra, targets)
    for attempt in (1, 2):
        if not (rc < 0 or rc >= 128):
            break
        print(f"[run_tests] {label} crashed (rc={rc}); retry {attempt}", flush=True)
        rc = run_file(path, extra, targets)
    return rc


def main() -> int:
    # positional args select test files; flags pass through to pytest
    selected = [a for a in sys.argv[1:] if not a.startswith("-")
                and "::" not in a and a.endswith(".py")]
    extra = [a for a in sys.argv[1:] if a not in selected]
    files = sorted(glob.glob(os.path.join(REPO, "tests", "test_*.py")))
    if selected:
        names = {os.path.basename(s) for s in selected}
        files = [f for f in files if os.path.basename(f) in names]
        if not files:
            print(f"[run_tests] no test files match {sorted(names)}")
            return 2
    failures: list[str] = []
    t0 = time.time()
    # Crash-isolation shards apply whenever the slow tests are
    # INCLUDED in the run (explicit -m slow, or a bare invocation
    # with no filter at all — the heaviest load of the three);
    # only the default "not slow" tier is light enough to skip them.
    includes_slow = not any("not slow" in a for a in extra)
    if not includes_slow:
        # default tier: one process for all the light files (they are
        # only "light" with the slow marks deselected)
        light = [f for f in files if os.path.basename(f) in LIGHT_BATCH]
        files = [f for f in files if os.path.basename(f) not in LIGHT_BATCH]
        if light:
            t1 = time.time()
            rc = run_with_retry(light[0], extra, light, "light batch")
            if rc not in (0, NO_TESTS_COLLECTED):
                failures.append("light-batch")
            print(f"[run_tests] light batch ({len(light)} files): rc={rc} "
                  f"({time.time()-t1:.0f}s)", flush=True)
    for path in files:
        name = os.path.basename(path)
        t1 = time.time()
        nshards = (SLOW_SHARDS if includes_slow else SHARDS).get(name, 1)
        chunks: list[list[str] | None] = [None]
        if nshards > 1:
            ids = collect_ids(path, extra)
            if len(ids) >= nshards:
                per = -(-len(ids) // nshards)
                chunks = [ids[i : i + per] for i in range(0, len(ids), per)]
        rcs = [run_with_retry(path, extra, chunk, name) for chunk in chunks]
        rc = next((r for r in rcs if r not in (0, NO_TESTS_COLLECTED)), rcs[0])
        if rc not in (0, NO_TESTS_COLLECTED):
            failures.append(name)
        print(f"[run_tests] {name}: rc={rc} ({time.time()-t1:.0f}s"
              f"{', %d shards' % len(chunks) if len(chunks) > 1 else ''})",
              flush=True)
    print(f"[run_tests] total {time.time()-t0:.0f}s; "
          f"{'FAIL: ' + ', '.join(failures) if failures else 'all green'}",
          flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
