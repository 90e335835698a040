"""BLS12-381 G1 at-scale smoke (BASELINE config #5 in reduced form).

The 381-bit base field runs on 24 limbs — 2.25x the limb work of the
256-bit curves — so this drives the full engine (deal, device
transcript hash, RLC batch verify, finalise) at growing n on the
current backend and reports wall-clock per phase.

The ceremony runs TWICE in one process: run 0 pays compilation and
fixed-base table builds (reported as the ``cold`` phases), run 1 is the
steady state a warm service actually operates in (jit caches hot,
tables resident) and is what ``pairs_per_sec`` is computed from — the
same warm methodology the secp256k1 record uses.

Usage: python scripts/bls_smoke.py [n] [t]    (default 512 170)
"""
from __future__ import annotations

import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from dkg_tpu.utils import compilecache

compilecache.enable()

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.utils.tracing import CeremonyTrace

n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
t = int(sys.argv[2]) if len(sys.argv) > 2 else (n - 1) // 3

print(f"bls12_381_g1 n={n} t={t} platform={jax.devices()[0].platform}", flush=True)

runs = []
for phase_name in ("cold", "steady"):
    trace = CeremonyTrace()
    t0 = time.perf_counter()
    c = ce.BatchedCeremony("bls12_381_g1", n, t, b"bls-smoke", random.Random(0xB15))
    setup_s = time.perf_counter() - t0
    out = c.run(rho_bits=128, trace=trace)
    assert "error" not in out, out.get("error")
    assert bool(np.asarray(out["ok"]).all())
    print(f"[{phase_name}] setup {setup_s:.1f}s", flush=True)
    for name, span in trace.timings_s.items():
        print(f"[{phase_name}] {name:10s} {span:8.3f}s", flush=True)
    runs.append(trace.timings_s)

cold, steady = runs

# Artifact for the record (BLS_SMOKE.json at the repo root): BASELINE
# config 5 evidence, keyed per backend+shape so a TPU run ADDS to the
# CPU record instead of clobbering it.  ``phases_s`` and
# ``pairs_per_sec`` are STEADY-state (run 1); the cold run keeps its
# own key so compile/table cost stays attributable.
import json
import pathlib

_ARTIFACT = pathlib.Path(__file__).resolve().parent.parent / "BLS_SMOKE.json"
report = {
    "curve": "bls12_381_g1",
    "n": n,
    "t": t,
    "platform": jax.devices()[0].platform,
    "phases_s": {k: round(v, 3) for k, v in steady.items()},
    "phases_cold_s": {k: round(v, 3) for k, v in cold.items()},
    "pairs_per_sec": round(
        n * (n - 1) / steady["verify"], 1
    ) if steady.get("verify") else None,
    "all_verified": True,
}
try:
    records = json.loads(_ARTIFACT.read_text())
    if not isinstance(records, dict):
        records = {}
except (OSError, ValueError):
    records = {}
records[f"{report['platform']}_n{n}_t{t}"] = report
_ARTIFACT.write_text(json.dumps(records, indent=1))
print(json.dumps(report), flush=True)
