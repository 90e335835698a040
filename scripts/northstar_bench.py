#!/usr/bin/env python
"""North-star scale run: the mesh-sharded ceremony, measured and published.

BASELINE.md pins the driver target — secp256k1, n=4096, t=1365, <10 s on
8 chips.  This script runs ``parallel.mesh.run_sharded_ceremony`` at a
requested shape on a real device mesh (a host-count-forced CPU mesh when
no TPU is attached — clearly labelled ``platform``), byte-checks the
sharded path against the unsharded ``BatchedCeremony`` engine, and emits
one ``NORTHSTAR_r*.json`` round artifact at the repo root plus the same
dict as its last stdout line (bench.py's north-star rung runs this
script in a time-boxed child and embeds that line in the BENCH round's
``north_star`` slot; scripts/perf_regress.py gates round-over-round
regressions of ``wall_s`` at matching shape).

The artifact always records the TARGET config next to the MEASURED one:
a 1-core CI box cannot execute n=4096 honestly, so it publishes the
measured rung, the mesh shape, the platform, and the pair-count
extrapolation to n=4096 — never a fabricated headline.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import random
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

if __name__ == "__main__":  # virtual mesh before jax init
    # The forced CPU mesh must exist before any backend init;
    # --platform ambient keeps the attached accelerator (the TPU path).
    _ndev = 8
    _ambient = False
    for _i, _a in enumerate(sys.argv):
        if _a == "--ndev" and _i + 1 < len(sys.argv):
            _ndev = int(sys.argv[_i + 1])
        elif _a.startswith("--ndev="):
            _ndev = int(_a.split("=", 1)[1])
        elif _a == "--platform" and _i + 1 < len(sys.argv):
            _ambient = sys.argv[_i + 1] == "ambient"
        elif _a == "--platform=ambient":
            _ambient = True
    if not _ambient:
        from dkg_tpu.parallel.hostmesh import force_cpu_mesh

        force_cpu_mesh(_ndev)

TARGET = {
    "curve": "secp256k1",
    "n": 4096,
    "t": 1365,
    "chips": 8,
    "budget_s": 10.0,
}


def _pair_cost(n: int, t: int) -> float:
    """The shape's dominant work term: the n*(t+1) commitment/verify
    column grid plus the n^2 share grid (deal + all_to_all + RLC dot).
    Used only to extrapolate a measured rung to the n=4096 target —
    advisory, always published next to the measured number."""
    return n * (t + 1) + n * n


def _bit_exact(curve: str, n: int, t: int, rho_bits: int, mesh) -> bool:
    """Sharded vs unsharded at (n, t): master key bytes, per-party
    final shares, and the batch-check verdict, all limb-exact (rho is
    bit-identical by construction through sharded_transcript_digest —
    equality of the finals pins it transitively)."""
    import numpy as np

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.parallel import mesh as pm

    rng = random.Random(0x4096)
    c = ce.BatchedCeremony(curve, n, t, b"north-star-oracle", rng)
    ref = c.run(rho_bits=rho_bits)
    res = pm.run_sharded_ceremony(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table,
        rho_bits=rho_bits, ceremony_id="northstar-oracle",
    )
    return (
        np.array_equal(np.asarray(ref["master"]), np.asarray(res["master"]))
        and np.array_equal(
            np.asarray(ref["final_shares"]), np.asarray(res["final_shares"])
        )
        and bool(np.asarray(ref["ok"]).all()) == bool(np.asarray(res["ok"]).all())
    )


def run(args) -> dict:
    import jax
    import numpy as np

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.parallel import mesh as pm
    from dkg_tpu.utils import obslog

    platform = jax.default_backend()
    mesh = pm.make_mesh(args.ndev)
    rng = random.Random(0x4096)
    c = ce.BatchedCeremony(args.curve, args.n, args.t, b"north-star", rng)

    def one() -> dict:
        return pm.run_sharded_ceremony(
            c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table,
            rho_bits=args.rho_bits, ceremony_id="northstar",
        )

    t0 = time.perf_counter()
    res = one()
    np.asarray(res["master"])
    cold = time.perf_counter() - t0
    assert bool(np.asarray(res["ok"]).all()), "north-star batch check failed"
    t0 = time.perf_counter()
    res = one()
    np.asarray(res["master"])
    warm = time.perf_counter() - t0

    # bit-exactness oracle: at the measured shape when it is small
    # enough to run the unsharded engine too, else at the pinned small
    # shape (the subprocess tests pin (16,5) and (64,21) every tier run)
    bx_n, bx_t = (args.n, args.t) if args.n <= 64 else (16, 5)
    bit_exact = _bit_exact(args.curve, bx_n, bx_t, args.rho_bits, mesh)

    scale = _pair_cost(TARGET["n"], TARGET["t"]) / _pair_cost(args.n, args.t)
    cp = obslog.critical_path(res["events"])
    report = {
        "bench": "northstar",
        "target": dict(TARGET),
        "curve": args.curve,
        "n": args.n,
        "t": args.t,
        "mesh_shape": list(res["mesh_shape"]),
        "n_devices": res["n_devices"],
        "platform": platform,
        "wall_s": round(warm, 3),
        "cold_s": round(cold, 3),
        "phases_s": {k: round(v, 3) for k, v in res["phases_s"].items()},
        "pairs_per_s": round(args.n * (args.n - 1) / max(warm, 1e-9), 1),
        "bit_exact_vs_unsharded": bool(bit_exact),
        "bit_exact_shape": [bx_n, bx_t],
        "extrapolated_n4096_s": round(warm * scale, 3),
        "on_budget": bool(
            warm * scale < TARGET["budget_s"] * TARGET["chips"] / args.ndev
        ),
        # per-shard straggler attribution, the same decomposition the
        # networked path gets (obslog.critical_path over the sharded
        # round_head/publish/round_tail events)
        "critical_path": [
            {
                "round": e["round"],
                "barrier_s": round(e["barrier_s"], 4),
                "straggler": e["straggler"],
                "compute_s": round(e["compute_s"], 4),
                "transport_s": round(e["transport_s"], 4),
            }
            for e in cp
        ],
    }
    return report


def _next_round(root: pathlib.Path) -> int:
    rounds = []
    for p in root.glob("NORTHSTAR_r*.json"):
        try:
            rounds.append(int(p.stem.split("_r")[-1]))
        except ValueError:
            continue
    return max(rounds, default=0) + 1


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--t", type=int, default=85)
    ap.add_argument("--ndev", type=int, default=8)
    ap.add_argument("--rho-bits", type=int, default=128)
    ap.add_argument(
        "--platform",
        choices=("cpu", "ambient"),
        default="cpu",
        help="cpu re-execs onto a host-count-forced CPU mesh; "
        "ambient keeps the attached accelerator",
    )
    ap.add_argument(
        "--out",
        default=None,
        help="artifact path (default: NORTHSTAR_r<next>.json at repo root)",
    )
    args = ap.parse_args()

    report = run(args)
    root = pathlib.Path(__file__).resolve().parent.parent
    out = (
        pathlib.Path(args.out)
        if args.out
        else root / f"NORTHSTAR_r{_next_round(root):02d}.json"
    )
    with open(out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))


if __name__ == "__main__":
    main()
