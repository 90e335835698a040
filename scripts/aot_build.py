#!/usr/bin/env python
"""Pre-bake the AOT executable store for a bucket set.

Compiles-and-serializes every program a serving worker needs — the
deal/verify/finalise ladder per (bucket, convoy width) plus the steady
sign lane's folded ladder per rung — into ``DKG_TPU_AOT_DIR`` (see
dkg_tpu.service.aot).  The bake IS the serving path: it runs throwaway
warmup convoys and sign rungs through the engine's AOT dispatch seams,
so the persisted keys/specs agree with production bit-for-bit by
construction.  A fleet worker process started against the baked store
deserializes in seconds instead of recompiling for minutes
(FLEET_r01 warmup: 222.6s).

The default bucket set mirrors ``scripts/fleet_bench.py``'s MIX; pass
``--shapes n:t,n:t,...`` to bake others.

``--validate`` runs the compile-only TPU leg afterwards: it invokes
``scripts/aot_lab.py`` (in a subprocess, chip-less
``topologies.get_topology_desc`` compile) for each north-star shape so
a layout/OOM regression in the real TPU compiler is caught in the same
pass that bakes the CPU store.

Run (CPU):
    JAX_PLATFORMS=cpu DKG_TPU_AOT_DIR=/tmp/dkg_tpu_aot \
        python scripts/aot_build.py --out AOT_BUILD.json
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from dkg_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

import numpy as np  # noqa: E402

from dkg_tpu.service import aot, buckets, engine  # noqa: E402
from dkg_tpu.sign import cache as sign_cache  # noqa: E402
from dkg_tpu.sign import hash_to_curve_batch  # noqa: E402

#: (n, t) shapes whose buckets the default bake covers — the
#: fleet_bench MIX buckets.
DEFAULT_SHAPES = ((16, 5), (32, 8), (64, 16))


def bake_ceremonies(curve, shapes, widths, rho_bits) -> list[dict]:
    """One throwaway warmup convoy per (bucket, width): the engine's
    dispatch seams compile + persist each program on the miss."""
    runtime = engine.WarmRuntime()
    done = []
    seen = set()
    for n, t in shapes:
        req = engine.CeremonyRequest(curve, n, t, seed=0, rho_bits=rho_bits)
        b = req.bucket()
        if b in seen:
            continue
        seen.add(b)
        cap = buckets.width_cap(b)
        for w in sorted({min(w, cap) for w in widths}, reverse=True):
            t0 = time.perf_counter()
            runtime.warmup(req, widths=(w,))
            dt = time.perf_counter() - t0
            print(
                f"aot_build: bucket ({b.n},{b.t}) width {w}: {dt:.1f}s",
                flush=True,
            )
            done.append(
                {"bucket": [b.n, b.t], "width": w, "seconds": round(dt, 2)}
            )
    return done


def bake_sign_rungs(curve, rungs) -> list[dict]:
    """One folded ladder per rung, over dummy rung-shaped rows (the
    executable is keyed by shape, not values)."""
    limbs = sign_cache.sigma_limb_count(curve)
    done = []
    for rung in sorted(set(rungs), reverse=True):
        t0 = time.perf_counter()
        _, h_dev = hash_to_curve_batch(
            curve, [b"aot-bake-%d" % i for i in range(rung)]
        )
        rows = np.zeros((rung, limbs), np.uint32)
        rows[:, 0] = 1  # sigma=1: a valid scalar, values are irrelevant
        np.asarray(engine.aot_sign_folded(curve, rows, h_dev))
        dt = time.perf_counter() - t0
        print(f"aot_build: sign rung {rung}: {dt:.1f}s", flush=True)
        done.append({"rung": rung, "seconds": round(dt, 2)})
    return done


def validate_leg(shapes_nt, curve) -> list[dict]:
    """Compile-only AOT validation against the real TPU compiler:
    scripts/aot_lab.py per shape, in a subprocess (it owns its
    backend-assumption env)."""
    lab = pathlib.Path(__file__).resolve().parent / "aot_lab.py"
    out = []
    for n, t in shapes_nt:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, str(lab), str(n), str(t), curve],
            capture_output=True, text=True, env=env, check=False,
        )
        recs = []
        for line in proc.stdout.splitlines():
            try:
                recs.append(json.loads(line))
            except json.JSONDecodeError:
                continue
        out.append(
            {
                "n": n,
                "t": t,
                "returncode": proc.returncode,
                "phases": recs,
                "ok": proc.returncode == 0
                and bool(recs)
                and all(r.get("ok") for r in recs),
            }
        )
        print(
            f"aot_build: validate ({n},{t}): "
            f"{'ok' if out[-1]['ok'] else 'FAILED'}",
            flush=True,
        )
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument(
        "--shapes", default=None,
        help="comma-separated n:t list (default: the fleet_bench MIX buckets)",
    )
    ap.add_argument("--batch-max", type=int, default=8)
    ap.add_argument(
        "--widths", default=None,
        help="comma-separated convoy widths (default: the width ladder "
        "up to batch-max, plus 1)",
    )
    ap.add_argument("--rho-bits", type=int, default=64)
    ap.add_argument(
        "--sign-rungs", default=None,
        help="comma-separated sign rung sizes (default: buckets.SIGN_RUNGS); "
        "'none' skips the sign bake",
    )
    ap.add_argument(
        "--validate", action="store_true",
        help="also run the compile-only TPU validation leg (aot_lab.py) "
        "per shape",
    )
    ap.add_argument("--out", default=None, help="JSON report path")
    args = ap.parse_args(argv)

    if not aot.enabled():
        print(
            "aot_build: DKG_TPU_AOT_DIR is not set — nothing to bake into",
            file=sys.stderr,
        )
        return 2
    shapes = (
        tuple(
            tuple(int(x) for x in s.split(":")) for s in args.shapes.split(",")
        )
        if args.shapes
        else DEFAULT_SHAPES
    )
    if args.widths:
        widths = tuple(int(w) for w in args.widths.split(","))
    else:
        widths = tuple(
            w for w in buckets.WIDTHS if w <= args.batch_max
        ) or (1,)
        widths = tuple(sorted(set(widths) | {1}, reverse=True))
    t0 = time.perf_counter()
    report = {
        "bench": "aot_build",
        "platform": jax.default_backend(),
        "curve": args.curve,
        "store": aot.cache_dir(),
        "rho_bits": args.rho_bits,
        "ceremony_programs": bake_ceremonies(
            args.curve, shapes, widths, args.rho_bits
        ),
    }
    if args.sign_rungs != "none":
        rungs = (
            tuple(int(r) for r in args.sign_rungs.split(","))
            if args.sign_rungs
            else buckets.SIGN_RUNGS
        )
        report["sign_rungs"] = bake_sign_rungs(args.curve, rungs)
    report["bake_s"] = round(time.perf_counter() - t0, 1)
    report["aot"] = aot.stats()
    if args.validate:
        report["validate"] = validate_leg(shapes, args.curve)
    print(
        f"aot_build: {report['aot']['builds']} built, "
        f"{report['aot']['disk_loads']} loaded, "
        f"{report['aot']['resident']} resident in {report['bake_s']}s",
        flush=True,
    )
    if args.out:
        pathlib.Path(args.out).write_text(
            json.dumps(report, indent=1) + "\n"
        )
        print(f"aot_build: wrote {args.out}", flush=True)
    ok = all(
        v.get("ok", True) for v in report.get("validate", [])
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
