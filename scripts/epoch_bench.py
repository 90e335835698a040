"""Epoch-operation benchmark: refresh throughput + reshare wall-clock.

Measures the SERVICE lane of the epoch subsystem
(:mod:`dkg_tpu.epoch.inprocess` — the batched device algebra the
scheduler's :meth:`~dkg_tpu.service.scheduler.CeremonyScheduler.refresh`
/ ``reshare`` methods run), because that lane is the one with a stable,
gateable cost: one ``eval_many`` dispatch per op, no channel timeouts or
thread scheduling in the measurement.  The networked
:class:`~dkg_tpu.epoch.EpochManager` path rides the same kernels plus
sealing, which BENCH/FLEET rounds already gate.

Protocol, per round:

* build an (n, t) base sharing from a seeded polynomial (no ceremony —
  the bench isolates epoch cost);
* warm up one refresh + one reshare (compiles persist in the JAX
  compilation cache);
* time ``--refreshes`` sequential proactive refreshes (each feeds the
  next, like a real proactivization schedule) -> ``refreshes_per_s``;
* time ONE reshare to ``(--n-new, --t-new)`` -> ``reshare_wall_s``;
* assert the secret is bit-invariant through every epoch against the
  poly.host Lagrange oracle (``secret_invariant`` in the report — the
  bench fails loudly rather than publish rates for wrong math).

Writes one JSON report (default ``EPOCH_r01.json``);
``scripts/perf_regress.py`` diffs the newest two rounds and fails on a
>20% ``refreshes_per_s`` drop (reshare wall-clock is informational).

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/epoch_bench.py --out EPOCH_r01.json
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import random
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from dkg_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

from dkg_tpu.epoch import inprocess  # noqa: E402
from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.poly import host as ph  # noqa: E402
from dkg_tpu.utils.metrics import REGISTRY  # noqa: E402


def base_sharing(fs, n: int, t: int, rng) -> tuple[int, list[int]]:
    """A seeded (n, t) Shamir sharing: (secret, shares at 1..n)."""
    coeffs = [fs.rand_int(rng) for _ in range(t + 1)]

    def at(x: int) -> int:
        acc = 0
        for c in reversed(coeffs):
            acc = (acc * x + c) % fs.modulus
        return acc

    return coeffs[0], [at(i) for i in range(1, n + 1)]


def reconstruct(fs, shares: list[int], indices: list[int]) -> int:
    """poly.host Lagrange-at-zero oracle over the given share subset."""
    return ph.lagrange_interpolation(fs, 0, shares, indices)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--curve", default="ristretto255")
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--t", type=int, default=3)
    ap.add_argument("--n-new", type=int, default=None, help="reshare committee size (default n)")
    ap.add_argument("--t-new", type=int, default=None, help="reshare threshold (default t)")
    ap.add_argument("--refreshes", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default="EPOCH_r01.json")
    args = ap.parse_args(argv)
    n, t = args.n, args.t
    n_new = args.n_new if args.n_new is not None else n
    t_new = args.t_new if args.t_new is not None else t

    fs = gh.ALL_GROUPS[args.curve].scalar_field
    rng = random.Random(args.seed)
    secret, shares = base_sharing(fs, n, t, rng)
    print(
        f"epoch_bench: ({n},{t}) -> ({n_new},{t_new}) on {args.curve}, "
        f"{args.refreshes} refreshes, platform {jax.default_backend()}",
        flush=True,
    )

    t0 = time.perf_counter()
    inprocess.refresh_shares(fs, n, t, shares, random.Random(args.seed + 1))
    inprocess.reshare_shares(
        fs, n, t, shares, n_new, t_new, random.Random(args.seed + 2)
    )
    warm_s = time.perf_counter() - t0
    print(f"epoch_bench: warmup {warm_s:.1f}s", flush=True)

    ok = True
    t0 = time.perf_counter()
    for _ in range(args.refreshes):
        shares = inprocess.refresh_shares(fs, n, t, shares, rng)
    refresh_wall = time.perf_counter() - t0
    ok &= reconstruct(fs, shares[: t + 1], list(range(1, t + 2))) == secret

    t0 = time.perf_counter()
    new_shares = inprocess.reshare_shares(fs, n, t, shares, n_new, t_new, rng)
    reshare_wall = time.perf_counter() - t0
    ok &= (
        reconstruct(fs, new_shares[: t_new + 1], list(range(1, t_new + 2)))
        == secret
    )

    report = {
        "bench": "epoch",
        "platform": jax.default_backend(),
        "nproc": os.cpu_count(),
        "curve": args.curve,
        "n": n,
        "t": t,
        "n_new": n_new,
        "t_new": t_new,
        "refreshes": args.refreshes,
        "seed": args.seed,
        "warmup_s": round(warm_s, 3),
        "refresh_wall_s": round(refresh_wall, 3),
        "refreshes_per_s": round(args.refreshes / refresh_wall, 3),
        "reshare_wall_s": round(reshare_wall, 3),
        "secret_invariant": bool(ok),
        "metrics": REGISTRY.snapshot(),
    }
    print(
        f"epoch_bench: {report['refreshes_per_s']} refreshes/s, reshare "
        f"{report['reshare_wall_s']}s, secret_invariant={report['secret_invariant']}",
        flush=True,
    )
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(f"epoch_bench: wrote {args.out}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
