"""Threshold-signing benchmark: partial-sign / verify / aggregate rates.

Measures the three stages of :mod:`dkg_tpu.sign` against a seeded
Shamir sharing (no ceremony — the bench isolates signing cost), per
curve and committee shape:

* ``partials_per_s`` — batched partial signatures through the one
  broadcast ladder (``sign.partial.partial_sign``), counted as B
  messages x (t+1) signers lanes per wall-second;
* ``proofs_per_s`` — DLEQ generation + the one-pass batch verification
  (``verify_partials``) over the same grid;
* ``signatures_per_s`` — Lagrange aggregation (one Pippenger MSM with
  the message batch leading) plus canonical encoding.

Every run first CHECKS the math: the aggregate of the first message
must equal ``secret * H(m)`` by the host big-int oracle — the bench
fails loudly rather than publish rates for wrong signatures.

``--steady N`` adds the steady-state mode: a real scheduler's sign lane
(convoy batching + SignCache + the folded fast path, docs/signing.md
"Steady-state lane") serves N messages after warmup and the report
gains a ``steady_state`` block with the headline ``signatures_per_s``
— every signature oracle-checked, a sample cross-checked against the
partial-grid path.  The embedded ``metrics`` snapshot carries the
lane's ``sign_seconds`` histogram for ``scripts/slo_gate.py``.

Writes one JSON report (default ``SIGN_r01.json``);
``scripts/perf_regress.py`` diffs the newest two rounds per
(curve, n, messages) shape and fails on a >20% ``partials_per_s`` drop
(verify and aggregate rates are informational — they carry host-side
Fiat-Shamir hashing and single-dispatch MSM noise), and gates
``steady_state.signatures_per_s`` the same way once two rounds carry
the block.

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/sign_bench.py --steady 2000 \\
        --out SIGN_r02.json
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

from dkg_tpu import sign as signing  # noqa: E402
from dkg_tpu.groups import device as gd  # noqa: E402
from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.utils import runtimeobs  # noqa: E402
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


def bench_shape(curve: str, n: int, t: int, messages: int, seed: int) -> dict:
    group = gh.ALL_GROUPS[curve]
    fs = group.scalar_field
    rng = random.Random(seed)
    secret, shares = base_sharing(fs, n, t, rng)
    indices = list(range(1, t + 2))
    signer_shares = shares[: t + 1]
    msgs = [f"sign-bench|{curve}|{n}|{i}".encode() for i in range(messages)]

    # warmup: compile the ladder/MSM shapes (persisted in the JAX cache)
    # at the FULL measured batch — warming B=1 left the B-message hash
    # and (B, t+1) grid compiles inside the timed sections, so early
    # rounds' rates were compile-contaminated
    h_warm, _ = signing.hash_to_curve_batch(curve, msgs)
    ps_warm = signing.partial_sign(
        curve, signer_shares, indices, h_warm, rng=rng, prove=True
    )
    signing.verify_partials(ps_warm)
    signing.aggregate(ps_warm)

    t0 = time.perf_counter()
    h_points, _ = signing.hash_to_curve_batch(curve, msgs)
    hash_wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    ps = signing.partial_sign(curve, signer_shares, indices, h_points)
    partial_wall = time.perf_counter() - t0

    t0 = time.perf_counter()
    ps = signing.partial_sign(
        curve, signer_shares, indices, h_points, rng=rng, prove=True
    )
    ok = signing.verify_partials(ps)
    verify_wall = time.perf_counter() - t0
    correct = bool(ok.all())

    t0 = time.perf_counter()
    sigs = signing.signature_encode(curve, signing.aggregate(ps))
    agg_wall = time.perf_counter() - t0

    # the oracle check: sig_0 == secret * H(m_0), host big ints
    correct &= sigs[0] == group.encode(
        group.scalar_mul_vartime(secret, h_points[0])
    )

    lanes = messages * (t + 1)
    return {
        "curve": curve,
        "n": n,
        "t": t,
        "messages": messages,
        "signers": t + 1,
        "hash_wall_s": round(hash_wall, 3),
        "partial_wall_s": round(partial_wall, 3),
        "partials_per_s": round(lanes / partial_wall, 1),
        "verify_wall_s": round(verify_wall, 3),
        "proofs_per_s": round(lanes / verify_wall, 1),
        "aggregate_wall_s": round(agg_wall, 3),
        "signatures_per_s": round(messages / agg_wall, 1),
        "correct": correct,
    }


def bench_steady(
    curve: str, n: int, t: int, total: int, batch: int, seed: int
) -> dict:
    """Steady-state mode: a real scheduler's sign lane under sustained
    ``prove=False`` traffic — the service's warm signing throughput.

    Drives ``total`` messages through ``sign_submit``/``sign_wait`` in
    ``batch``-message tickets with a small in-flight window (so the
    lane overlaps hashing/ladder work across convoys without letting
    queue wait dominate the ``sign_seconds`` histogram), after warming
    the rung shapes.  Before publishing a rate, EVERY signature is
    checked byte-identical to the host ``secret * H(m)`` oracle, and a
    sample is re-signed through the partial-grid + MSM path (the
    pre-lane single-call leg) — the folded fast path is not allowed to
    be fast and wrong.
    """
    import collections

    import numpy as np

    from dkg_tpu.fields import host as fh
    from dkg_tpu.service.engine import CeremonyOutcome
    from dkg_tpu.service.scheduler import CeremonyScheduler

    group = gh.ALL_GROUPS[curve]
    fs = group.scalar_field
    rng = random.Random(seed)
    secret, shares = base_sharing(fs, n, t, rng)
    msgs = [f"sign-steady|{curve}|{n}|{i}".encode() for i in range(total)]

    sch = CeremonyScheduler(
        concurrency=1, queue_depth=4, batch_max=1, runtime=object(),
        sign_flush_ms=5, sign_batch_max=batch,
    )
    try:
        out = CeremonyOutcome(
            ceremony_id="steady", status="done", curve=curve, n=n, t=t,
            master=group.encode(
                group.scalar_mul_vartime(secret, group.generator())
            ),
            qualified=(True,) * n,
            final_shares=np.asarray(fh.encode(fs, shares)),
        )
        with sch._cond:
            sch._record(out)

        # warm the measured rung shapes (and the fold/λ caches), not
        # counted: a full-width ticket plus a (batch-1)-wide one so the
        # tail rungs (16/4/2/1 under the default ladder) compile here
        # rather than inside the timed window when total % batch != 0
        warm_widths = [batch, batch, max(batch - 1, 1)]
        wi = 0
        for w in warm_widths:
            warm = [b"sign-steady-warm|%d" % i for i in range(wi, wi + w)]
            wi += w
            sch.sign("steady", warm, prove=False, seed=seed)

        window = collections.deque()
        sigs: list[bytes] = []
        t0 = time.perf_counter()
        for a in range(0, total, batch):
            window.append(
                sch.sign_submit(
                    "steady", msgs[a : a + batch], prove=False, seed=seed
                )
            )
            while len(window) >= 3:
                sigs.extend(sch.sign_wait(window.popleft()))
        while window:
            sigs.extend(sch.sign_wait(window.popleft()))
        wall = time.perf_counter() - t0

        # byte-identity leg 1: EVERY signature against the host oracle
        correct = len(sigs) == total
        for m, sig in zip(msgs, sigs):
            correct &= sig == group.encode(
                group.scalar_mul_vartime(
                    secret, signing.hash_to_curve_host(group, m)
                )
            )
        # byte-identity leg 2: a sample through the partial-grid + MSM
        # path (tamper=identity routes the lane to the grid leg)
        grid_n = min(4, total)
        grid = sch.sign(
            "steady", msgs[:grid_n], prove=False, seed=seed,
            tamper=lambda ps: ps,
        )
        correct &= grid == sigs[:grid_n]
        # byte-identity leg 3: the device-sharded folded lane.  The
        # measured window ran whatever DKG_TPU_SIGN_MESH's auto logic
        # picked (recorded below); here a sample batch re-signs with
        # the mesh FORCEd so the sharded ladder's bytes are pinned
        # against the measured lane (and thereby the host oracle) in
        # every published round, even on boxes where auto declines
        from dkg_tpu.parallel import signmesh

        mesh_auto = signmesh.sign_mesh()
        mesh_n = min(batch, total)
        saved = os.environ.get("DKG_TPU_SIGN_MESH")
        os.environ["DKG_TPU_SIGN_MESH"] = "force"
        try:
            forced = signmesh.sign_mesh()
            mesh_checked = 0
            if forced is not None:
                meshed = sch.sign(
                    "steady", msgs[:mesh_n], prove=False, seed=seed
                )
                correct &= meshed == sigs[:mesh_n]
                mesh_checked = mesh_n
        finally:
            if saved is None:
                os.environ.pop("DKG_TPU_SIGN_MESH", None)
            else:
                os.environ["DKG_TPU_SIGN_MESH"] = saved
    finally:
        sch.close()

    return {
        "curve": curve,
        "n": n,
        "t": t,
        "messages": total,
        "batch": batch,
        "warmup_messages": wi,
        "wall_s": round(wall, 3),
        "signatures_per_s": round(total / wall, 1),
        "oracle_checked": total,
        "grid_checked": grid_n,
        "sign_mesh": {
            "knob": saved,
            "measured_devices": (
                int(mesh_auto.devices.size) if mesh_auto is not None else 0
            ),
            "forced_devices": (
                int(forced.devices.size) if forced is not None else 0
            ),
            "forced_checked": mesh_checked,
        },
        "correct": correct,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--curves", default="secp256k1,bls12_381_g1",
        help="comma-separated device curve names",
    )
    ap.add_argument(
        "--shapes", default="64,256",
        help="comma-separated committee sizes (t = (n-1)//3)",
    )
    ap.add_argument("--messages", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument(
        "--steady", type=int, default=0, metavar="N",
        help="also drive N messages through the scheduler's sign lane "
        "and report steady-state signatures_per_s (0 = off)",
    )
    ap.add_argument(
        "--steady-batch", type=int, default=64,
        help="ticket size (= convoy cap) for --steady",
    )
    ap.add_argument(
        "--steady-n", type=int, default=64,
        help="committee size for --steady (t = (n-1)//3); runs on the "
        "first --curves entry",
    )
    ap.add_argument("--out", default="SIGN_r01.json")
    args = ap.parse_args(argv)

    # force=True: the bench opts into compile/cache telemetry without
    # the knob (DKG_TPU_RUNTIMEOBS=off still wins)
    runtimeobs.install(force=True)
    shapes = []
    ok = True
    for curve in args.curves.split(","):
        for n in (int(v) for v in args.shapes.split(",")):
            t = (n - 1) // 3
            print(
                f"sign_bench: {curve} n={n} t={t} B={args.messages} "
                f"on {jax.default_backend()}",
                flush=True,
            )
            shape = bench_shape(curve, n, t, args.messages, args.seed)
            ok &= shape["correct"]
            print(
                f"sign_bench: {shape['partials_per_s']} partials/s, "
                f"{shape['proofs_per_s']} proofs/s, "
                f"{shape['signatures_per_s']} signatures/s, "
                f"correct={shape['correct']}",
                flush=True,
            )
            shapes.append(shape)

    steady = None
    if args.steady > 0:
        curve = args.curves.split(",")[0]
        n = args.steady_n
        t = (n - 1) // 3
        print(
            f"sign_bench: steady {curve} n={n} t={t} "
            f"messages={args.steady} batch={args.steady_batch}",
            flush=True,
        )
        steady = bench_steady(
            curve, n, t, args.steady, args.steady_batch, args.seed
        )
        ok &= steady["correct"]
        print(
            f"sign_bench: steady {steady['signatures_per_s']} "
            f"signatures/s over {steady['messages']} messages "
            f"(oracle_checked={steady['oracle_checked']}, "
            f"grid_checked={steady['grid_checked']}, "
            f"correct={steady['correct']})",
            flush=True,
        )

    report = {
        "bench": "sign",
        "platform": jax.default_backend(),
        # kernel tier the measured programs traced with — perf_regress
        # refuses to diff rounds across a fused/XLA flip (different
        # programs, not a regression)
        "pallas": bool(gd.fused_kernels_active()),
        "nproc": os.cpu_count(),
        "messages": args.messages,
        "seed": args.seed,
        "shapes": shapes,
        # the lane's sign_seconds/sign_flush_total land here: this is
        # the histogram scripts/slo_gate.py judges for SIGN rounds
        "metrics": REGISTRY.snapshot(),
        "runtime": runtimeobs.snapshot(),
    }
    if steady is not None:
        report["steady_state"] = steady
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(f"sign_bench: wrote {args.out}", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
