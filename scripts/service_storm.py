"""Service-layer chaos storm: prove the blast radius stays contained.

scripts/fleet_bench.py measures the service's THROUGHPUT; this script
measures its RESILIENCE.  Three legs, one seeded
:class:`~dkg_tpu.service.faultsvc.ServiceFaultPlan`, one JSON verdict
(default ``SVCSTORM_r01.json``) that scripts/perf_regress.py gates as
FLOORS — survival, bit-identity, typed poisoning, and blame accuracy
must all be perfect.

* **convoy leg** — the same ~200-request mixed workload runs twice, in
  identical submit order (so every request gets the SAME ceremony id in
  both legs: ``engine.request_id`` hashes shape+seed+seq, never the
  tag).  The first pass is fault-free and records every master; the
  second runs under a fault plan mixing deterministic per-request
  poison (~5%), transient engine faults, slow starts, and one worker
  crash.  Verdict: every healthy request completes ``done`` with a
  master BIT-IDENTICAL to the fault-free pass, every tagged request
  ends ``poisoned`` with a typed ``PoisonedRequest`` error, and the set
  the scheduler blamed equals the plan's ground truth exactly.
* **recovery leg** — durable ceremonies are journalled, the WAL tail is
  corrupted (:func:`faultsvc.corrupt_journal`), and a fresh scheduler
  must re-serve every terminal outcome bit-identically off the intact
  prefix.  A synthetic crash-looping pending record (``max_replays``
  replay stamps, exactly what a kill -9 loop leaves behind) must come
  back ``poisoned`` instead of being re-queued.
* **sign leg** — a Byzantine signer forges one DLEQ response inside a
  t+1 quorum signing under a ceremony the convoy leg actually ran.
  Verdict: direct ``rlc_verify`` blames the exact forged (message,
  signer) cell within its logarithmic pass bound, the scheduler
  quarantines exactly the forging signer, and the substitute quorum's
  signature bytes equal the honest quorum's (Lagrange-at-zero makes
  substitution invisible).

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/service_storm.py --out SVCSTORM_r01.json
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import pathlib
import random
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

import jax  # noqa: E402

from dkg_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

from dkg_tpu import sign as signing  # noqa: E402
from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.service import buckets, engine, faultsvc  # noqa: E402
from dkg_tpu.service.durable import ServiceJournal  # noqa: E402
from dkg_tpu.service.scheduler import CeremonyScheduler  # noqa: E402
from dkg_tpu.sign.verify import rlc_verify  # noqa: E402
from dkg_tpu.utils.metrics import REGISTRY  # noqa: E402

# shape mix: small-heavy like real service traffic, two buckets so the
# storm exercises multi-bucket convoy keys without paying the (64,16)
# compile; four of five requests land on bucket (16,5), the rest on
# (32,8) via n=24
SHAPES = ((16, 5), (16, 5), (16, 5), (16, 5), (24, 8))

# poisons land on the dominant shape only: bisection then exercises the
# full width ladder where the traffic is, and the minority bucket never
# needs its sub-primary widths loaded — each (bucket, width) program
# costs ~40 s of single-core wall clock to load even from a warm
# compile cache, and the minority ladder would buy no extra coverage
# (whole-convoy transient retries and crash re-queues re-run at the
# original width, and unit tests already pin bisection per se)
POISON_SHAPE = (16, 5)


def build_workload(curve: str, total: int, rho_bits: int, seed: int):
    """``total`` uniquely-tagged seeded requests, shuffled like arriving
    traffic.  Tags are the fault plan's handle on a request and never
    enter the ceremony id, so both legs see identical ids."""
    reqs = []
    for i in range(total):
        n, t = SHAPES[i % len(SHAPES)]
        reqs.append(
            engine.CeremonyRequest(
                curve, n, t,
                seed=seed * 1_000_000 + i,
                rho_bits=rho_bits,
                tag=f"req-{i}",
            )
        )
    random.Random(seed).shuffle(reqs)
    return reqs


def warmup(runtime, reqs, batch_max: int, ladder_buckets) -> float:
    """Load every (bucket, width) program the storm can reach.  Only
    POISONABLE buckets need the full bisection ladder (bisection halves
    a ladder width onto a smaller ladder width); fault-free buckets run
    pure primary-width convoys — their request counts are multiples of
    the width, and transient retries / crash re-queues re-run at the
    original width — so warming their ladder would only burn the
    single-core wall-clock budget on programs never dispatched."""
    t0 = time.perf_counter()
    by_bucket = {}
    for r in reqs:
        by_bucket.setdefault(r.bucket(), r)
    for b, req in sorted(by_bucket.items(), key=lambda kv: kv[0].n):
        cap = min(batch_max, buckets.width_cap(b))
        widths = (
            [w for w in buckets.WIDTHS if w <= cap]
            if b in ladder_buckets
            else [next(w for w in buckets.WIDTHS if w <= cap)]
        )
        for w in widths:
            print(f"service_storm: warmup bucket ({b.n},{b.t}) width {w}", flush=True)
            runtime.warmup(req, widths=(w,))
    return time.perf_counter() - t0


def run_leg(reqs, runtime, concurrency, batch_max, fault_plan=None):
    """Submit the whole workload, drain it, return {cid: outcome} plus
    the submit-order cid list (identical across legs by construction)."""
    sch = CeremonyScheduler(
        concurrency=concurrency,
        queue_depth=len(reqs),
        batch_max=batch_max,
        runtime=runtime,
        fault_plan=fault_plan,
    )
    cids = [sch.submit(r) for r in reqs]
    outs = {c: sch.result(c) for c in cids}
    return sch, cids, outs


def convoy_leg(args, runtime, reqs):
    """Fault-free reference pass, then the storm pass, then the
    bit-compare verdict.  Returns the (still-open) storm scheduler so
    the sign leg can sign under a ceremony it actually ran."""
    print(f"service_storm: clean leg ({len(reqs)} requests)", flush=True)
    sch0, cids, clean = run_leg(
        reqs, runtime, args.concurrency, args.batch_max
    )
    sch0.close()
    not_done = [c for c in cids if clean[c].status != "done"]
    if not_done:
        raise SystemExit(
            f"service_storm: fault-free leg failed {len(not_done)} "
            f"request(s) — box problem, not a resilience verdict"
        )

    rng = random.Random(args.seed + 1)
    poisonable = [r.tag for r in reqs if (r.n, r.t) == POISON_SHAPE]
    poison_tags = rng.sample(poisonable, k=args.poison)
    plan = (
        faultsvc.ServiceFaultPlan(seed=args.seed)
        .poison(*poison_tags)
        .transient(times=2)
        .slow(0.05, times=2)
        .crash_worker(at_start=7)
    )
    print(
        f"service_storm: storm leg ({args.poison} poisoned, 2 transient, "
        "2 slow, 1 worker crash)",
        flush=True,
    )
    REGISTRY.reset()
    sch, cids2, stormy = run_leg(
        reqs, runtime, args.concurrency, args.batch_max, fault_plan=plan
    )
    assert cids2 == cids, "cids must be submit-order stable across legs"

    truth = {
        cid for cid, r in zip(cids, reqs) if r.tag in plan.poisoned_tags
    }
    blamed = {cid for cid in cids if stormy[cid].status == "poisoned"}
    healthy = [cid for cid in cids if cid not in truth]
    healthy_done = [c for c in healthy if stormy[c].status == "done"]
    identical = [
        c for c in healthy_done if stormy[c].master == clean[c].master
    ]
    typed = [
        c
        for c in blamed
        if (stormy[c].error or "").startswith("PoisonedRequest")
    ]
    counters = REGISTRY.snapshot()["counters"]
    leg = {
        "requests": len(reqs),
        "healthy": len(healthy),
        "healthy_done": len(healthy_done),
        "healthy_bit_identical": len(identical),
        "poisoned": len(blamed),
        "poisoned_typed": len(typed),
        "survival_rate": len(healthy_done) / max(1, len(healthy)),
        "blame_accuracy": (
            len(truth & blamed) / len(truth | blamed)
            if truth | blamed
            else 1.0
        ),
        "bisections": counters.get("service_convoy_bisections_total", 0),
        "retries": counters.get("service_retries_total", 0),
        "worker_restarts": counters.get(
            "service_worker_restarts_total", 0
        ),
        "requeued": counters.get("service_requeued_total", 0),
    }
    print(f"service_storm: convoy {leg}", flush=True)
    held = [
        c
        for c, r in zip(cids, reqs)
        if c in healthy_done and (r.n, r.t) == (16, 5)
    ]
    return leg, plan, sch, held


def recovery_leg(args, runtime) -> dict:
    """Journal durable ceremonies, corrupt the WAL tail, and verify the
    next recovery re-serves everything off the intact prefix; then the
    crash-loop guard on a synthetic replay-stamped pending record."""
    curve = args.curve
    tmp = pathlib.Path(tempfile.mkdtemp(prefix="svcstorm-wal-"))
    wal_a = tmp / "a"
    reqs = [
        engine.CeremonyRequest(
            curve, 16, 5,
            seed=args.seed * 2_000_000 + i,
            rho_bits=args.rho_bits,
            durable=True,
        )
        for i in range(4)
    ]
    with CeremonyScheduler(
        concurrency=2, queue_depth=8, batch_max=4,
        runtime=runtime, wal_dir=str(wal_a),
    ) as sch:
        cids = [sch.submit(r) for r in reqs]
        outs = {c: sch.result(c) for c in cids}
    wal_path = faultsvc.corrupt_journal(wal_a, seed=args.seed)
    sch2 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        runtime=runtime, wal_dir=str(wal_a),
    )
    reserved = [
        c
        for c in cids
        if sch2.poll(c) == "done"
        and sch2.result(c).master == outs[c].master
    ]
    sch2.close()

    wal_b = tmp / "b"
    jreq = engine.CeremonyRequest(
        curve, 16, 5, seed=args.seed * 3_000_000, rho_bits=args.rho_bits,
        durable=True,
    )
    jcid = engine.request_id(jreq, 0)
    j = ServiceJournal(wal_b)
    j.record_request(jcid, 0, jreq)
    for count in range(1, 4):
        j.record_replay(jcid, count)
    sch3 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        runtime=runtime, wal_dir=str(wal_b), max_replays=3,
    )
    crash_loop_poisoned = sch3.poll(jcid) == "poisoned"
    crash_loop_error = sch3.result(jcid).error if crash_loop_poisoned else None
    sch3.close()
    leg = {
        "durable": len(cids),
        "corrupted_wal": wal_path,
        "terminal_reserved": len(reserved),
        "corrupt_tail_skipped": len(reserved) == len(cids),
        "crash_loop_poisoned": crash_loop_poisoned,
        "crash_loop_error": crash_loop_error,
    }
    print(f"service_storm: recovery {leg}", flush=True)
    return leg


def sign_leg(args, sch, held_cids) -> dict:
    """Byzantine signing under a convoy-leg ceremony: exact cell blame
    (direct rlc_verify), signer quarantine + invisible substitution
    (scheduler path)."""
    curve = args.curve
    group = gh.ALL_GROUPS[curve]
    fs = group.scalar_field
    q = fs.modulus
    msgs = [b"svcstorm message 0", b"svcstorm message 1"]

    # direct RLC blame on a host sharing with the SAME grid shape the
    # scheduler path uses (2 messages x 6 signers), so both share one
    # compiled program
    n, t = 16, 5
    rng = random.Random(args.seed + 2)
    coeffs = [fs.rand_int(rng) for _ in range(t + 1)]

    def horner(x: int) -> int:
        acc = 0
        for c in reversed(coeffs):
            acc = (acc * x + c) % q
        return acc

    indices = list(range(1, t + 2))
    h_points, _ = signing.hash_to_curve_batch(curve, msgs)
    ps = signing.partial_sign(
        curve,
        [horner(i) for i in indices],
        indices,
        h_points,
        rng=rng,
        prove=True,
    )
    cell = (1, 2)  # forge message 1's DLEQ response from signer column 2
    m = len(ps.indices)
    proofs = list(ps.proofs)
    p = proofs[cell[0] * m + cell[1]]
    proofs[cell[0] * m + cell[1]] = dataclasses.replace(
        p, response=(p.response + 1) % q
    )
    report = rlc_verify(
        dataclasses.replace(ps, proofs=proofs), rng=random.Random(args.seed)
    )

    # scheduler path: honest quorum, then a one-shot forger, then a
    # follow-up with the quarantine standing — all three must encode
    # identical bytes
    cid = held_cids[0]
    sigs0 = sch.sign(cid, msgs, seed=args.seed + 11)
    state = {"signer": None}

    def forge_once(grid):
        if state["signer"] is not None:
            return grid
        state["signer"] = grid.indices[1]
        gm = len(grid.indices)
        gp = list(grid.proofs)
        bad = gp[0 * gm + 1]
        gp[0 * gm + 1] = dataclasses.replace(
            bad, response=(bad.response + 1) % q
        )
        return dataclasses.replace(grid, proofs=gp)

    sigs1 = sch.sign(cid, msgs, seed=args.seed + 12, tamper=forge_once)
    sigs2 = sch.sign(cid, msgs, seed=args.seed + 13)
    quarantined = sorted(sch.quarantined(cid))
    leg = {
        "grid": report.grid,
        "byzantine_cell": list(cell),
        "blamed_cells": [list(c) for c in report.bad_cells],
        "blamed_cells_exact": report.bad_cells == (cell,),
        "passes": report.passes,
        "pass_bound": report.pass_bound(),
        "substitute_sig_bit_identical": sigs1 == sigs0 and sigs2 == sigs0,
        "quarantined": quarantined,
        "quarantined_exact": quarantined == [state["signer"]],
        "ceremony": cid,
    }
    print(f"service_storm: sign {leg}", flush=True)
    return leg


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ceremonies", type=int, default=200)
    ap.add_argument("--poison", type=int, default=10)
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--batch-max", type=int, default=8)
    ap.add_argument("--rho-bits", type=int, default=64)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--out", default="SVCSTORM_r01.json")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    reqs = build_workload(args.curve, args.ceremonies, args.rho_bits, args.seed)
    runtime = engine.WarmRuntime()
    print(
        f"service_storm: {len(reqs)} x {args.curve} requests, "
        f"platform {jax.default_backend()}",
        flush=True,
    )
    ladder_buckets = {
        r.bucket() for r in reqs if (r.n, r.t) == POISON_SHAPE
    }
    warm_s = warmup(runtime, reqs, args.batch_max, ladder_buckets)
    print(f"service_storm: warmup {warm_s:.1f}s", flush=True)

    convoy, plan, sch, held_cids = convoy_leg(args, runtime, reqs)
    try:
        sign = sign_leg(args, sch, held_cids)
    finally:
        sch.close()
    recovery = recovery_leg(args, runtime)

    report = {
        "bench": "service_storm",
        "platform": jax.default_backend(),
        "nproc": os.cpu_count(),
        "curve": args.curve,
        "seed": args.seed,
        "concurrency": args.concurrency,
        "batch_max": args.batch_max,
        "rho_bits": args.rho_bits,
        "warmup_s": round(warm_s, 1),
        "faults": plan.as_dict(),
        "convoy": convoy,
        "recovery": recovery,
        "sign": sign,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(f"service_storm: wrote {args.out}", flush=True)
    ok = (
        convoy["survival_rate"] == 1.0
        and convoy["healthy_bit_identical"] == convoy["healthy"]
        and convoy["poisoned_typed"] == convoy["poisoned"]
        and convoy["blame_accuracy"] == 1.0
        and recovery["corrupt_tail_skipped"]
        and recovery["crash_loop_poisoned"]
        and sign["blamed_cells_exact"]
        and sign["passes"] <= sign["pass_bound"]
        and sign["substitute_sig_bit_identical"]
        and sign["quarantined_exact"]
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
