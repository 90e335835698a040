"""Fleet-level chaos storm: prove worker failover loses nothing.

scripts/service_storm.py storms ONE scheduler in-process; this storms
the multi-process front door (dkg_tpu.service.fleet) at the process
boundary, where the failure modes are SIGKILL, garbled pipes and torn
slot journals instead of injected exceptions.  Two legs, one seeded
:class:`~dkg_tpu.service.faultsvc.FleetFaultPlan`, one JSON verdict
(default ``FLEETSTORM_r01.json``) that scripts/perf_regress.py gates
as FLOORS — zero accepted ceremonies lost, recovered masters
bit-identical, quarantine counts exact.

* **failover leg** — >=100 seeded durable ceremonies burst into a
  2-worker fleet with per-slot journals (``wal_root``).  The plan
  SIGKILLs the worker holding the Nth accepted submission (mid-ceremony:
  its queue is full of pending work), corrupts that slot's journal tail
  in the same breath (the torn tail the replacement must compact past),
  SIGKILLs the first replacement the fleet spawns (mid-recovery — the
  hardest window), and injects one unpicklable pipe frame against a
  healthy worker (which must shrug it off and keep serving).  The AOT
  store points at an empty directory, so every worker boots down the
  jit-fallback path — failover and AOT degradation are proven to
  COMPOSE, not just pass separately.  Verdict: every accepted ceremony
  reaches ``done`` under its ORIGINAL ceremony id, and every ceremony
  that was placed on a killed worker comes back with a master
  BIT-IDENTICAL to a fresh fault-free single run of the same seed.
* **quarantine leg** — a 1-worker fleet whose child is wired to die at
  boot (``worker_fault={"boot_fail": True}``).  The slot must burn its
  respawn budget (capped backoff, no hot loop) and land in quarantine
  EXACTLY once — fleet_worker_quarantined_total and ``GET /fleet`` are
  the observables.

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/fleet_storm.py --out FLEETSTORM_r01.json
"""

from __future__ import annotations

import argparse
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

from dkg_tpu.service import engine  # noqa: E402
from dkg_tpu.service.faultsvc import FleetFaultPlan  # noqa: E402
from dkg_tpu.service.fleet import FleetServer  # noqa: E402
from dkg_tpu.utils.metrics import REGISTRY  # noqa: E402

# (n, t, count): two small buckets, the shape service traffic is; the
# counts land >=100 total so the zero-loss floor means something
SHAPES = [(16, 5, 64), (32, 8, 48)]


def build_workload(curve: str, rho_bits: int, seed: int) -> list:
    reqs = []
    for n, t, count in SHAPES:
        for i in range(count):
            reqs.append(
                engine.CeremonyRequest(
                    curve=curve, n=n, t=t,
                    seed=(seed << 20) | (n << 10) | i,
                    rho_bits=rho_bits, durable=True,
                )
            )
    random.Random(seed).shuffle(reqs)
    return reqs


def _req_wire(r: engine.CeremonyRequest) -> dict:
    return {
        "curve": r.curve, "n": r.n, "t": r.t, "seed": r.seed,
        "rho_bits": r.rho_bits, "durable": True,
    }


def failover_leg(args, reqs, wal_root: str) -> tuple[dict, FleetFaultPlan]:
    plan = (
        FleetFaultPlan(seed=args.seed)
        .kill_worker(at_submit=args.kill_at)
        .kill_on_respawn(times=1)
        .garble_pipe(at_submit=args.garble_at)
        .corrupt_slot_journal(at_submit=args.kill_at)
    )
    warm = [
        {"curve": args.curve, "n": n, "t": t,
         "rho_bits": args.rho_bits, "widths": (1, args.batch_max)}
        for n, t, _ in SHAPES
    ]
    fleet = FleetServer(
        procs=2, k_min=2, k_max=2,
        control_interval_s=0.25,
        wal_root=wal_root,
        respawn_backoff_s=0.2,
        fault_plan=plan,
        scheduler_kwargs=dict(
            concurrency=args.concurrency,
            queue_depth=len(reqs) + 16,
            batch_max=args.batch_max,
            # kill + kill-on-respawn stamp up to two replays per pending
            # ceremony; keep clear of the crash-loop poison threshold
            max_replays=6,
        ),
        warm=warm,
    )
    try:
        warmups = fleet.wait_ready(timeout=1800)
        print(f"fleet_storm: 2 workers warm {warmups}", flush=True)

        t0 = time.monotonic()
        cids = []
        for r in reqs:
            cid = fleet.submit(_req_wire(r))
            cids.append(cid)
            plan.on_submit(fleet, len(cids), cid)
        # the garble can miss if the pipe lock is busy at that instant:
        # the floor wants >=1 garbled frame, so make sure one landed
        for _ in range(50):
            if plan.injected.get("fleet_pipe_garbage", 0):
                break
            if any(
                w.alive() and w.inject_garbage() for w in list(fleet._workers)
            ):
                plan._note("fleet_pipe_garbage")
                break
            time.sleep(0.1)
        submit_s = time.monotonic() - t0
        print(
            f"fleet_storm: {len(cids)} accepted in {submit_s:.1f}s, "
            f"faults {plan.injected}",
            flush=True,
        )

        outs = []
        for cid in cids:
            try:
                outs.append(fleet.result(cid, timeout=900))
            except Exception as exc:
                print(
                    f"fleet_storm: LOST {cid}: {type(exc).__name__}: {exc}",
                    file=sys.stderr, flush=True,
                )
                outs.append(None)
        drain_s = time.monotonic() - t0

        killed = set(plan.killed_cids)
        recovered = [
            (r, o) for r, c, o in zip(reqs, cids, outs) if c in killed
        ]
        # one clean (never-orphaned) ceremony per bucket rides along in
        # the bit-identity check as the control group
        clean_sample, seen = [], set()
        for r, c, o in zip(reqs, cids, outs):
            if c not in killed and (r.n, r.t) not in seen:
                seen.add((r.n, r.t))
                clean_sample.append((r, o))
        mismatches = []
        for r, o in recovered + clean_sample:
            if o is None or o.get("master") != engine.run_single_reference(r).hex():
                mismatches.append({"n": r.n, "t": r.t, "seed": r.seed})
        rec_identical = sum(
            1
            for r, o in recovered
            if o is not None
            and o.get("master") == engine.run_single_reference(r).hex()
        )

        done = sum(1 for o in outs if o and o.get("status") == "done")
        lost = sum(1 for o in outs if o is None)
        describe = fleet.describe()
    finally:
        fleet.close()

    leg = {
        "requests": len(cids),
        "done": done,
        "lost": lost,
        "recovered": {
            "count": len(recovered),
            "bit_identical": rec_identical,
        },
        "clean_sample_bit_identical": not any(
            m for m in mismatches
            if m["seed"] in {r.seed for r, _ in clean_sample}
        ),
        "submit_s": round(submit_s, 1),
        "drain_s": round(drain_s, 1),
        "slots": describe["slots"],
        "tombstones": describe["tombstones"],
    }
    if mismatches:
        leg["mismatches"] = mismatches
    print(
        f"fleet_storm: failover leg: {done}/{len(cids)} done, {lost} lost, "
        f"recovered {rec_identical}/{len(recovered)} bit-identical, "
        f"drain {leg['drain_s']}s",
        flush=True,
    )
    return leg, plan


def quarantine_leg(args, wal_root: str) -> dict:
    """One slot, a child that dies at boot, a respawn budget of 2 —
    the fleet must quarantine the slot instead of hot-looping."""
    before = REGISTRY.snapshot()["counters"].get(
        "fleet_worker_quarantined_total", 0
    )
    fleet = FleetServer(
        procs=1, k_min=1, k_max=1,
        control_interval_s=0.1,
        wal_root=wal_root,
        respawn_backoff_s=0.05,
        respawn_max=2,
        respawn_window_s=60.0,
        worker_fault={"boot_fail": True, "seed": args.seed},
        scheduler_kwargs=dict(concurrency=1, queue_depth=8, batch_max=1),
    )
    t0 = time.monotonic()
    observed = 0
    try:
        while time.monotonic() - t0 < 90.0:
            observed = fleet.describe()["quarantined"]
            if observed:
                break
            time.sleep(0.2)
        wall = time.monotonic() - t0
        slots = fleet.describe()["slots"]
    finally:
        fleet.close()
    snap = REGISTRY.snapshot()["counters"]
    metric = snap.get("fleet_worker_quarantined_total", 0) - before
    print(
        f"fleet_storm: quarantine leg: {observed} slot(s) quarantined in "
        f"{wall:.1f}s (metric +{metric})",
        flush=True,
    )
    return {
        "expected": 1,
        "observed": int(observed),
        "metric_delta": int(metric),
        "wall_s": round(wall, 1),
        "slots": slots,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument("--concurrency", type=int, default=2)
    ap.add_argument("--batch-max", type=int, default=4)
    ap.add_argument("--rho-bits", type=int, default=64)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--kill-at", type=int, default=45)
    ap.add_argument("--garble-at", type=int, default=20)
    ap.add_argument("--out", default="FLEETSTORM_r01.json")
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    reqs = build_workload(args.curve, args.rho_bits, args.seed)
    print(
        f"fleet_storm: {len(reqs)} x {args.curve} durable seeded ceremonies, "
        f"platform {jax.default_backend()}",
        flush=True,
    )
    with tempfile.TemporaryDirectory(prefix="dkg_fleetstorm_") as tmp:
        # empty AOT store: every worker misses and falls back to jit —
        # the degradation seam the failover must compose with
        os.environ["DKG_TPU_AOT_DIR"] = os.path.join(tmp, "aot_empty")
        failover, plan = failover_leg(
            args, reqs, wal_root=os.path.join(tmp, "wal")
        )
        quarantine = quarantine_leg(args, wal_root=os.path.join(tmp, "qwal"))

    injected = plan.injected
    report = {
        "bench": "fleet_storm",
        "platform": jax.default_backend(),
        "nproc": os.cpu_count(),
        "curve": args.curve,
        "seed": args.seed,
        "concurrency": args.concurrency,
        "batch_max": args.batch_max,
        "rho_bits": args.rho_bits,
        "ceremonies": {
            "requests": failover["requests"],
            "done": failover["done"],
            "lost": failover["lost"],
            "recovered": failover["recovered"],
        },
        "faults": {
            "kills_mid_ceremony": injected.get("fleet_kill", 0),
            "kills_mid_recovery": injected.get("fleet_kill_recovery", 0),
            "pipe_garbage": injected.get("fleet_pipe_garbage", 0),
            "journal_corrupted": injected.get("fleet_journal_tail", 0),
            "injected": dict(injected),
            "plan": plan.as_dict(),
        },
        "quarantine": quarantine,
        "failover": failover,
        "metrics": {
            k: v
            for k, v in sorted(REGISTRY.snapshot()["counters"].items())
            if str(k).startswith("fleet_")
        },
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    rec = failover["recovered"]
    ok = (
        failover["requests"] >= 100
        and failover["lost"] == 0
        and failover["done"] == failover["requests"]
        and rec["count"] >= 1
        and rec["bit_identical"] == rec["count"]
        and failover["clean_sample_bit_identical"]
        and report["faults"]["kills_mid_ceremony"] >= 1
        and report["faults"]["kills_mid_recovery"] >= 1
        and report["faults"]["pipe_garbage"] >= 1
        and report["faults"]["journal_corrupted"] >= 1
        and quarantine["observed"] == quarantine["expected"]
        and quarantine["metric_delta"] == quarantine["expected"]
    )
    report["ok"] = ok
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(
        f"fleet_storm: wrote {args.out} (ok={ok}, "
        f"wall {report['wall_s']}s)",
        flush=True,
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
