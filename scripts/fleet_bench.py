"""Fleet throughput benchmark: ~1000 queued ceremonies through the service.

Measures the multi-tenant service (dkg_tpu.service) against the
pre-service serial-loop shape on the SAME workload:

* **service leg** — a :class:`CeremonyScheduler` with M workers and the
  stacked convoy lane enabled (``--concurrency``, ``--batch-max``),
  fed the entire workload up front (a full-queue burst: every ceremony
  is queued at t0, so per-ceremony latency IS queue-to-completion).
* **baseline leg** — the same scheduler shape degenerated to the
  pre-service loop: concurrency 1, batch_max 1 (one ceremony at a time
  through the plain width-1 executables, exactly what a caller looping
  over ``BatchedCeremony`` pays).
* **fleet leg** (``--procs``) — the multi-process front door
  (dkg_tpu.service.fleet): K spawned scheduler workers against the
  shared AOT executable store, measuring process-spawn-to-first-ceremony
  (``fleet.first_ceremony_s``), per-worker warmup, and per-proc
  throughput across fleet sizes.  Run with ``DKG_TPU_AOT_DIR`` pointing
  at a store baked by ``scripts/aot_build.py`` — without it every worker
  recompiles from scratch and the leg takes minutes per worker.

The workload mixes committee sizes n=16..64 (small-heavy, as service
traffic is) with thresholds chosen so the mix lands on three buckets —
(16,5), (32,8), (64,16) — and the per-shape counts are multiples of the
max convoy width, so the steady state runs pure width-``batch_max``
convoys.  A warmup pass compiles every (bucket, width) program before
the clock starts (compiles persist in the JAX compilation cache, so
reruns skip them); the timed legs measure the WARM service, which is
the regime a long-lived server lives in.

Correctness is asserted, not assumed: a sample of service-leg masters
is compared bit-for-bit against FRESH unpadded single-ceremony runs of
the same seeds (``engine.run_single_reference``) — the pad-and-mask +
stacking machinery must be invisible in the results.

Writes one JSON report (default ``FLEET_r01.json``) with
``service.ceremonies_per_s``, ``service.p50_s``/``p99_s`` latency,
``baseline.ceremonies_per_s`` and the speedup —
``scripts/perf_regress.py`` gates consecutive rounds on the throughput
and p99 numbers.

Run (CPU):
    JAX_PLATFORMS=cpu python scripts/fleet_bench.py --out FLEET_r01.json
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

from dkg_tpu.service import buckets, engine  # noqa: E402
from dkg_tpu.service.scheduler import CeremonyScheduler  # noqa: E402
from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.utils import runtimeobs, serde  # noqa: E402
from dkg_tpu.utils.metrics import REGISTRY  # noqa: E402

# (n, t, count-per-1000): thresholds picked so the whole mix lands on
# three buckets, small-heavy the way service traffic is (per-group
# threshold keys are small committees; big ceremonies are rare), and
# the stackable buckets' counts are multiples of the max convoy width
# so their steady state is pure width-8 convoys with no ragged tails.
# The (48/64, 16) shapes land on the (64, 16) bucket, which is past the
# stacking crossover (buckets.WIDTH_CAP_N) and runs width-1 in both
# legs.
MIX = (
    (16, 5, 896),  # bucket (16, 5)
    (24, 8, 56),   # bucket (32, 8)
    (32, 8, 24),   # bucket (32, 8) — convoys WITH the n=24s
    (48, 16, 16),  # bucket (64, 16), width-capped to 1
    (64, 16, 8),   # bucket (64, 16), width-capped to 1
)


def build_workload(curve: str, total: int, rho_bits: int, seed: int):
    """The request list, shuffled like arriving traffic (deterministic
    under ``seed``)."""
    scale = total / sum(c for _, _, c in MIX)
    reqs = []
    for n, t, count in MIX:
        # small --ceremonies runs drop the rare heavy shapes entirely
        # rather than inflating their share (a 16-ceremony smoke run
        # must not pay a (64,16) compile)
        for i in range(round(count * scale)):
            reqs.append(
                engine.CeremonyRequest(
                    curve, n, t,
                    seed=seed * 1_000_000 + n * 1_000 + i,
                    rho_bits=rho_bits,
                )
            )
    if not reqs:
        n, t, _ = MIX[0]
        reqs = [
            engine.CeremonyRequest(
                curve, n, t, seed=seed * 1_000_000 + i, rho_bits=rho_bits
            )
            for i in range(total)
        ]
    random.Random(seed).shuffle(reqs)
    return reqs


def wire_mix(curve: str, reqs) -> dict:
    """Serde-exact wire cost of the workload: every ceremony's traffic
    is deterministic at its (n, t) (utils.serde.ceremony_wire_bytes),
    so the bench publishes the totals analytically rather than running
    the hub transport.  perf_regress gates growth of the per-ceremony
    average — a fatter wire multiplies across the whole fleet."""
    group = gh.ALL_GROUPS[curve]
    total = sum(serde.ceremony_wire_bytes(group, r.n, r.t) for r in reqs)
    pairs = sum(r.n * (r.n - 1) for r in reqs)
    return {
        "bytes_total": total,
        "bytes_per_ceremony_avg": round(total / len(reqs), 1),
        "bytes_per_pair_avg": round(total / pairs, 1),
    }


def warmup(runtime: engine.WarmRuntime, reqs, widths) -> float:
    """Make every (bucket, width) program the legs will need servable;
    returns seconds spent.  Without the AOT store that means compiles +
    first table builds; with ``DKG_TPU_AOT_DIR`` pointing at a baked
    store (scripts/aot_build.py) the bucket's hot convoy shape
    deserializes instead and the rest is skipped to lazy dispatch-time
    loads — one warmup call per bucket with the full width tuple, so
    engine.WarmRuntime.warmup eagerly preloads only the largest width."""
    t0 = time.perf_counter()
    by_bucket = {}
    for r in reqs:
        by_bucket.setdefault(r.bucket(), r)
    for b, req in sorted(by_bucket.items(), key=lambda kv: kv[0].n):
        cap = buckets.width_cap(b)
        ws = tuple(sorted({min(w, cap) for w in widths}, reverse=True))
        print(f"fleet_bench: warmup bucket ({b.n},{b.t}) widths {ws}", flush=True)
        runtime.warmup(req, widths=ws)
    return time.perf_counter() - t0


def _req_wire(r: engine.CeremonyRequest) -> dict:
    """The JSON-able request dict the fleet front door accepts."""
    return {
        "curve": r.curve, "n": r.n, "t": r.t,
        "seed": r.seed, "rho_bits": r.rho_bits,
    }


def build_fleet_workload(curve: str, per_bucket: int, rho_bits: int, seed: int):
    """Bucket-BALANCED workload for the multi-process leg: the fleet
    routes by bucket hash, so equal per-bucket counts spread work across
    workers (the service-leg MIX is 90% one bucket and would pin a
    single worker)."""
    reqs = []
    for i, (n, t) in enumerate(((16, 5), (24, 8), (48, 16))):
        for j in range(per_bucket):
            reqs.append(
                engine.CeremonyRequest(
                    curve, n, t,
                    seed=seed * 2_000_000 + i * 10_000 + j,
                    rho_bits=rho_bits,
                )
            )
    random.Random(seed).shuffle(reqs)
    return reqs


def run_fleet_leg(args, procs: int, reqs) -> dict:
    """One multi-process fleet size: spawn ``procs`` workers against the
    shared AOT store, measure process-start-to-first-ceremony, per-worker
    warmup, and drained throughput.  Width-1 singles (concurrency 1,
    batch_max 1) keep the leg's programs to the store's smallest set so
    the leg measures fleet scale-out, not convoy stacking (the service
    leg above already measures that)."""
    from dkg_tpu.service.fleet import FleetServer

    by_bucket = {}
    for r in reqs:
        by_bucket.setdefault(r.bucket(), r)
    warm = [
        {"curve": r.curve, "n": r.n, "t": r.t,
         "rho_bits": r.rho_bits, "widths": (1,)}
        for _, r in sorted(by_bucket.items(), key=lambda kv: kv[0].n)
    ]
    t_start = time.monotonic()
    fleet = FleetServer(
        procs=procs, k_min=procs, k_max=procs,
        control_interval_s=None,
        scheduler_kwargs=dict(
            concurrency=1, queue_depth=len(reqs) + 8, batch_max=1
        ),
        warm=warm,
    )
    # first ceremony submitted BEFORE any worker is warm: this measures
    # the cold start end to end — process spawn + backend init + AOT
    # deserializes + the ceremony itself
    cid0 = fleet.submit(_req_wire(reqs[0]))
    out0 = fleet.result(cid0, timeout=1800)
    first_s = time.monotonic() - t_start
    warmups = fleet.wait_ready(timeout=1800)
    t0 = time.monotonic()
    cids = [fleet.submit(_req_wire(r)) for r in reqs[1:]]
    outs = [fleet.result(c, timeout=1800) for c in cids]
    total = time.monotonic() - t0
    all_outs = [out0] + outs
    done = sum(1 for o in all_outs if o.get("status") == "done")
    # masters bit-identical to fresh unpadded single runs, one per bucket
    sample, seen = [], set()
    for r, o in zip(reqs, all_outs):
        b = r.bucket()
        if b not in seen:
            seen.add(b)
            sample.append((r, o))
    mismatches = [
        {"n": r.n, "t": r.t, "seed": r.seed}
        for r, o in sample
        if o.get("master") != engine.run_single_reference(r).hex()
    ]
    workers = fleet.describe()
    fleet.close()
    leg = {
        "procs": procs,
        "ceremonies": len(all_outs),
        "completed": done,
        "first_ceremony_s": round(first_s, 2),
        "worker_warmup_s": [
            round(w, 2) if isinstance(w, (int, float)) else w for w in warmups
        ],
        "total_s": round(total, 3),
        "ceremonies_per_s": round(len(outs) / total, 3),
        "per_proc_ceremonies_per_s": round(len(outs) / total / procs, 3),
        "masters_match": not mismatches,
        "placed": workers["placed"],
    }
    if mismatches:
        leg["mismatches"] = mismatches
    print(
        f"fleet_bench: fleet procs={procs}: first ceremony {leg['first_ceremony_s']}s "
        f"after spawn, warmups {leg['worker_warmup_s']}, "
        f"{leg['ceremonies_per_s']}/s ({leg['per_proc_ceremonies_per_s']}/s/proc), "
        f"masters_match={leg['masters_match']}",
        flush=True,
    )
    return leg


def run_leg(
    label: str,
    reqs,
    runtime: engine.WarmRuntime,
    concurrency: int,
    batch_max: int,
) -> dict:
    """Queue the whole workload, drain it, and report throughput +
    queue-to-completion latency percentiles."""
    sch = CeremonyScheduler(
        concurrency=concurrency,
        queue_depth=len(reqs),
        batch_max=batch_max,
        runtime=runtime,
    )
    t0 = time.monotonic()
    ids = [sch.submit(r) for r in reqs]
    outs = [sch.result(i) for i in ids]
    total = time.monotonic() - t0
    sch.close()
    lat = sorted(o.completed_at - t0 for o in outs)
    statuses: dict[str, int] = {}
    for o in outs:
        statuses[o.status] = statuses.get(o.status, 0) + 1
    leg = {
        "concurrency": concurrency,
        "batch_max": batch_max,
        "completed": len(outs),
        "statuses": statuses,
        "total_s": round(total, 3),
        "ceremonies_per_s": round(len(outs) / total, 3),
        "p50_s": round(lat[len(lat) // 2], 3),
        "p99_s": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 3),
    }
    print(
        f"fleet_bench: {label}: {leg['completed']} ceremonies in "
        f"{leg['total_s']}s -> {leg['ceremonies_per_s']}/s "
        f"(p50 {leg['p50_s']}s, p99 {leg['p99_s']}s)",
        flush=True,
    )
    return leg, outs


def per_bucket_seconds(outs) -> dict:
    """Mean engine residency per ceremony (start_convoy -> finish wall
    clock, divided by convoy width) grouped by bucket.  Residencies of
    concurrent/pipelined convoys OVERLAP, so these are not additive CPU
    costs and are only comparable across legs at equal concurrency —
    they are reported to show the per-shape latency profile of each
    leg, not to derive per-bucket speedups."""
    acc: dict[str, list[float]] = {}
    for o in outs:
        acc.setdefault(f"{o.bucket_n}x{o.bucket_t}", []).append(o.seconds)
    return {k: round(sum(v) / len(v), 4) for k, v in sorted(acc.items())}


def verify_sample(reqs, outs, k: int) -> dict:
    """Bit-compare a shape-covering sample of service masters against
    fresh unpadded single runs of the same seeds."""
    by_shape = {}
    for req, out in zip(reqs, outs):
        by_shape.setdefault((req.n, req.t), []).append((req, out))
    picked = []
    shapes = list(by_shape.values())
    i = 0
    while len(picked) < k and any(shapes):
        bucket_list = shapes[i % len(shapes)]
        if bucket_list:
            picked.append(bucket_list.pop())
        i += 1
    mismatches = []
    for req, out in picked:
        ref = engine.run_single_reference(req)
        if out.status != "done" or out.master != ref:
            mismatches.append({"n": req.n, "t": req.t, "seed": req.seed})
    report = {"sampled": len(picked), "masters_match": not mismatches}
    if mismatches:
        report["mismatches"] = mismatches
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ceremonies", type=int, default=1000)
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument("--concurrency", type=int, default=4)
    ap.add_argument("--batch-max", type=int, default=8)
    ap.add_argument("--rho-bits", type=int, default=64)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--verify-sample", type=int, default=10)
    ap.add_argument(
        "--skip-baseline", action="store_true",
        help="service leg only (no speedup in the report)",
    )
    ap.add_argument(
        "--warm-widths", default=None,
        help="comma-separated convoy widths to precompile "
        "(default: batch_max and 1)",
    )
    ap.add_argument(
        "--procs", default=None,
        help="also run the multi-process fleet leg at these worker "
        "counts (comma-separated, e.g. '1,2'; a single K measures 1 "
        "and K so scaling is always a comparison)",
    )
    ap.add_argument(
        "--fleet-ceremonies", type=int, default=36,
        help="ceremonies per fleet size in the --procs leg "
        "(bucket-balanced, so they spread across workers)",
    )
    ap.add_argument("--out", default="FLEET_r01.json")
    args = ap.parse_args(argv)

    widths = (
        tuple(int(w) for w in args.warm_widths.split(","))
        if args.warm_widths
        else tuple(sorted({min(args.batch_max, buckets.WIDTHS[0]), 1}, reverse=True))
    )
    reqs = build_workload(args.curve, args.ceremonies, args.rho_bits, args.seed)
    runtime = engine.WarmRuntime()
    print(
        f"fleet_bench: {len(reqs)} x {args.curve} ceremonies, "
        f"buckets {sorted({(r.bucket().n, r.bucket().t) for r in reqs})}, "
        f"platform {jax.default_backend()}",
        flush=True,
    )
    # force=True: the bench opts into compile/cache telemetry without
    # the knob; armed BEFORE warmup so the report's runtime block counts
    # the expensive (bucket, width) compiles the warm legs then skip.
    # snapshot() reads runtimeobs' own aggregates, so the REGISTRY.reset
    # between legs below does not zero it.
    runtimeobs.install(force=True)
    warm_s = warmup(runtime, reqs, widths)
    print(f"fleet_bench: warmup {warm_s:.1f}s", flush=True)

    REGISTRY.reset()
    service, outs = run_leg(
        "service", reqs, runtime, args.concurrency, args.batch_max
    )
    report = {
        "bench": "fleet",
        "platform": jax.default_backend(),
        "nproc": os.cpu_count(),
        "curve": args.curve,
        "ceremonies": len(reqs),
        "concurrency": args.concurrency,
        "batch_max": args.batch_max,
        "rho_bits": args.rho_bits,
        "seed": args.seed,
        "mix": {f"{n}x{t}": c for n, t, c in MIX},
        "wire": wire_mix(args.curve, reqs),
        "warmup_s": round(warm_s, 1),
        "service": service,
        "metrics": REGISTRY.snapshot(),
    }
    service["per_bucket_residency_s"] = per_bucket_seconds(outs)
    report["verify"] = verify_sample(reqs, outs, args.verify_sample)
    print(f"fleet_bench: verify {report['verify']}", flush=True)
    if not args.skip_baseline:
        baseline, base_outs = run_leg("baseline", reqs, runtime, 1, 1)
        baseline["per_bucket_residency_s"] = per_bucket_seconds(base_outs)
        report["baseline"] = baseline
        report["speedup"] = round(
            service["ceremonies_per_s"] / baseline["ceremonies_per_s"], 2
        )
        # the speedup has two independent factors: convoy stacking
        # (dispatch amortization — all a 1-core host can show, bounded
        # by the per-bucket calibration in buckets.width_cap's docs)
        # and M-worker overlap (needs real cores); nproc above records
        # which regime this round measured
        report["speedup_note"] = (
            "M workers + stacked convoys vs the width-1 serial loop on "
            f"{os.cpu_count()} core(s); on a single core this is the "
            "stacking/dispatch-amortization share only"
        )
        print(f"fleet_bench: speedup {report['speedup']}x", flush=True)

    from dkg_tpu.service import aot  # noqa: E402 (after jax env setup)

    if aot.enabled():
        report["aot"] = aot.stats()
    fleet_ok = True
    if args.procs:
        sizes = sorted({int(k) for k in str(args.procs).split(",")} | {1})
        fleet_reqs = build_fleet_workload(
            args.curve, max(1, args.fleet_ceremonies // 3),
            args.rho_bits, args.seed + 7,
        )
        legs = [run_fleet_leg(args, k, fleet_reqs) for k in sizes]
        report["fleet"] = {
            "sizes": legs,
            # first_ceremony_s definition, for readers of the JSON:
            # process spawn -> first ceremony result, measured on a
            # submission made before any worker finished warming
            "first_ceremony_s": min(l["first_ceremony_s"] for l in legs),
            "scaling_note": (
                "per-proc ceremonies/s on "
                f"{os.cpu_count()} core(s): with fewer cores than "
                "workers the processes time-slice one CPU, so total "
                "throughput stays ~flat and per-proc falls ~1/K; on a "
                "multi-core host the same fleet multiplies throughput "
                "until cores or the device saturate"
            ),
        }
        fleet_ok = all(
            l["masters_match"] and l["completed"] == l["ceremonies"]
            for l in legs
        )

    # taken last so the block covers warmup AND both measured legs (a
    # warm rerun shows compiles_total collapsing toward zero here)
    runtimeobs.sample_memory()
    report["runtime"] = runtimeobs.snapshot()
    pathlib.Path(args.out).write_text(json.dumps(report, indent=1) + "\n")
    print(f"fleet_bench: wrote {args.out}", flush=True)
    ok = (
        report["verify"]["masters_match"]
        and service["statuses"].get("done") == len(reqs)
        and fleet_ok
    )
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
