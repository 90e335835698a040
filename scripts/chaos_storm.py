"""Chaos soak driver: seeded random fault schedules -> CHAOS.json.

Runs many full threaded ceremonies (dkg_tpu.net.run_party over an
InProcessChannel or a TcpHub), each under a random-but-seeded
FaultPlan, and asserts the resilience contract per ceremony: every
honest (untouched) party finishes ``ok`` and all honest parties agree
on the master public key.  A failing seed is a complete reproduction
recipe — the plan is derived from the seed alone, so
``tests/test_chaos.py`` can replay it exactly.

Usage::

    python scripts/chaos_storm.py --ceremonies 8 --n 6 --t 2 --out CHAOS.json
    python scripts/chaos_storm.py --tcp          # exercise the TCP hub path
    python scripts/chaos_storm.py --restarts 2   # crash-restart parties too

Faulty parties are kept within the protocol's tolerance (at most t of
the n members misbehave), so every run is *expected* to converge; a
non-converging seed is a bug, not noise.

With ``--restarts K``, up to K additional parties (outside the faulty
set) are killed mid-round and re-spawned from their checkpoint WALs
(net/checkpoint.py): restarted parties must ALSO finish ``ok`` with the
agreed master key — a restart consumes zero fault budget, which is the
whole point of durable checkpointing (docs/fault_model.md, "Crash
recovery").

With ``--churn K``, every ceremony continues into the epoch subsystem
(dkg_tpu.epoch): one proactive refresh, then a reshare in which K
seeded parties leave and K fresh parties join (committee size
preserved).  Byte faults move to the epoch DEAL rounds (senders keep
their stable old-committee numbering there) and restarts strike epoch
rounds, so the chaos contract extends across epochs: every non-faulted
party — stayers, joiners, and restarted parties alike — must finish its
epoch sequence without error, leavers must exit cleanly after dealing,
and every master key observed after every epoch must be bit-identical
to the ceremony's.  Per-run epoch counters (``epochs_run``,
``epoch_masters_stable``, ``churn``) land in CHAOS.json.  Cold-compile
caveat: the first epoch run compiles the dealing kernels; a warmup run
with a fault-free plan and a long deadline precedes the storm so
fetch timeouts measure faults, not XLA.

Set ``DKG_TPU_OBSLOG=<dir>`` to additionally write one flight-recorder
JSONL per party per ceremony (committees get per-seed shared strings,
so every run has a distinct ceremony_id); ``scripts/trace_viz.py`` over
that directory renders the whole storm as one Chrome/Perfetto timeline
(docs/observability.md).  The report embeds a process-wide metrics
snapshot under ``"metrics"``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from dkg_tpu.utils import compilecache  # noqa: E402

compilecache.enable()  # epoch runs compile the dealing kernels; persist them across storms

from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.net import InProcessChannel, PartyResult, TcpHub, TcpHubChannel  # noqa: E402
from dkg_tpu.net.faults import (  # noqa: E402
    FaultPlan,
    churn_schedule,
    honest_results,
    make_committee,
    run_epochs_with_faults,
    run_with_faults,
)
from dkg_tpu.utils import obslog  # noqa: E402
from dkg_tpu.utils.metrics import REGISTRY  # noqa: E402

G = gh.RISTRETTO255

# Wire-fault kinds the storm samples from (crash/delay are scheduled
# separately so at most one party loses liveness per ceremony — more
# than that turns every round into a full timeout wait).
_BYTE_FAULTS = ("garbage", "truncate", "bitflip", "equivocate", "duplicate", "drop")


def random_plan(seed: int, n: int, t: int, timeout: float, restarts: int = 0) -> FaultPlan:
    """Sample a fault schedule touching at most t of the n parties,
    plus up to ``restarts`` mid-round crash-restarts on OTHER parties
    (recoverable with checkpointing, so they sit outside the t budget)."""
    rng = random.Random(seed)
    plan = FaultPlan(seed)
    faulty = rng.sample(range(1, n + 1), rng.randint(1, t))
    liveness_used = False
    for sender in faulty:
        style = rng.random()
        if style < 0.25 and not liveness_used:
            liveness_used = True
            if rng.random() < 0.5:
                plan.crash_after(sender=sender, round_no=rng.randint(1, 4))
            else:
                plan.delay(rng.randint(1, 5), sender, seconds=timeout * 2.5)
        else:
            for _ in range(rng.randint(1, 2)):
                kind = rng.choice(_BYTE_FAULTS)
                getattr(plan, kind)(rng.randint(1, 5), sender)
    if restarts:
        candidates = [p for p in range(1, n + 1) if p not in faulty]
        for sender in rng.sample(candidates, min(restarts, len(candidates))):
            plan.restart(sender=sender, round_no=rng.randint(1, 5))
    return plan


def random_epoch_plan(
    seed: int, n: int, t: int, restarts: int = 0, refreshes: int = 1
) -> FaultPlan:
    """Sample a fault schedule for a ceremony + epoch sequence: byte
    faults land on the epoch DEAL rounds only (their senders keep the
    stable OLD-committee numbering, so "honest = untouched" stays
    well-defined after the reshare renumbers the committee), restarts
    land on refresh rounds every founding party fetches.  The ceremony
    rounds run clean — ceremony-round chaos is the plain storm's job."""
    rng = random.Random(seed ^ 0xE70C)
    plan = FaultPlan(seed)
    # deal rounds: op k (1-based) deals at round 6 + 3*(k-1); the
    # reshare is op refreshes+1
    deal_rounds = [6 + 3 * op for op in range(refreshes + 1)]
    faulty = rng.sample(range(1, n + 1), rng.randint(1, t))
    for sender in faulty:
        for _ in range(rng.randint(1, 2)):
            kind = rng.choice(_BYTE_FAULTS)
            getattr(plan, kind)(rng.choice(deal_rounds), sender)
    if restarts:
        refresh_rounds = list(range(6, 6 + 3 * refreshes))
        candidates = [p for p in range(1, n + 1) if p not in faulty]
        for sender in rng.sample(candidates, min(restarts, len(candidates))):
            plan.restart(sender=sender, round_no=rng.choice(refresh_rounds))
    return plan


def run_one_epochs(
    seed: int,
    n: int,
    t: int,
    churn_k: int,
    timeout: float,
    tcp: bool,
    restarts: int = 0,
    refreshes: int = 1,
    warmup: bool = False,
) -> dict:
    """One ceremony + ``refreshes`` refreshes + one K-leave/K-join
    reshare under a seeded epoch fault plan; asserts the epoch chaos
    contract per run.  ``warmup=True`` runs fault-free with a long
    deadline purely to populate the XLA compile caches."""
    env, keys, pks = make_committee(
        G, n, t, seed, shared_string=f"chaos-epoch-{seed:x}".encode()
    )
    churn = churn_schedule(seed, n, churn_k)
    if warmup:
        plan, timeout = FaultPlan(seed), 600.0
    else:
        plan = random_epoch_plan(seed, n, t, restarts=restarts, refreshes=refreshes)
    hub = None
    ckpt = tempfile.TemporaryDirectory(prefix="dkg-wal-") if restarts else None
    try:
        if tcp:
            hub = TcpHub().start()
            host, port = hub.address

            def factory(i: int):
                return TcpHubChannel(host, port)

            evidence_channel = hub.channel
        else:
            chan = InProcessChannel()

            def factory(i: int):
                return chan

            evidence_channel = chan

        t0 = time.monotonic()
        outcomes = run_epochs_with_faults(
            env, keys, pks, plan, factory,
            churn=churn, refreshes=refreshes, timeout=timeout, seed=seed,
            checkpoint_dir=ckpt.name if ckpt else None,
        )
        wall = time.monotonic() - t0
        founding, joiners = outcomes[:n], outcomes[n:]
        faulty = {s for (_rnd, s) in plan._faults}
        honest = [o for o in founding if o.party not in faulty]
        final_epoch = refreshes + 1
        base_masters = {
            G.encode(o.base.master.point).hex()
            for o in honest
            if isinstance(o.base, PartyResult) and o.base.ok
        }
        epoch_masters = {
            m.hex() for o in honest + joiners for m in o.masters
        }
        epoch_all_ok = (
            all(o.error is None for o in honest + joiners)
            and all(o.left for o in honest if o.party in churn.leavers)
            and all(
                o.state is not None and o.state.epoch == final_epoch
                for o in honest + joiners
                if o.party not in churn.leavers
            )
        )
        return {
            "seed": seed,
            "ceremony_id": obslog.ceremony_id_for(env),
            "plan": plan.as_dict(),
            "wall_s": round(wall, 3),
            "outcomes": [
                {
                    "party": o.party,
                    "joiner": o.party > n,
                    "base_ok": isinstance(o.base, PartyResult) and o.base.ok,
                    "left": o.left,
                    "epoch": None if o.state is None else o.state.epoch,
                    "masters_seen": len(o.masters),
                    "resumes": o.resumes,
                    "error": None if o.error is None else repr(o.error),
                }
                for o in outcomes
            ],
            "honest_parties": [o.party for o in honest],
            "honest_all_ok": bool(honest)
            and all(isinstance(o.base, PartyResult) and o.base.ok for o in honest),
            "honest_agreed": len(base_masters) == 1,
            "restarted_parties": sorted(plan._restarts),
            "restarted_all_ok": (
                all(
                    founding[s - 1].error is None and founding[s - 1].resumes > 0
                    for s in plan._restarts
                )
                if plan._restarts
                else None
            ),
            "restarted_agreed": None,
            "equivocations": [
                {"round": rn, "sender": s, "distinct_payloads": len(p)}
                for (rn, s), p in sorted(evidence_channel.equivocation_evidence().items())
            ],
            "epochs": {
                "epochs_run": final_epoch,
                "refreshes": refreshes,
                "churn": churn.churn,
                "leavers": list(churn.leavers),
                "joiners": churn.joiners,
                "epoch_all_ok": epoch_all_ok,
                # the tentpole invariant: every master key any honest
                # party observed after any epoch is bit-identical to the
                # ceremony's master public key
                "epoch_masters_stable": epoch_masters <= base_masters
                and len(epoch_masters) == 1,
                "resumes": sum(o.resumes for o in outcomes),
            },
        }
    finally:
        if hub is not None:
            hub.stop()
        if ckpt is not None:
            ckpt.cleanup()


def run_one(
    seed: int, n: int, t: int, timeout: float, tcp: bool, restarts: int = 0
) -> dict:
    # per-seed shared string -> per-run commitment key -> distinct
    # ceremony_id per storm run, so flight-recorder logs never collide
    env, keys, pks = make_committee(
        G, n, t, seed, shared_string=f"chaos-{seed:x}".encode()
    )
    plan = random_plan(seed, n, t, timeout, restarts=restarts)
    hub = None
    ckpt = tempfile.TemporaryDirectory(prefix="dkg-wal-") if restarts else None
    try:
        if tcp:
            hub = TcpHub().start()
            host, port = hub.address

            def factory(i: int):
                return TcpHubChannel(host, port)

            evidence_channel = hub.channel
        else:
            chan = InProcessChannel()

            def factory(i: int):
                return chan

            evidence_channel = chan

        t0 = time.monotonic()
        results = run_with_faults(
            env, keys, pks, plan, factory, timeout=timeout, seed=seed,
            checkpoint_dir=ckpt.name if ckpt else None,
        )
        wall = time.monotonic() - t0
        honest = honest_results(results, plan)
        masters = {G.encode(r.master.point).hex() for r in honest if r.ok}
        restarted = [results[s - 1] for s in sorted(plan._restarts)]
        restarted_masters = {
            G.encode(r.master.point).hex()
            for r in restarted
            if isinstance(r, PartyResult) and r.ok
        }
        return {
            "seed": seed,
            "ceremony_id": obslog.ceremony_id_for(env),
            "plan": plan.as_dict(),
            "wall_s": round(wall, 3),
            "outcomes": [
                {"party": i + 1, "kind": type(r).__name__}
                | (
                    {
                        "ok": r.ok,
                        "error": str(r.error) if r.error else None,
                        "quarantined": r.quarantined,
                        "timeouts": r.timeouts,
                        "retries": r.retries,
                        "resumes": r.resumes,
                    }
                    if isinstance(r, PartyResult)
                    else {"detail": str(r)}
                )
                for i, r in enumerate(results)
            ],
            "honest_parties": [r.index for r in honest],
            "honest_all_ok": bool(honest) and all(r.ok for r in honest),
            "honest_agreed": len(masters) == 1,
            "restarted_parties": sorted(plan._restarts),
            # the checkpointing contract: every restarted party recovers
            # and lands on the same master key the honest set agreed on
            "restarted_all_ok": (
                all(isinstance(r, PartyResult) and r.ok for r in restarted)
                if restarted
                else None
            ),
            "restarted_agreed": (
                restarted_masters <= masters if restarted else None
            ),
            "equivocations": [
                {"round": rn, "sender": s, "distinct_payloads": len(p)}
                for (rn, s), p in sorted(evidence_channel.equivocation_evidence().items())
            ],
        }
    finally:
        if hub is not None:
            hub.stop()
        if ckpt is not None:
            ckpt.cleanup()


def run_storm(
    ceremonies: int = 8,
    n: int = 6,
    t: int = 2,
    base_seed: int = 0xC7A05,
    timeout: float = 1.0,
    tcp: bool = False,
    restarts: int = 0,
    churn: int = 0,
) -> dict:
    if churn:
        # fault-free compile pass: first contact with the epoch kernels
        # takes minutes of XLA on a cold cache, which would otherwise be
        # indistinguishable from a liveness fault at a 1-10s deadline
        run_one_epochs(
            base_seed - 1, n, t, churn, timeout, tcp,
            restarts=restarts, warmup=True,
        )
        runs = [
            run_one_epochs(
                base_seed + c, n, t, churn, timeout, tcp, restarts=restarts
            )
            for c in range(ceremonies)
        ]
    else:
        runs = [
            run_one(base_seed + c, n, t, timeout, tcp, restarts=restarts)
            for c in range(ceremonies)
        ]
    survived = sum(
        r["honest_all_ok"]
        and r["honest_agreed"]
        and r["restarted_all_ok"] is not False
        and r["restarted_agreed"] is not False
        and (
            r["epochs"]["epoch_all_ok"] and r["epochs"]["epoch_masters_stable"]
            if churn
            else True
        )
        for r in runs
    )
    fault_counts: dict[str, int] = {}
    for r in runs:
        for f in r["plan"]["faults"]:
            fault_counts[f["kind"]] = fault_counts.get(f["kind"], 0) + 1
        fault_counts["crash"] = fault_counts.get("crash", 0) + len(r["plan"]["crash_after"])
        fault_counts["restart"] = fault_counts.get("restart", 0) + sum(
            len(v) for v in r["plan"]["restarts"].values()
        )
    return {
        "ceremonies": ceremonies,
        "n": n,
        "t": t,
        "base_seed": base_seed,
        "timeout_s": timeout,
        "transport": "tcp_hub" if tcp else "in_process",
        "checkpointing": bool(restarts),
        "churn": churn,
        "epochs_run": sum(r["epochs"]["epochs_run"] for r in runs) if churn else 0,
        "epoch_masters_stable": (
            all(r["epochs"]["epoch_masters_stable"] for r in runs) if churn else None
        ),
        "survived": survived,
        "survival_rate": survived / ceremonies if ceremonies else None,
        "faults_injected": dict(sorted(fault_counts.items())),
        "metrics": REGISTRY.snapshot(),
        "runs": runs,
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ceremonies", type=int, default=8)
    ap.add_argument("--n", type=int, default=6)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--seed", type=lambda v: int(v, 0), default=0xC7A05)
    ap.add_argument(
        "--timeout", type=float, default=None,
        help="per-round fetch timeout (s); default 1.0, or 10.0 with --churn "
        "(epoch ops dispatch batched EC kernels per step)",
    )
    ap.add_argument("--tcp", action="store_true", help="run over a TcpHub instead of in-process")
    ap.add_argument(
        "--restarts", type=int, default=0,
        help="also crash-restart up to K non-faulty parties per ceremony, "
        "recovered from checkpoint WALs (0 = off)",
    )
    ap.add_argument(
        "--churn", type=int, default=0,
        help="continue every ceremony into one refresh + one reshare with "
        "K seeded leavers and K joiners, faults moved to epoch deal "
        "rounds (0 = ceremony-only storm)",
    )
    ap.add_argument("--out", default="CHAOS.json")
    args = ap.parse_args()
    timeout = args.timeout if args.timeout is not None else (10.0 if args.churn else 1.0)

    report = run_storm(
        ceremonies=args.ceremonies,
        n=args.n,
        t=args.t,
        base_seed=args.seed,
        timeout=timeout,
        tcp=args.tcp,
        restarts=args.restarts,
        churn=args.churn,
    )
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
    epoch_note = (
        f"; epochs: {report['epochs_run']} run, masters_stable="
        f"{report['epoch_masters_stable']}"
        if args.churn
        else ""
    )
    print(
        f"chaos storm: {report['survived']}/{report['ceremonies']} ceremonies survived "
        f"({report['transport']}){epoch_note}; faults: {report['faults_injected']} -> {args.out}"
    )
    bad = [
        r["seed"]
        for r in report["runs"]
        if not (r["honest_all_ok"] and r["honest_agreed"])
        or r["restarted_all_ok"] is False
        or r["restarted_agreed"] is False
        or (
            args.churn
            and not (
                r["epochs"]["epoch_all_ok"] and r["epochs"]["epoch_masters_stable"]
            )
        )
    ]
    if bad:
        print(f"NON-CONVERGING SEEDS (reproduce via FaultPlan(seed)): {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
