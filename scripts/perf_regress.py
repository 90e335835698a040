"""Perf-regression gate over the committed bench history.

The driver appends one ``BENCH_r{NN}.json`` per round; each carries the
bench's single JSON line under ``parsed`` (bench.py docstring).  This
script diffs the NEWEST TWO rounds' headline metric
(``share_verify_pairs_per_sec_per_chip``) and FAILS (exit 1) when the
newer rate dropped more than 20% below the older one — the tripwire
that catches a perf_opt PR quietly un-doing a previous one.  Three
phase metrics are gated the same way when both rounds carry them: the
dealing DEM rate (``config.pairs_sealed_per_s``, the vectorized
KEM+DEM pipeline), the deal-phase pair rate
(``config.rates_per_s.deal``), and the Fiat-Shamir pair rate
(``config.rates_per_s.fiat_shamir`` — the jitted/host-dispatched
transcript digest pipeline).

Deliberately forgiving about everything except a real regression:

* fewer than two comparable rounds (missing files, ``parsed: null``
  from a failed bench, zero/absent value) -> exit 0 with a note; an
  infra-dead round must not block unrelated work;
* different platforms (cpu vs tpu rounds) are incomparable -> exit 0
  with a note, since a change of machine says nothing about the
  code;
* different ``config.checkpoint`` flags (one round measured with
  durable WAL journaling armed, the other without) are likewise
  incomparable -> exit 0 with a note: fsync'd checkpointing is a
  deliberate durability cost, not a perf regression;
* different kernel tiers (``config.pallas_ceremony``, falling back to
  the plain ``config.pallas`` flag on older rounds; same rule per
  round for the SIGN history's ``pallas`` field) are incomparable ->
  exit 0 with a note: an interpret-mode Pallas round on CPU and an
  XLA round execute entirely different programs;
* improvements and <=20% noise -> exit 0;
* the ``metrics`` block (process-wide registry snapshot embedded by
  bench.py since the observability PR) is tolerated and passed through
  with an informational note — it is telemetry, never a gate.

The multi-tenant service has its own history, ``FLEET_r{NN}.json``
(scripts/fleet_bench.py): the newest two fleet rounds are diffed the
same way — FAIL when ``ceremonies_per_s`` dropped more than the
threshold, or when the tail latency ``p99_s`` ROSE more than the
threshold (a throughput win bought by starving the queue tail is a
regression for a service), or when ``warmup_s`` ROSE more than the
threshold (the cold-start gate: the AOT executable store took warmup
from minutes of recompiles to seconds of deserializes, and a quiet
slide back must fail here).  The same forgiveness rules apply: fewer
than two comparable fleet rounds, mismatched platforms, or mismatched
service shapes (concurrency/batch_max) skip with a note.

The epoch subsystem likewise: ``EPOCH_r{NN}.json`` rounds
(scripts/epoch_bench.py) are diffed newest-two — FAIL when
``refreshes_per_s`` dropped more than the threshold (reshare wall-clock
is reported but informational: a single op's wall time on a shared box
is too noisy to gate).  Mismatched platforms or committee shapes
(n/t/curve) skip with a note.

The signing subsystem: ``SIGN_r{NN}.json`` rounds
(scripts/sign_bench.py) are diffed newest-two, per (curve, n, messages)
shape — FAIL when a shape's ``partials_per_s`` dropped more than the
threshold (proof and aggregate rates are informational: they carry
host-side Fiat-Shamir hashing and single-dispatch MSM noise).  Shapes
present in only one round, or rounds from different platforms, skip
with a note.  Rounds carrying a ``steady_state`` block (sign_bench
``--steady``: the scheduler lane's warm throughput) additionally gate
``steady_state.signatures_per_s`` the same way; an older round that
predates steady-state mode skips that leg with a note.

The north-star scale run: ``NORTHSTAR_r{NN}.json`` rounds
(scripts/northstar_bench.py — the mesh-sharded ceremony measured at the
largest honest shape, bench.py's ``north_star`` slot embeds the same
dict) gate two ways.  FLOOR on the newest round:
``bit_exact_vs_unsharded`` must be true — a sharded ceremony that
drifts from the single-chip engine is a correctness bug whatever its
speed.  DIFF newest-two: FAIL when ``wall_s`` ROSE more than the
threshold at a matching (curve, n, t, mesh_shape, platform) key;
mismatched keys are incomparable (a different rung or a different box)
and skip with a note, as does a history with fewer than two rounds.

The service chaos storm: ``SVCSTORM_r{NN}.json`` rounds
(scripts/service_storm.py) gate FLOORS on the newest round rather than
a newest-two diff — resilience is an invariant, not a rate.  FAIL when
the newest storm round shows ``survival_rate`` < 1.0 (a healthy request
was harmed by someone else's fault), a healthy master that was not
bit-identical to the fault-free reference leg, a poisoned request
without a typed ``PoisonedRequest`` outcome, blame accuracy < 1.0
(convoy bisection or signing RLC blame fingered the wrong culprit), or
a signing blame pass count above the ceil(log2 grid)+1-per-bad-cell
bound.  No storm rounds on disk skips with a note.

The fleet chaos storm: ``FLEETSTORM_r{NN}.json`` rounds
(scripts/fleet_storm.py) gate FLOORS on the newest round the same way —
process-level failover is an invariant.  FAIL when the newest round
accepted fewer than 100 seeded ceremonies, LOST any accepted ceremony
(no terminal outcome under its original cid), injected fewer than one
worker kill mid-ceremony plus one mid-recovery, skipped the pipe
garbage or slot-journal tail corruption legs, recovered any master
that was not bit-identical to the fault-free single-process reference,
or quarantined a different number of crash-looping slots than the
fault plan scheduled.  No fleet-storm rounds on disk skips with a
note.

Run: ``python scripts/perf_regress.py [--threshold 0.2] [dir]``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import pathlib
import re
import sys

_PAT = re.compile(r"BENCH_r(\d+)\.json$")
_FLEET_PAT = re.compile(r"FLEET_r(\d+)\.json$")
_EPOCH_PAT = re.compile(r"EPOCH_r(\d+)\.json$")
_SIGN_PAT = re.compile(r"SIGN_r(\d+)\.json$")
_SVCSTORM_PAT = re.compile(r"SVCSTORM_r(\d+)\.json$")
_FLEETSTORM_PAT = re.compile(r"FLEETSTORM_r(\d+)\.json$")
_NORTHSTAR_PAT = re.compile(r"NORTHSTAR_r(\d+)\.json$")


def _load_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, parsed bench line) for every round with a usable
    measurement, ascending."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("BENCH_r*.json")):
        m = _PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        parsed = doc.get("parsed") if isinstance(doc, dict) else None
        if not isinstance(parsed, dict):
            continue
        value = parsed.get("value")
        if not isinstance(value, (int, float)) or value <= 0:
            continue  # zeroed value == "all ladder rungs failed"
        out.append((int(m.group(1)), parsed))
    out.sort(key=lambda t: t[0])
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dir", nargs="?", default=None, help="history dir (default: repo root)")
    ap.add_argument(
        "--threshold",
        type=float,
        default=0.2,
        help="fractional drop that fails the gate (default 0.2 == 20%%)",
    )
    args = ap.parse_args(argv)
    root = (
        pathlib.Path(args.dir)
        if args.dir
        else pathlib.Path(__file__).resolve().parent.parent
    )

    fleet_bad = (
        fleet_gate(root, args.threshold)
        or epoch_gate(root, args.threshold)
        or sign_gate(root, args.threshold)
        or svcstorm_gate(root)
        or fleetstorm_gate(root)
        or northstar_gate(root, args.threshold)
        or _slo_gate(root)
    )

    rounds = _load_rounds(root)
    if len(rounds) < 2:
        print(f"perf_regress: {len(rounds)} usable round(s) in {root} — nothing to diff")
        return fleet_bad
    (old_n, old), (new_n, new) = rounds[-2], rounds[-1]
    old_plat = (old.get("config") or {}).get("platform")
    new_plat = (new.get("config") or {}).get("platform")
    if old_plat != new_plat:
        print(
            f"perf_regress: r{old_n} ({old_plat}) vs r{new_n} ({new_plat}) "
            "ran on different platforms — incomparable, skipping"
        )
        return fleet_bad
    old_ckpt = bool((old.get("config") or {}).get("checkpoint"))
    new_ckpt = bool((new.get("config") or {}).get("checkpoint"))
    if old_ckpt != new_ckpt:
        print(
            f"perf_regress: r{old_n} (checkpoint={old_ckpt}) vs r{new_n} "
            f"(checkpoint={new_ckpt}) measured different durability modes "
            "— incomparable, skipping"
        )
        return fleet_bad

    # which kernel tier did the measured ceremony run?  ``pallas_ceremony``
    # (the fused-kernel flag as the bench child saw it) with the older
    # rounds' plain ``pallas`` flag as the fallback key — a cpu
    # interpret-mode Pallas round and an XLA round execute entirely
    # different programs, so diffing them says nothing about either.
    def _pallas_mode(parsed: dict) -> bool:
        cfg = parsed.get("config") or {}
        return bool(cfg.get("pallas_ceremony", cfg.get("pallas")))

    old_pal, new_pal = _pallas_mode(old), _pallas_mode(new)
    if old_pal != new_pal:
        print(
            f"perf_regress: r{old_n} (pallas={old_pal}) vs r{new_n} "
            f"(pallas={new_pal}) measured different kernel tiers "
            "— incomparable, skipping"
        )
        return fleet_bad
    # every gated metric goes through one loop with one forgiveness
    # rule: rounds predating a metric (or with that leg failed/zero)
    # skip that gate with a note rather than blocking.
    def _headline(parsed: dict):
        return parsed.get("value")

    def _cfg(key: str):
        def get(parsed: dict):
            return (parsed.get("config") or {}).get(key)

        return get

    def _rate(phase: str):
        def get(parsed: dict):
            rates = (parsed.get("config") or {}).get("rates_per_s")
            return (rates or {}).get(phase)

        return get

    gates = [
        ("headline", new.get("unit", ""), _headline),
        ("dealing DEM", "pairs-sealed/s", _cfg("pairs_sealed_per_s")),
        ("deal phase", "pairs/s", _rate("deal")),
        ("fiat_shamir", "pairs/s", _rate("fiat_shamir")),
    ]
    bad = 0
    for label, unit, extract in gates:
        old_v, new_v = extract(old), extract(new)
        if not (
            isinstance(old_v, (int, float)) and old_v > 0
            and isinstance(new_v, (int, float)) and new_v > 0
        ):
            print(
                f"perf_regress: {label} metric absent in r{old_n} or "
                f"r{new_n} — skipping this gate"
            )
            continue
        change = (new_v - old_v) / old_v
        line = (
            f"perf_regress: {label} r{old_n} {old_v:.1f} -> r{new_n} "
            f"{new_v:.1f} {unit} ({change:+.1%}) on {new_plat}"
        )
        if change < -args.threshold:
            print(f"{line} — REGRESSION beyond {args.threshold:.0%}", file=sys.stderr)
            bad = 1
        else:
            print(line)
    # wire bytes gate the OPPOSITE way from the rate gates: the serde
    # layer makes ceremony traffic deterministic at a given (n, t), so
    # GROWTH beyond the threshold means a protocol change silently
    # fattened the wire — a cost the fleet pays n*(n-1) times over.
    old_w, new_w = _cfg("wire_bytes")(old), _cfg("wire_bytes")(new)
    if (
        isinstance(old_w, (int, float)) and old_w > 0
        and isinstance(new_w, (int, float)) and new_w > 0
    ):
        change = (new_w - old_w) / old_w
        line = (
            f"perf_regress: wire bytes r{old_n} {int(old_w)} -> r{new_n} "
            f"{int(new_w)} B/ceremony ({change:+.1%})"
        )
        if change > args.threshold:
            print(
                f"{line} — WIRE GROWTH beyond {args.threshold:.0%}",
                file=sys.stderr,
            )
            bad = 1
        else:
            print(line)
    else:
        print(
            f"perf_regress: wire_bytes absent in r{old_n} or r{new_n} "
            "— skipping the wire gate"
        )
    # newer rounds embed a process-wide metrics snapshot alongside the
    # parsed line; acknowledge it so its presence is visibly tolerated,
    # but never gate on it (telemetry, not a benchmark)
    snap = new.get("metrics")
    if isinstance(snap, dict):
        n_series = sum(
            len(v) for v in snap.values() if isinstance(v, dict)
        )
        print(
            f"perf_regress: r{new_n} carries a metrics snapshot "
            f"({n_series} series) — passed through, not gated"
        )
    _runtime_drift(old, new, old_n, new_n)
    return bad or fleet_bad


def _runtime_drift(old: dict, new: dict, old_n: int, new_n: int) -> None:
    """Soft warning (never a gate) when compiles_total rose between two
    rounds at IDENTICAL config flags: a warm rerun of the same program
    set should compile strictly less, so a rise means the persistent
    compile cache regressed or a shape started churning (ROADMAP item 5
    evidence).  Rounds without a ``runtime`` block — everything before
    the introspection layer — are tolerated silently."""
    old_rt, new_rt = old.get("runtime"), new.get("runtime")
    if not isinstance(new_rt, dict):
        return
    n_comp = new_rt.get("compiles_total")
    print(
        f"perf_regress: r{new_n} carries a runtime block "
        f"({n_comp} compiles, cache {new_rt.get('cache_hits')}h/"
        f"{new_rt.get('cache_misses')}m) — passed through, not gated"
    )
    if not isinstance(old_rt, dict):
        return
    if (old.get("config") or {}).get("flags") != (new.get("config") or {}).get(
        "flags"
    ):
        return  # different knobs legitimately compile different programs
    o_comp = old_rt.get("compiles_total")
    if (
        isinstance(o_comp, (int, float))
        and isinstance(n_comp, (int, float))
        and n_comp > o_comp
    ):
        print(
            f"perf_regress: WARNING compiles_total rose r{old_n} "
            f"{int(o_comp)} -> r{new_n} {int(n_comp)} at identical flags "
            "— compile-cache regression or shape churn (soft warning, "
            "not gated)"
        )


def _slo_gate(root: pathlib.Path) -> int:
    """Serving-SLO judgment of the newest FLEET/SVCSTORM/SIGN rounds
    (scripts/slo_gate.py).  Loaded by path so this script keeps working
    from any cwd (tests import it the same way); a missing or broken
    slo_gate module skips with a note rather than failing history-less
    checkouts."""
    gate_path = pathlib.Path(__file__).resolve().parent / "slo_gate.py"
    try:
        spec = importlib.util.spec_from_file_location("slo_gate", gate_path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        bad = mod.run_gate(root)
    except Exception as exc:  # noqa: BLE001 — the gate must not brick history-less runs
        print(f"perf_regress: slo_gate unavailable ({exc}) — skipping")
        return 0
    if bad:
        print(f"perf_regress: slo_gate reports {bad} violation(s)", file=sys.stderr)
        return 1
    return 0


def _load_fleet_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, fleet report) for every usable fleet round,
    ascending — usable means the service leg completed and reports a
    positive throughput."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("FLEET_r*.json")):
        m = _FLEET_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        service = (doc.get("service") or {}) if isinstance(doc, dict) else {}
        rate = service.get("ceremonies_per_s")
        if not isinstance(rate, (int, float)) or rate <= 0:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def fleet_gate(root: pathlib.Path, threshold: float) -> int:
    """Diff the newest two fleet rounds: throughput must not DROP and
    tail latency must not RISE beyond the threshold."""
    rounds = _load_fleet_rounds(root)
    if len(rounds) < 2:
        print(
            f"perf_regress: {len(rounds)} usable fleet round(s) in {root} "
            "— nothing to diff"
        )
        return 0
    (old_n, old), (new_n, new) = rounds[-2], rounds[-1]
    for key in ("platform", "concurrency", "batch_max"):
        old_v, new_v = old.get(key), new.get(key)
        if old_v != new_v:
            print(
                f"perf_regress: fleet r{old_n} ({key}={old_v}) vs "
                f"r{new_n} ({key}={new_v}) measured different service "
                "shapes — incomparable, skipping"
            )
            return 0
    bad = 0
    old_s, new_s = old.get("service", {}), new.get("service", {})
    # throughput gates on DROPS, latency on RISES — sign-flipped checks
    for label, unit, worse_sign in (
        ("ceremonies_per_s", "ceremonies/s", -1),
        ("p99_s", "s", +1),
    ):
        old_v, new_v = old_s.get(label), new_s.get(label)
        if not (
            isinstance(old_v, (int, float)) and old_v > 0
            and isinstance(new_v, (int, float)) and new_v > 0
        ):
            print(
                f"perf_regress: fleet {label} absent in r{old_n} or "
                f"r{new_n} — skipping this gate"
            )
            continue
        change = (new_v - old_v) / old_v
        line = (
            f"perf_regress: fleet {label} r{old_n} {old_v:.3f} -> "
            f"r{new_n} {new_v:.3f} {unit} ({change:+.1%})"
        )
        if worse_sign * change > threshold:
            print(
                f"{line} — REGRESSION beyond {threshold:.0%}",
                file=sys.stderr,
            )
            bad = 1
        else:
            print(line)
    # cold-start gate: warmup_s RISING is a regression — the AOT
    # executable store (service/aot.py) took process warmup from
    # minutes of recompiles to seconds of deserializes, and a quiet
    # slide back (store misses, digest skew, a widened warm set) must
    # fail here, not resurface as FLEET_r01's 222.6s
    old_wu, new_wu = old.get("warmup_s"), new.get("warmup_s")
    if (
        isinstance(old_wu, (int, float)) and old_wu > 0
        and isinstance(new_wu, (int, float)) and new_wu > 0
    ):
        change = (new_wu - old_wu) / old_wu
        line = (
            f"perf_regress: fleet warmup_s r{old_n} {old_wu:.1f} -> "
            f"r{new_n} {new_wu:.1f} s ({change:+.1%})"
        )
        if change > threshold:
            print(
                f"{line} — COLD-START REGRESSION beyond {threshold:.0%}",
                file=sys.stderr,
            )
            bad = 1
        else:
            print(line)
    else:
        print(
            f"perf_regress: fleet warmup_s absent in r{old_n} or "
            f"r{new_n} — skipping the cold-start gate"
        )
    # wire growth gates like p99: RISES are regressions (the mix is
    # pinned by the shape keys above, so per-ceremony average traffic
    # only moves when the protocol's wire format does)
    old_w = (old.get("wire") or {}).get("bytes_per_ceremony_avg")
    new_w = (new.get("wire") or {}).get("bytes_per_ceremony_avg")
    if (
        isinstance(old_w, (int, float)) and old_w > 0
        and isinstance(new_w, (int, float)) and new_w > 0
    ):
        change = (new_w - old_w) / old_w
        line = (
            f"perf_regress: fleet wire r{old_n} {old_w:.0f} -> "
            f"r{new_n} {new_w:.0f} B/ceremony ({change:+.1%})"
        )
        if change > threshold:
            print(
                f"{line} — WIRE GROWTH beyond {threshold:.0%}",
                file=sys.stderr,
            )
            bad = 1
        else:
            print(line)
    else:
        print(
            f"perf_regress: fleet wire bytes absent in r{old_n} or "
            f"r{new_n} — skipping the wire gate"
        )
    return bad


def _load_epoch_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, epoch report) for every usable epoch round,
    ascending — usable means a positive refresh throughput."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("EPOCH_r*.json")):
        m = _EPOCH_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        rate = doc.get("refreshes_per_s") if isinstance(doc, dict) else None
        if not isinstance(rate, (int, float)) or rate <= 0:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def epoch_gate(root: pathlib.Path, threshold: float) -> int:
    """Diff the newest two epoch rounds: refresh throughput must not
    DROP beyond the threshold.  Reshare wall-clock is printed but not
    gated (single-op wall time is noise-bound on shared hosts)."""
    rounds = _load_epoch_rounds(root)
    if len(rounds) < 2:
        print(
            f"perf_regress: {len(rounds)} usable epoch round(s) in {root} "
            "— nothing to diff"
        )
        return 0
    (old_n, old), (new_n, new) = rounds[-2], rounds[-1]
    for key in ("platform", "curve", "n", "t"):
        old_v, new_v = old.get(key), new.get(key)
        if old_v != new_v:
            print(
                f"perf_regress: epoch r{old_n} ({key}={old_v}) vs "
                f"r{new_n} ({key}={new_v}) measured different shapes "
                "— incomparable, skipping"
            )
            return 0
    old_v, new_v = old.get("refreshes_per_s"), new.get("refreshes_per_s")
    change = (new_v - old_v) / old_v
    line = (
        f"perf_regress: epoch refreshes_per_s r{old_n} {old_v:.3f} -> "
        f"r{new_n} {new_v:.3f} refreshes/s ({change:+.1%})"
    )
    bad = 0
    if change < -threshold:
        print(f"{line} — REGRESSION beyond {threshold:.0%}", file=sys.stderr)
        bad = 1
    else:
        print(line)
    rw_old, rw_new = old.get("reshare_wall_s"), new.get("reshare_wall_s")
    if isinstance(rw_old, (int, float)) and isinstance(rw_new, (int, float)):
        print(
            f"perf_regress: epoch reshare_wall_s r{old_n} {rw_old:.3f} -> "
            f"r{new_n} {rw_new:.3f} s — informational, not gated"
        )
    return bad


def _load_sign_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, sign report) for every usable signing round,
    ascending — usable means at least one correct shape with a positive
    partial rate."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("SIGN_r*.json")):
        m = _SIGN_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        shapes = doc.get("shapes") if isinstance(doc, dict) else None
        if not isinstance(shapes, list):
            continue
        usable = [
            s
            for s in shapes
            if isinstance(s, dict)
            and s.get("correct")
            and isinstance(s.get("partials_per_s"), (int, float))
            and s["partials_per_s"] > 0
        ]
        if not usable:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def sign_gate(root: pathlib.Path, threshold: float) -> int:
    """Diff the newest two signing rounds per (curve, n, messages)
    shape: ``partials_per_s`` must not DROP beyond the threshold.
    Proof/aggregate rates print but never gate."""
    rounds = _load_sign_rounds(root)
    if len(rounds) < 2:
        print(
            f"perf_regress: {len(rounds)} usable sign round(s) in {root} "
            "— nothing to diff"
        )
        return 0
    (old_n, old), (new_n, new) = rounds[-2], rounds[-1]
    if old.get("platform") != new.get("platform"):
        print(
            f"perf_regress: sign r{old_n} ({old.get('platform')}) vs "
            f"r{new_n} ({new.get('platform')}) ran on different platforms "
            "— incomparable, skipping"
        )
        return 0
    if bool(old.get("pallas")) != bool(new.get("pallas")):
        print(
            f"perf_regress: sign r{old_n} (pallas={bool(old.get('pallas'))}) "
            f"vs r{new_n} (pallas={bool(new.get('pallas'))}) measured "
            "different kernel tiers — incomparable, skipping"
        )
        return 0

    def by_shape(doc: dict) -> dict:
        return {
            (s.get("curve"), s.get("n"), s.get("messages")): s
            for s in doc.get("shapes", [])
            if isinstance(s, dict) and s.get("correct")
        }

    olds, news = by_shape(old), by_shape(new)
    bad = 0
    matched = False
    for key in sorted(olds.keys() & news.keys(), key=str):
        old_v = olds[key].get("partials_per_s")
        new_v = news[key].get("partials_per_s")
        if not (
            isinstance(old_v, (int, float)) and old_v > 0
            and isinstance(new_v, (int, float)) and new_v > 0
        ):
            continue
        matched = True
        change = (new_v - old_v) / old_v
        curve, n, b = key
        line = (
            f"perf_regress: sign {curve} n={n} B={b} partials_per_s "
            f"r{old_n} {old_v:.1f} -> r{new_n} {new_v:.1f} ({change:+.1%})"
        )
        if change < -threshold:
            print(f"{line} — REGRESSION beyond {threshold:.0%}", file=sys.stderr)
            bad = 1
        else:
            print(line)
    if not matched:
        print(
            f"perf_regress: sign r{old_n} and r{new_n} share no usable "
            "shapes — nothing to diff"
        )
    bad |= _steady_gate(old_n, old, new_n, new, threshold)
    return bad


def _steady_gate(
    old_n: int, old: dict, new_n: int, new: dict, threshold: float
) -> int:
    """Gate ``steady_state.signatures_per_s`` — the sign lane's warm
    throughput headline — between the newest two rounds.  Rounds that
    predate steady-state mode (no block) skip with a note; shape
    mismatches (different curve/n/batch) are incomparable and skip."""

    def usable(doc: dict) -> dict | None:
        s = doc.get("steady_state")
        if (
            isinstance(s, dict)
            and s.get("correct")
            and isinstance(s.get("signatures_per_s"), (int, float))
            and s["signatures_per_s"] > 0
        ):
            return s
        return None

    old_s, new_s = usable(old), usable(new)
    if old_s is None or new_s is None:
        which = f"r{old_n}" if old_s is None else f"r{new_n}"
        print(
            f"perf_regress: sign {which} carries no usable steady_state "
            "block (predates --steady mode?) — steady gate skipped"
        )
        return 0
    old_key = (old_s.get("curve"), old_s.get("n"), old_s.get("batch"))
    new_key = (new_s.get("curve"), new_s.get("n"), new_s.get("batch"))
    if old_key != new_key:
        print(
            f"perf_regress: sign steady shapes differ "
            f"(r{old_n} {old_key} vs r{new_n} {new_key}) "
            "— incomparable, skipping"
        )
        return 0
    old_v, new_v = old_s["signatures_per_s"], new_s["signatures_per_s"]
    change = (new_v - old_v) / old_v
    curve, n, batch = new_key
    line = (
        f"perf_regress: sign steady {curve} n={n} batch={batch} "
        f"signatures_per_s r{old_n} {old_v:.1f} -> r{new_n} {new_v:.1f} "
        f"({change:+.1%})"
    )
    if change < -threshold:
        print(f"{line} — REGRESSION beyond {threshold:.0%}", file=sys.stderr)
        return 1
    print(line)
    return 0


def _load_svcstorm_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, storm report) for every usable storm round,
    ascending — usable means the convoy leg ran a positive number of
    requests (an infra-dead round skips rather than blocks)."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("SVCSTORM_r*.json")):
        m = _SVCSTORM_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        convoy = (doc.get("convoy") or {}) if isinstance(doc, dict) else {}
        reqs = convoy.get("requests")
        if not isinstance(reqs, int) or reqs <= 0:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def svcstorm_gate(root: pathlib.Path) -> int:
    """Floor-check the NEWEST storm round (no diff: resilience is an
    invariant, not a rate).  Survival, bit-identity, typed poisoning,
    and blame accuracy must all be perfect; signing blame must stay
    within its logarithmic pass bound."""
    rounds = _load_svcstorm_rounds(root)
    if not rounds:
        print(f"perf_regress: no usable storm round in {root} — skipping")
        return 0
    new_n, doc = rounds[-1]
    convoy = doc.get("convoy") or {}
    sign = doc.get("sign") or {}
    bad = 0

    def floor(label: str, ok: bool, detail: str) -> None:
        nonlocal bad
        line = f"perf_regress: storm r{new_n} {label}: {detail}"
        if ok:
            print(line)
        else:
            print(f"{line} — RESILIENCE FLOOR VIOLATED", file=sys.stderr)
            bad = 1

    survival = convoy.get("survival_rate")
    floor(
        "survival_rate",
        survival == 1.0,
        f"{survival!r} over {convoy.get('requests')} requests",
    )
    healthy = convoy.get("healthy")
    identical = convoy.get("healthy_bit_identical")
    floor(
        "healthy bit-identity",
        isinstance(healthy, int) and identical == healthy,
        f"{identical!r}/{healthy!r} masters match the fault-free leg",
    )
    poisoned = convoy.get("poisoned")
    typed = convoy.get("poisoned_typed")
    floor(
        "typed poisoning",
        isinstance(poisoned, int) and typed == poisoned,
        f"{typed!r}/{poisoned!r} poisoned requests got PoisonedRequest",
    )
    blame = convoy.get("blame_accuracy")
    floor("blame accuracy", blame == 1.0, f"{blame!r}")
    if sign:
        floor(
            "sign blame cells",
            bool(sign.get("blamed_cells_exact")),
            f"exact={sign.get('blamed_cells_exact')!r}",
        )
        passes, bound = sign.get("passes"), sign.get("pass_bound")
        floor(
            "sign pass bound",
            isinstance(passes, int)
            and isinstance(bound, int)
            and passes <= bound,
            f"{passes!r} passes vs bound {bound!r}",
        )
        floor(
            "sign substitute signature",
            bool(sign.get("substitute_sig_bit_identical")),
            f"bit_identical={sign.get('substitute_sig_bit_identical')!r}",
        )
    else:
        print(
            f"perf_regress: storm r{new_n} has no sign leg — convoy "
            "floors only"
        )
    return bad


def _load_fleetstorm_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, fleet-storm report) for every usable round,
    ascending — usable means the storm accepted a positive number of
    seeded ceremonies (an infra-dead round skips rather than blocks)."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("FLEETSTORM_r*.json")):
        m = _FLEETSTORM_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        cer = (doc.get("ceremonies") or {}) if isinstance(doc, dict) else {}
        reqs = cer.get("requests")
        if not isinstance(reqs, int) or reqs <= 0:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def fleetstorm_gate(root: pathlib.Path) -> int:
    """Floor-check the NEWEST fleet-storm round (scripts/fleet_storm.py)
    in the SVCSTORM style: worker-failover resilience is an invariant,
    not a rate.  Hard floors — >=100 accepted seeded ceremonies under
    >=2 worker kills (one mid-ceremony, one mid-recovery) plus pipe
    garbage and journal tail corruption; ZERO accepted ceremonies lost;
    every recovered master bit-identical to the fault-free reference;
    crash-loop quarantine counts exact."""
    rounds = _load_fleetstorm_rounds(root)
    if not rounds:
        print(f"perf_regress: no usable fleet-storm round in {root} — skipping")
        return 0
    new_n, doc = rounds[-1]
    cer = doc.get("ceremonies") or {}
    faults = doc.get("faults") or {}
    quarantine = doc.get("quarantine") or {}
    bad = 0

    def floor(label: str, ok: bool, detail: str) -> None:
        nonlocal bad
        line = f"perf_regress: fleetstorm r{new_n} {label}: {detail}"
        if ok:
            print(line)
        else:
            print(f"{line} — RESILIENCE FLOOR VIOLATED", file=sys.stderr)
            bad = 1

    reqs = cer.get("requests")
    floor(
        "workload",
        isinstance(reqs, int) and reqs >= 100,
        f"{reqs!r} accepted seeded ceremonies (need >= 100)",
    )
    lost = cer.get("lost")
    floor("zero loss", lost == 0, f"{lost!r} accepted ceremonies lost")
    mid_c = faults.get("kills_mid_ceremony")
    mid_r = faults.get("kills_mid_recovery")
    floor(
        "worker kills",
        isinstance(mid_c, int)
        and isinstance(mid_r, int)
        and mid_c >= 1
        and mid_r >= 1,
        f"{mid_c!r} mid-ceremony + {mid_r!r} mid-recovery (need >= 1 each)",
    )
    garbage = faults.get("pipe_garbage")
    floor(
        "pipe garbage",
        isinstance(garbage, int) and garbage >= 1,
        f"{garbage!r} garbled frames injected",
    )
    torn = faults.get("journal_corrupted")
    floor(
        "journal corruption",
        isinstance(torn, int) and torn >= 1,
        f"{torn!r} slot-journal tails corrupted",
    )
    rec = cer.get("recovered") or {}
    rcount, rident = rec.get("count"), rec.get("bit_identical")
    floor(
        "recovered bit-identity",
        isinstance(rcount, int) and rcount >= 1 and rident == rcount,
        f"{rident!r}/{rcount!r} recovered masters match the fault-free leg",
    )
    q_exp, q_obs = quarantine.get("expected"), quarantine.get("observed")
    floor(
        "quarantine count",
        isinstance(q_exp, int) and q_obs == q_exp,
        f"{q_obs!r}/{q_exp!r} slots quarantined",
    )
    floor("overall", doc.get("ok") is True, f"ok={doc.get('ok')!r}")
    return bad


def _load_northstar_rounds(root: pathlib.Path) -> list[tuple[int, dict]]:
    """(round number, report) for every usable north-star round,
    ascending — usable means the run actually measured something
    (``wall_s`` > 0); an infra-dead round skips rather than blocks."""
    out: list[tuple[int, dict]] = []
    for path in sorted(root.glob("NORTHSTAR_r*.json")):
        m = _NORTHSTAR_PAT.search(path.name)
        if not m:
            continue
        try:
            doc = json.loads(path.read_text())
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(doc, dict):
            continue
        wall = doc.get("wall_s")
        if not isinstance(wall, (int, float)) or wall <= 0:
            continue
        out.append((int(m.group(1)), doc))
    out.sort(key=lambda t: t[0])
    return out


def northstar_gate(root: pathlib.Path, threshold: float) -> int:
    """Gate the north-star sharded-ceremony history.

    FLOOR on the newest round: ``bit_exact_vs_unsharded`` must be true
    — a sharded ceremony that drifts from the single-chip engine is
    wrong whatever its speed.  DIFF newest-two: ``wall_s`` must not
    RISE more than ``threshold`` at a matching
    (curve, n, t, mesh_shape, platform) key; a different rung or a
    different box is incomparable and skips with a note.
    """
    rounds = _load_northstar_rounds(root)
    if not rounds:
        print(f"perf_regress: no usable north-star round in {root} — skipping")
        return 0
    new_n, new = rounds[-1]
    bad = 0
    if not new.get("bit_exact_vs_unsharded"):
        print(
            f"perf_regress: northstar r{new_n} sharded ceremony is NOT "
            f"bit-exact vs unsharded at shape "
            f"{new.get('bit_exact_shape')!r} — CORRECTNESS FLOOR VIOLATED",
            file=sys.stderr,
        )
        bad = 1
    else:
        print(
            f"perf_regress: northstar r{new_n} bit-exact vs unsharded "
            f"at shape {new.get('bit_exact_shape')!r}"
        )
    if len(rounds) < 2:
        print(
            f"perf_regress: {len(rounds)} usable north-star round(s) in "
            f"{root} — nothing to diff"
        )
        return bad

    def key(doc: dict) -> tuple:
        return (
            doc.get("curve"),
            doc.get("n"),
            doc.get("t"),
            tuple(doc.get("mesh_shape") or ()),
            doc.get("platform"),
        )

    old_n, old = rounds[-2]
    old_key, new_key = key(old), key(new)
    if old_key != new_key:
        print(
            f"perf_regress: northstar shapes differ "
            f"(r{old_n} {old_key} vs r{new_n} {new_key}) "
            "— incomparable, skipping the wall gate"
        )
        return bad
    old_v, new_v = old["wall_s"], new["wall_s"]
    change = (new_v - old_v) / old_v
    curve, n, t, mesh_shape, platform = new_key
    line = (
        f"perf_regress: northstar {curve} n={n} t={t} "
        f"mesh={list(mesh_shape)} wall_s r{old_n} {old_v:.3f} -> "
        f"r{new_n} {new_v:.3f} ({change:+.1%}) on {platform}"
    )
    if change > threshold:
        print(f"{line} — REGRESSION beyond {threshold:.0%}", file=sys.stderr)
        bad = 1
    else:
        print(line)
    return bad


if __name__ == "__main__":
    sys.exit(main())
