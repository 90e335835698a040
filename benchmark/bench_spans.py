"""Deltas of the program's histograms around the window, for the readers of its
own spans: the stage spans of a convoy (`dkg_phase_seconds{phase="convoy.<stage>"}`,
from `tracing.phase_span` in service/engine.py and `convoy.hold` from
service/scheduler.py) and the scheduler's series.  `counters` is `ctx["counters"]`:
the registry's snapshots before and after the whole window.  A program without a
series (the parent of the PR that added the spans) gives no observations, and the
readers then return None and the metric is left out.  On the TPU `convoy.encode`,
counted with the host stages, holds a device round trip (`gd.encode_batch` runs
`affine_canon` on the master keys and waits for it): PERF.md section 5."""

from __future__ import annotations

HOST_STAGES = (
    "draw", "deal_dispatch", "digest_dispatch", "rho_fold", "verify_dispatch",
    "blame", "finalise_dispatch", "encode",
)
WAIT_STAGES = ("deal_wait", "digest_wait", "verify_wait", "finalise_wait")
HOLD_STAGES = ("hold",)


def hist_delta(counters: dict, name: str, **labels: str) -> tuple[float, int]:
    """(seconds, observations) added in the window over every series of the
    histogram `name` whose labels include `labels`."""
    wanted = [f'{k}="{v}"' for k, v in labels.items()]
    before = counters["before"].get("histograms", {})
    total, count = 0.0, 0
    for series, h in counters["after"].get("histograms", {}).items():
        base, _, rest = series.partition("{")
        if base != name or not all(w in rest for w in wanted):
            continue
        was = before.get(series, {"sum": 0.0, "count": 0})
        total += h["sum"] - was["sum"]
        count += h["count"] - was["count"]
    return total, count


def stage_ms_per_convoy(counters: dict, stages: tuple[str, ...]) -> float | None:
    """Milliseconds a convoy spends in `stages`: for each stage, its seconds in the
    window over the convoys that passed it in the window (its own observations;
    for `blame`, which runs only after a failed check, those of `verify_wait`
    before it), added up.  Not the seconds over the convoys that *finished*: a
    closed loop's window ends with some seven convoys in flight, their early
    stages booked and their end not, and that read 101.4 % of the mean convoy
    (PR 25, PERF.md).  None where no convoy finished in the window."""
    if not hist_delta(counters, "dkg_phase_seconds", phase="convoy.encode")[1]:
        return None
    total = 0.0
    for stage in stages:
        seconds, passed = hist_delta(counters, "dkg_phase_seconds", phase=f"convoy.{stage}")
        if stage == "blame":
            passed = hist_delta(counters, "dkg_phase_seconds", phase="convoy.verify_wait")[1]
        total += seconds / passed if passed else 0.0
    return total * 1e3
