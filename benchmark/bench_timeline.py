"""The program's convoy timeline, cut to the window: the arithmetic of the readers
that need more than a histogram's sum (a tail, a stall, the time the chip was left
unfed).  Everything here works on plain records, so it is tested on hand-made lists.

A record is what `dkg_tpu.utils.tracing.TIMELINE` keeps, one a convoy, assembled by
the scheduler where the convoy ends (`service/scheduler.py` `_book_timeline`):
`convoy`, `slot`, `bucket`, `width`, `popped`, `spans` = `(phase, start, end)` of
every stage in the order they closed (`convoy.<stage>`, service/engine.py
`CONVOY_STAGES`), `members` = `(ceremony id, admitted, completed, status)`.  All times
are on the program's `time.perf_counter()`, and so is `"at"` of the registry's
snapshots that `run.py` takes at each edge of the window (`ctx["counters"]`): the
window is `before["at"]` to `after["at"]`, all 51 s and the open loop's drain, not the
traced slice.  Readers run after the scheduler has drained, so a convoy in flight
when the window closed is in the ring too, with its true end.

A program without the ring or without `"at"` (the parent of the PR that added them)
gives `window()` None, and every reader then returns None: the metric is left out.
So does a ring that wrapped inside the window (it says so): the oldest of a full
ring's records ended after the window began, so earlier ones may be lost.

* a **request** is a member with status `done` that completed inside the window;
  its latency is `completed - admitted`, and `split()` divides it with nothing left
  over into `queue` (admission to the pop), each stage of its convoy, and `rest`
  (pop to the first span, between spans, `_finish_outcomes`);
* a convoy **feeds** the device from each `<x>_dispatch`'s start to the end of the
  `<x>_wait` after it (deal, digest, verify, finalise), and through `encode`, which is
  a device round trip (PERF.md section 3): outside every convoy's fed intervals
  nobody has a program dispatched and unfetched, so the chip idles for want of work;
* a **stall** is a stretch in which at least one request was admitted and
  unfinished and no request completed.
"""

from __future__ import annotations

import collections

from bench_spans import HOLD_STAGES, HOST_STAGES, WAIT_STAGES
from bench_stats import percentile
from bench_trace import _union

PREFIX = "convoy."
FED_PAIRS = {"deal", "digest", "verify", "finalise"}
FED_WHOLE = ("encode",)


def note(msg: str) -> None:
    print(f"[timeline] {msg}", flush=True)


def cut(ring: list[dict], depth: int, t_from: float) -> list[dict] | None:
    """The ring's records, or None where the ring may have lost a record of the window
    that began at `t_from`: it is full and its oldest record ended inside the window."""
    if len(ring) >= depth:
        oldest = max(m[2] for m in ring[0]["members"])
        if oldest >= t_from:
            note(
                f"the ring of {depth} convoys wrapped inside the window: its oldest record ended "
                f"{oldest - t_from:.3f}s after the window began; nothing is read"
            )
            return None
    return ring


def window(ctx: dict) -> tuple[list[dict], float, float] | None:
    """(records, window start, window end) from the program's ring and the harness's snapshots."""
    from dkg_tpu.utils import tracing

    timeline = getattr(tracing, "TIMELINE", None)
    t_from, t_to = (ctx["counters"][edge].get("at") for edge in ("before", "after"))
    if timeline is None or t_from is None or t_to is None:
        return None
    records = cut(timeline.snapshot(), tracing.TIMELINE_DEPTH, t_from)
    return None if records is None else (records, t_from, t_to)


def _stage_seconds(record: dict) -> dict[str, float]:
    seconds: dict[str, float] = collections.defaultdict(float)
    for phase, start, end in record["spans"]:
        seconds[phase.removeprefix(PREFIX)] += end - start
    return seconds


def split(records: list[dict], t_from: float, t_to: float) -> list[dict]:
    """One row a request of the window: `latency`, `queue`, `rest`, every stage of its
    convoy under the stage's name, and the convoy's `bucket` and `width`; seconds."""
    rows = []
    for rec in records:
        stages = _stage_seconds(rec)
        staged = sum(stages.values())
        for _, admitted, completed, status in rec["members"]:
            if status != "done" or not t_from <= completed <= t_to:
                continue
            latency, queue = completed - admitted, rec["popped"] - admitted
            rows.append(
                {**stages, "latency": latency, "queue": queue, "rest": latency - queue - staged,
                 "bucket": rec["bucket"], "width": rec["width"]}
            )
    return rows


def parts(row: dict) -> dict[str, float]:
    """A request's latency in five parts.  `rest` is what the other four
    leave, so the five add up to the latency whatever stages a later engine adds."""
    out = {
        "queue": row["queue"],
        "hold": sum(row.get(s, 0.0) for s in HOLD_STAGES),
        "device_wait": sum(row.get(s, 0.0) for s in WAIT_STAGES),
        "host": sum(row.get(s, 0.0) for s in HOST_STAGES),
    }
    out["rest"] = row["latency"] - sum(out.values())
    return out


def tail(rows: list[dict]) -> list[dict]:
    """The requests at or above the window's p95 latency (nearest rank)."""
    p95 = percentile([r["latency"] for r in rows], 0.95)
    return [] if p95 is None else [r for r in rows if r["latency"] >= p95]


def mean(rows: list[dict], key) -> float | None:
    """Mean of `key(row)` (a column's name, or a function of the row) over `rows`."""
    if not rows:
        return None
    pick = key if callable(key) else (lambda r: r.get(key, 0.0))
    return sum(pick(r) for r in rows) / len(rows)


def tail_part_ms(ctx: dict, part: str, columns: tuple[str, ...]) -> float | None:
    """What the five `tail_*` readers share: the tail's mean of `part` in ms, and one
    line of the log with the part's `columns` beside the window's means of the same."""
    cutout = window(ctx)
    if cutout is None:
        return None
    rows = split(*cutout)
    worst = tail(rows)
    if not worst:
        return None
    value = mean(worst, lambda r: parts(r)[part])
    table = ", ".join(
        f"{c} {mean(worst, c) * 1e3:.3f} / {mean(rows, c) * 1e3:.3f}" for c in columns
    )
    note(
        f"tail {part}: {value * 1e3:.3f} ms of the tail's mean latency {mean(worst, 'latency') * 1e3:.3f} ms "
        f"({len(worst)} of {len(rows)} requests at or above the p95; the window's mean request "
        f"{mean(rows, lambda r: parts(r)[part]) * 1e3:.3f} of {mean(rows, 'latency') * 1e3:.3f}); "
        f"ms, tail / window: {table}"
    )
    return value * 1e3


def fed_intervals(record: dict) -> list[tuple[float, float]]:
    """The stretches in which `record`'s convoy had a program dispatched and unfetched."""
    out, opened = [], {}
    for phase, start, end in record["spans"]:
        stage = phase.removeprefix(PREFIX)
        head, _, kind = stage.rpartition("_")
        if head in FED_PAIRS and kind == "dispatch":
            opened[head] = start
        elif head in FED_PAIRS and kind == "wait":
            out.append((opened.pop(head, start), end))
        elif stage in FED_WHOLE:
            out.append((start, end))
    return out


def unfed(records: list[dict], t_from: float, t_to: float) -> list[tuple[float, float]]:
    """The stretches of `t_from`..`t_to` inside no convoy's fed interval, in time order."""
    fed = _union(
        [
            (max(a, t_from), min(b, t_to))
            for rec in records
            for a, b in fed_intervals(rec)
            if b > t_from and a < t_to
        ]
    )
    gaps, at = [], t_from
    for a, b in fed:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t_to > at:
        gaps.append((at, t_to))
    return gaps


def unfed_share(records: list[dict], t_from: float, t_to: float) -> float | None:
    if t_to <= t_from:
        return None
    return sum(b - a for a, b in unfed(records, t_from, t_to)) / (t_to - t_from)


def stalls(records: list[dict], t_from: float, t_to: float) -> list[tuple[float, float]]:
    """Every stretch of `t_from`..`t_to` in which a request was admitted and unfinished and
    none completed: it begins with a completion that leaves a request outstanding, or
    with an admission that finds none, and ends with the next completion (or the
    window).  Members of any status count: a request that failed was waited for too."""
    events = []
    for rec in records:
        for _, admitted, completed, _ in rec["members"]:
            events += [(admitted, 1), (completed, -1)]
    events.sort(key=lambda e: (e[0], -e[1]))
    out, outstanding, since = [], 0, None

    def close(until: float) -> None:
        a, b = max(since, t_from), min(until, t_to)
        if b > a:
            out.append((a, b))

    for t, step in events:
        if step > 0:
            if outstanding == 0:
                since = t
            outstanding += 1
            continue
        close(t)
        outstanding -= 1
        since = t if outstanding else None
    if since is not None:
        close(t_to)
    return out


def slots_during(records: list[dict], a: float, b: float) -> str:
    """For the log: what each worker slot's thread was in for most of `a`..`b`: a stage
    with its convoy's number, bucket and width, or `between convoys` where it ran no
    span (`hold` is left out: it is a convoy waiting, not its worker working)."""
    by_slot: dict = collections.defaultdict(lambda: collections.defaultdict(float))
    for rec in records:
        for phase, start, end in rec["spans"]:
            stage = phase.removeprefix(PREFIX)
            overlap = min(end, b) - max(start, a)
            if overlap > 0 and stage not in HOLD_STAGES:
                by_slot[rec["slot"]][(stage, rec["convoy"], rec["bucket"], rec["width"])] += overlap
    said = []
    for slot in sorted({rec["slot"] for rec in records}, key=str):
        spent = by_slot[slot]
        between = (b - a) - sum(spent.values())
        (stage, convoy, bucket, width), longest = max(spent.items(), key=lambda kv: kv[1], default=((None,) * 4, 0.0))
        if between > longest:
            said.append(f"slot {slot}: between convoys {between / (b - a):.0%}")
        else:
            said.append(f"slot {slot}: {stage} {longest / (b - a):.0%} (convoy {convoy}, {bucket} x{width})")
    return "; ".join(said) if said else "no convoy on record"
