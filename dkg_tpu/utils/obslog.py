"""Ceremony flight recorder: structured JSONL events + Chrome trace export.

Every interesting transition in a ceremony — round head/tail, publish,
RPC retry, quarantine, timeout, WAL replay, injected fault — is one
JSON object with monotonic (``mono``) and wall (``ts``) timestamps and
``ceremony_id``/``party``/``round`` identity fields.  Events land in a
bounded in-memory ring (:class:`ObsLog`) and, when the ``DKG_TPU_OBSLOG``
env knob names a directory, in one append-mode JSONL file per party so a
chaos failure can be replayed from its logs alone.

Redaction is structural, not best-effort: the recorder NEVER accepts
share or key material — instrumentation sites only pass lengths, counts,
indices, and error kinds — and as belt-and-braces every ``bytes`` value
reaching :meth:`ObsLog.emit` is replaced by its length before
serialization.  ``tests/test_obslog.py`` greps the emitted bytes of a
live ceremony for the committee's secrets to prove it.

Channel and fault code run deep inside transport internals where no
recorder handle exists; they emit through an *ambient* recorder
(:func:`use` / :func:`emit_current`) that ``run_party`` binds for the
duration of its party thread.  The binding is a
:class:`contextvars.ContextVar`, not a ``threading.local``: threaded
callers see identical behavior (each thread starts from the unbound
default), but an async scheduler multiplexing many ceremonies on ONE
event loop (dkg_tpu.service) gets per-task isolation for free —
``asyncio`` snapshots the context per task, so two interleaved
ceremonies on the same thread cannot cross-contaminate each other's
streams (tests/test_obslog.py interleaves two recorders on one thread
to pin this).

:func:`to_chrome_trace` merges any number of per-party logs into one
Chrome/Perfetto trace-event JSON: one process per ceremony, one thread
per party, ``phase_span`` spans as complete ("X") slices with
``subtimings_s`` nested under them, and point events as instants.
``scripts/trace_viz.py`` is the CLI wrapper.
"""

from __future__ import annotations

import contextvars
import gzip
import json
import os
import threading
import time
import zlib
from collections import deque
from typing import Any, Iterable

from . import envknobs

# The ambient recorder binding.  A ContextVar instead of threading.local:
# identical semantics for plain threads (every thread starts unbound),
# but copyable per asyncio task / contextvars.Context, so one scheduler
# thread interleaving several ceremonies keeps their streams separate.
_AMBIENT: contextvars.ContextVar["ObsLog | None"] = contextvars.ContextVar(
    "dkg_tpu_obslog", default=None
)


def _sanitize(value: Any) -> Any:
    """Replace bytes payloads with their length, recursively.  The
    instrumentation contract is lengths-only already; this makes an
    accidental ``payload=raw`` emit a harmless ``"bytes:N"``."""
    if isinstance(value, (bytes, bytearray, memoryview)):
        return f"bytes:{len(value)}"
    if isinstance(value, dict):
        return {str(k): _sanitize(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_sanitize(v) for v in value]
    return value


class ObsLog:
    """Bounded ring of structured events with an optional JSONL file sink.

    ``ceremony_id`` and ``party`` bind once at construction and stamp
    every event; ``party`` is an int member index or ``"hub"``.
    """

    def __init__(
        self,
        capacity: int = 4096,
        path: str | os.PathLike | None = None,
        ceremony_id: str | None = None,
        party: int | str | None = None,
    ) -> None:
        self.ceremony_id = ceremony_id
        self.party = party
        self._ring: deque[dict] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._path = os.fspath(path) if path is not None else None
        self._fh = open(self._path, "a", encoding="utf-8") if self._path else None

    # -- recording ----------------------------------------------------------

    def emit(self, kind: str, *, round: int | None = None, **fields) -> dict:
        """Record one event; returns the event dict (tests poke at it)."""
        ev: dict[str, Any] = {
            "ts": time.time(),
            "mono": time.monotonic(),
            "kind": kind,
        }
        if self.ceremony_id is not None:
            ev["ceremony_id"] = self.ceremony_id
        if self.party is not None:
            ev["party"] = self.party
        if round is not None:
            ev["round"] = round
        for k, v in fields.items():
            ev[k] = _sanitize(v)
        with self._lock:
            self._ring.append(ev)
            if self._fh is not None:
                self._fh.write(json.dumps(ev, sort_keys=True) + "\n")
                self._fh.flush()
        return ev

    def emit_span(
        self,
        name: str,
        *,
        ts0: float,
        mono0: float,
        dur_s: float,
        subs: dict[str, float] | None = None,
        **fields,
    ) -> dict:
        """Record a completed span (``phase_span`` feeds these): start
        timestamps, duration, and optional sub-phase seconds that the
        trace export renders as nested slices."""
        span_fields: dict[str, Any] = {
            "name": name,
            "ts0": ts0,
            "mono0": mono0,
            "dur_s": dur_s,
        }
        if subs:
            span_fields["subs"] = {k: float(v) for k, v in subs.items()}
        span_fields.update(fields)
        return self.emit("span", **span_fields)

    # -- access -------------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._ring)

    @property
    def path(self) -> str | None:
        return self._path

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self) -> "ObsLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


# -- ambient (context-local) recorder ----------------------------------------


class _Use:
    """Context manager binding ``log`` as the current context's ambient
    recorder; ``use(None)`` is a no-op binding (events are dropped).
    Bindings nest: exit restores whatever was bound on entry."""

    def __init__(self, log: ObsLog | None) -> None:
        self._log = log
        self._token: contextvars.Token | None = None

    def __enter__(self) -> ObsLog | None:
        self._token = _AMBIENT.set(self._log)
        return self._log

    def __exit__(self, *exc) -> None:
        if self._token is not None:
            _AMBIENT.reset(self._token)
            self._token = None


def use(log: ObsLog | None) -> _Use:
    return _Use(log)


def current() -> ObsLog | None:
    """The current context's ambient recorder, or None."""
    return _AMBIENT.get()


def emit_current(kind: str, *, round: int | None = None, **fields) -> dict | None:
    """Emit into the ambient recorder if one is bound; else drop."""
    log = current()
    if log is None:
        return None
    return log.emit(kind, round=round, **fields)


# -- construction helpers ----------------------------------------------------


def ceremony_id_for(env) -> str:
    """Deterministic short id for a ceremony Environment: all parties of
    one ceremony derive the same id from the (group, n, t, commitment
    key) tuple, so their logs merge onto one timeline."""
    import hashlib

    h = hashlib.blake2b(digest_size=6)
    h.update(env.group.name.encode())
    h.update(f":{env.nr_members}:{env.threshold}:".encode())
    h.update(env.group.encode(env.commitment_key.h))
    return h.hexdigest()


def from_env(
    *,
    ceremony_id: str | None = None,
    party: int | str | None = None,
    capacity: int = 4096,
) -> ObsLog | None:
    """An :class:`ObsLog` with a file sink under the ``DKG_TPU_OBSLOG``
    directory, or None when the knob is unset.  File name is
    ``{ceremony_id}-p{party:03d}.jsonl`` (``-hub.jsonl`` for the hub)."""
    root = envknobs.string("DKG_TPU_OBSLOG", "flight-recorder log directory")
    if root is None:
        return None
    os.makedirs(root, exist_ok=True)
    cid = ceremony_id if ceremony_id is not None else "proc"
    tag = f"p{party:03d}" if isinstance(party, int) else str(party or "proc")
    path = os.path.join(root, f"{cid}-{tag}.jsonl")
    return ObsLog(capacity=capacity, path=path, ceremony_id=ceremony_id, party=party)


# -- event schema ------------------------------------------------------------

#: The pinned flight-recorder event schema (docs/observability.md, "Event
#: schema").  Every event carries the base fields ``ts``/``mono``/``kind``
#: (plus ``ceremony_id``/``party``/``round`` when bound); per-kind entries
#: list the REQUIRED payload fields and the OPTIONAL extras.  ``None`` for
#: the optional set marks an open kind (runtimeobs and service events whose
#: payloads vary by probe).  scripts/forensics.py and to_chrome_trace parse
#: exactly this schema — tests/test_obslog.py conformance-checks a live
#: ceremony's stream against it, so an emit-site drift fails loudly.
EVENT_SCHEMA: dict[str, dict[str, tuple | None]] = {
    # ceremony data plane (net.party / net.channel / net.faults)
    "round_head": {"required": ("round",), "optional": ()},
    "publish": {"required": ("round", "bytes", "seq"), "optional": ()},
    "round_tail": {
        "required": (
            "round", "present", "senders", "quarantined_delta", "timed_out",
        ),
        "optional": (),
    },
    "quarantine": {"required": ("round", "peer"), "optional": ()},
    "rpc_retry": {
        "required": ("attempt", "error", "backoff_s", "op"), "optional": (),
    },
    "budget_clamp": {"required": ("where", "timeout_s"), "optional": ("round",)},
    "fault_injected": {
        "required": ("round", "fault", "sender"), "optional": ("seconds",),
    },
    "abort": {"required": ("error", "drain_from"), "optional": ()},
    "party_done": {
        "required": (
            "ok", "quarantined", "timeouts", "retries", "resumes",
            "wal_records", "replayed_rounds",
        ),
        "optional": (),
    },
    # durability (net.checkpoint via net.party)
    "wal_record": {"required": ("round", "bytes", "terminal"), "optional": ()},
    "wal_resume": {"required": ("replayed_rounds",), "optional": ()},
    # epoch data plane (epoch.manager) — publish/tail mirror the ceremony
    # kinds field-for-field so forensics parses one format
    "epoch_head": {
        "required": ("round", "op", "step", "op_kind"), "optional": (),
    },
    "epoch_publish": {"required": ("round", "bytes", "seq"), "optional": ()},
    "epoch_tail": {
        "required": ("round", "present", "senders", "timed_out"),
        "optional": (),
    },
    "epoch_quarantine": {"required": ("round", "peer"), "optional": ()},
    "epoch_wal_record": {"required": ("op", "step", "bytes"), "optional": ()},
    "epoch_done": {
        "required": ("op", "op_kind", "status"), "optional": ("epoch",),
    },
    # hub side (net.channel TcpHub)
    "hub_rpc": {
        "required": ("op", "dur_s", "bytes_in", "bytes_out"), "optional": (),
    },
    "hub_junk_frame": {"required": ("reason",), "optional": ("op",)},
    # spans (tracing.phase_span / service scheduler).  The sign lane's
    # ``sign_convoy`` spans annotate the convoy composition: curve,
    # request/message/ceremony counts, proved flag, flush reason, and
    # how many tickets ended in error.  The ceremony lane's ``convoy``
    # spans name the convoy (sequence number, width, bucket, worker
    # slot), its members (``ceremonies`` is there the list of their ids,
    # ``queue_wait_s`` each one's seconds queued, in the same order) and
    # carry the stage seconds of service/engine.CONVOY_STAGES as subs,
    # and as ``spans`` every stage's interval, ``[stage, start, end]`` in
    # seconds from ``ts0``, in the order they closed.
    "span": {
        "required": ("name", "ts0", "mono0", "dur_s"),
        "optional": (
            "subs", "spans", "curve", "requests", "messages", "ceremonies",
            "proved", "reason", "errors",
            "convoy", "width", "bucket", "slot", "queue_wait_s",
        ),
    },
    # open kinds: payload varies by probe/deployment (utils.runtimeobs,
    # dkg_tpu.service) — base-field conformance only
    "jax_compile": {"required": (), "optional": None},
    "counter_sample": {"required": (), "optional": None},
    "jax_cost_probe": {"required": (), "optional": None},
    "http_error": {"required": (), "optional": None},
    "service_fault_injected": {"required": (), "optional": None},
}

#: Base fields every event may carry regardless of kind.
_SCHEMA_BASE = ("ts", "mono", "kind", "ceremony_id", "party", "round")


def validate_events(
    events: Iterable[dict], *, allow_unknown: bool = False
) -> list[str]:
    """Check events against :data:`EVENT_SCHEMA`; returns a list of
    human-readable problems (empty = conformant).  Unknown kinds are
    errors unless ``allow_unknown`` (service deployments add their own
    ``service_*`` kinds); ``None``-valued fields satisfy presence (e.g.
    ``fault_injected.seconds`` for non-delay faults)."""
    problems: list[str] = []
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            problems.append(f"event #{i}: not a dict")
            continue
        kind = ev.get("kind")
        where = f"event #{i} ({kind!r})"
        for base in ("ts", "mono", "kind"):
            if base not in ev:
                problems.append(f"{where}: missing base field {base!r}")
        spec = EVENT_SCHEMA.get(kind) if isinstance(kind, str) else None
        if spec is None:
            if not allow_unknown:
                problems.append(f"{where}: unknown kind")
            continue
        for req in spec["required"]:
            if req not in ev:
                problems.append(f"{where}: missing required field {req!r}")
        optional = spec["optional"]
        if optional is None:
            continue  # open kind: any extras allowed
        allowed = set(_SCHEMA_BASE) | set(spec["required"]) | set(optional)
        for k in ev:
            if k not in allowed:
                problems.append(f"{where}: unexpected field {k!r}")
    return problems


# -- timeline export ---------------------------------------------------------


def load_jsonl(path: str | os.PathLike) -> list[dict]:
    """Events from one JSONL log; malformed lines are skipped (a crash
    mid-write must not poison the whole timeline).  ``.gz`` paths are
    read through gzip — chaos/fleet runs compress their sinks."""
    p = os.fspath(path)
    opener = gzip.open if p.endswith(".gz") else open
    out: list[dict] = []
    with opener(p, "rt", encoding="utf-8") as fh:
        try:
            for line in fh:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line)
                except (json.JSONDecodeError, UnicodeDecodeError):
                    continue
                if isinstance(ev, dict):
                    out.append(ev)
        except (EOFError, OSError, zlib.error):
            pass  # torn gzip tail: keep every line that decoded
    return out


#: Dedicated thread id for the per-process "jax compile" track — far
#: above any real party index so it sorts last in the timeline.
_JAX_COMPILE_TID = 9999


def _tid(ev: dict) -> int:
    party = ev.get("party")
    return party + 1 if isinstance(party, int) else 0


def to_chrome_trace(events: Iterable[dict]) -> dict:
    """Merge flight-recorder events (any number of parties/ceremonies)
    into Chrome trace-event JSON (load via chrome://tracing or Perfetto).

    Mapping: one *process* per ceremony_id, one *thread* per party (the
    hub is tid 0); ``span`` events become complete ("X") slices with
    their ``subs`` rendered as nested child slices laid out sequentially
    from the parent's start (a span that carries ``spans``, its stages'
    own intervals, has each child where it ran instead); runtimeobs ``jax_compile`` events become
    "X" slices on a dedicated per-process "jax compile" thread (so
    compiles visibly overlap — or starve — ceremony phases);
    ``counter_sample`` events become Chrome counter ("C") tracks; every
    other kind becomes an instant ("i").  Wall-clock timestamps align
    events across OS processes — parties of one chaos restart run land
    on one coherent timeline.
    """
    events = [ev for ev in events if isinstance(ev, dict)]
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def wall0(ev: dict) -> float:
        # spans carry their start time; point events their emit time
        return ev.get("ts0", ev.get("ts", 0.0))

    t0 = min(wall0(ev) for ev in events)
    pids: dict[str, int] = {}
    compile_tids: set[int] = set()
    trace: list[dict] = []
    for ev in events:
        cid = str(ev.get("ceremony_id", "proc"))
        if cid not in pids:
            pids[cid] = len(pids) + 1
            trace.append(
                {
                    "name": "process_name",
                    "ph": "M",
                    "pid": pids[cid],
                    "tid": 0,
                    "args": {"name": f"ceremony {cid}"},
                }
            )
        pid, tid = pids[cid], _tid(ev)
        args = {
            k: v
            for k, v in ev.items()
            if k
            not in ("ts", "mono", "ts0", "mono0", "dur_s", "kind", "name",
                    "ceremony_id", "party", "subs", "spans")
        }
        if ev.get("kind") == "span":
            start_us = (wall0(ev) - t0) * 1e6
            dur_us = float(ev.get("dur_s", 0.0)) * 1e6
            trace.append(
                {
                    "name": str(ev.get("name", "span")),
                    "ph": "X",
                    "pid": pid,
                    "tid": tid,
                    "ts": start_us,
                    "dur": dur_us,
                    "args": args,
                }
            )
            # nested sub-slices: where the span kept its stages' own
            # intervals, each where it ran; else laid out back-to-back
            # from the parent start
            if ev.get("spans"):
                placed = [
                    (sub, start_us + float(a) * 1e6, (float(b) - float(a)) * 1e6)
                    for sub, a, b in ev["spans"]
                ]
            else:
                placed, sub_ts = [], start_us
                for sub, sec in (ev.get("subs") or {}).items():
                    placed.append((sub, sub_ts, float(sec) * 1e6))
                    sub_ts += float(sec) * 1e6
            for sub, sub_ts, sub_dur in placed:
                trace.append(
                    {
                        "name": f"{ev.get('name', 'span')}.{sub}",
                        "ph": "X",
                        "pid": pid,
                        "tid": tid,
                        "ts": sub_ts,
                        "dur": sub_dur,
                        "args": {},
                    }
                )
        elif ev.get("kind") == "jax_compile":
            # runtimeobs compile-stage events: their own thread per
            # process, so recompiles read as a parallel track next to
            # the ceremony phases they delay
            if pid not in compile_tids:
                compile_tids.add(pid)
                trace.append(
                    {
                        "name": "thread_name",
                        "ph": "M",
                        "pid": pid,
                        "tid": _JAX_COMPILE_TID,
                        "args": {"name": "jax compile"},
                    }
                )
            trace.append(
                {
                    "name": f"compile/{ev.get('stage', '?')}",
                    "ph": "X",
                    "pid": pid,
                    "tid": _JAX_COMPILE_TID,
                    "ts": (wall0(ev) - t0) * 1e6,
                    "dur": float(ev.get("dur_s", 0.0)) * 1e6,
                    "args": args,
                }
            )
        elif ev.get("kind") == "counter_sample":
            # runtimeobs memory watermarks (and any future sampled
            # gauges): Chrome counter tracks, one per counter name
            trace.append(
                {
                    "name": str(ev.get("counter", "counter")),
                    "ph": "C",
                    "pid": pid,
                    "tid": 0,
                    "ts": (wall0(ev) - t0) * 1e6,
                    "args": {"value": ev.get("value", 0)},
                }
            )
        else:
            trace.append(
                {
                    "name": str(ev.get("kind", "event")),
                    "ph": "i",
                    "pid": pid,
                    "tid": tid,
                    "ts": (wall0(ev) - t0) * 1e6,
                    "s": "t",
                    "args": args,
                }
            )
    trace.extend(_flow_events(events, pids, t0))
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


def _flow_events(events: list[dict], pids: dict[str, int], t0: float) -> list[dict]:
    """Synthesize Perfetto flow events (``ph: s/f``) linking each publish
    to every fetch of it: a ``round_tail``/``epoch_tail`` lists the
    ``senders`` it received, so one start anchored at the publish plus
    one finish per fetching tail renders the (ceremony_id, round,
    sender, seq) correlation key as arrows in the timeline.  Synthesized
    at export time — live emission would cost O(n^2) events per round."""
    pubkinds = {"publish": "round_tail", "epoch_publish": "epoch_tail"}
    # (cid, tailkind, round, party) -> publish event; first wins, matching
    # the channel's first-publish-wins semantics (resume republishes)
    pubs: dict[tuple, dict] = {}
    for ev in events:
        tailkind = pubkinds.get(ev.get("kind"))
        if tailkind is None or not isinstance(ev.get("party"), int):
            continue
        key = (
            str(ev.get("ceremony_id", "proc")), tailkind, ev.get("round"),
            ev["party"],
        )
        pubs.setdefault(key, ev)
    out: list[dict] = []
    for ev in events:
        if ev.get("kind") not in ("round_tail", "epoch_tail"):
            continue
        cid = str(ev.get("ceremony_id", "proc"))
        for sender in ev.get("senders") or ():
            pub = pubs.get((cid, ev["kind"], ev.get("round"), sender))
            if pub is None:
                continue  # log set missing this publisher's sink
            # one flow (unique id) per (publish, fetcher) pair — a chrome
            # flow id binds exactly one start to one finish
            fid = (
                f"{cid}:{ev['kind']}:{ev.get('round')}:{sender}"
                f":{pub.get('seq')}->{ev.get('party')}"
            )
            common = {
                "name": f"r{ev.get('round')} publish p{sender}",
                "cat": "flow",
                "pid": pids[cid],
                "id": fid,
            }
            out.append(
                {
                    **common,
                    "ph": "s",
                    "tid": _tid(pub),
                    "ts": (pub.get("ts", 0.0) - t0) * 1e6,
                }
            )
            out.append(
                {
                    **common,
                    "ph": "f",
                    "bp": "e",
                    "tid": _tid(ev),
                    "ts": (ev.get("ts", 0.0) - t0) * 1e6,
                }
            )
    return out


# -- critical-path forensics -------------------------------------------------


def _round_windows(evs: list[dict]) -> dict[int, dict]:
    """Per-round raw material for one ceremony's merged event list:
    head/tail/publish timestamps plus the per-party retry and
    injected-delay attributions."""
    rounds: dict[int, dict] = {}

    def bucket(r) -> dict | None:
        if not isinstance(r, int):
            return None
        return rounds.setdefault(
            r, {"heads": [], "tails": [], "pubs": {}, "timed_out": False}
        )

    for ev in evs:
        kind = ev.get("kind")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        if kind == "round_head":
            b = bucket(ev.get("round"))
            if b is not None:
                b["heads"].append(ts)
        elif kind == "round_tail":
            b = bucket(ev.get("round"))
            if b is not None:
                b["tails"].append(ev)
                if ev.get("timed_out"):
                    b["timed_out"] = True
        elif kind == "publish":
            b = bucket(ev.get("round"))
            party = ev.get("party")
            if b is not None and isinstance(party, int):
                # first-publish-wins, matching the channel semantics
                b["pubs"].setdefault(party, ts)
    return rounds


def _attributed(
    evs: list[dict], party, lo: float, hi: float, round_no: int
) -> tuple[float, float]:
    """(retry_s, fault_s) chargeable to ``party`` inside the wall-clock
    window [lo, hi]: recorded RPC backoff sleeps plus injected delay
    faults for this round, each charged to the window it was noted in
    (a straggler that also closes the round is not charged its delay a
    second time after its publish).  Each sum is clamped to the window
    width — attribution can never exceed the time it is explaining."""
    width = max(0.0, hi - lo)
    retry = fault = 0.0
    for ev in evs:
        if ev.get("party") != party:
            continue
        kind = ev.get("kind")
        ts = ev.get("ts", 0.0)
        if kind == "rpc_retry" and lo <= ts <= hi:
            retry += float(ev.get("backoff_s") or 0.0)
        elif (
            kind == "fault_injected"
            and ev.get("fault") == "delay"
            and ev.get("round") == round_no
            and ev.get("seconds") is not None
            and lo <= ts <= hi
        ):
            fault += float(ev.get("seconds"))
    retry = min(retry, width)
    fault = min(fault, width - retry)
    return retry, fault


def critical_path(events: Iterable[dict], registry=None) -> list[dict]:
    """Reconstruct each ceremony round's barrier and attribute it.

    Merges any number of per-party logs (wall-clock ``ts`` aligns them,
    as in :func:`to_chrome_trace`) and reports, per (ceremony_id, round):

    * ``barrier_s`` — first ``round_head`` to last ``round_tail``;
    * ``straggler`` — the last party to publish (or the absent party a
      timed-out round waited for), with ``straggler_lag_s`` = how long
      the round waited for it (round open -> its publish);
    * a decomposition ``compute_s + transport_s + retry_s +
      quarantine_s == barrier_s`` **exactly** (the buckets partition the
      barrier by construction): the leg up to the straggler's publish is
      compute time net of its recorded retries and injected delays, the
      leg after it is fetch/transport time net of the closing fetcher's
      retries; retry backoffs land in ``retry_s``, injected-fault delays
      and time spent waiting on an absent (crashed/timed-out) straggler
      land in ``quarantine_s``.

    ``registry`` (a MetricsRegistry) receives one
    ``net_round_straggler_lag_seconds{ceremony_id,round,straggler}``
    gauge per round for the SLO layer.  scripts/forensics.py is the CLI.
    """
    by_cid: dict[str, list[dict]] = {}
    for ev in events:
        if isinstance(ev, dict):
            by_cid.setdefault(str(ev.get("ceremony_id", "proc")), []).append(ev)
    report: list[dict] = []
    for cid in sorted(by_cid):
        evs = by_cid[cid]
        committee = {
            ev["party"] for ev in evs if isinstance(ev.get("party"), int)
        }
        for r, b in sorted(_round_windows(evs).items()):
            if not b["tails"]:
                continue  # round never closed anywhere: no barrier to explain
            t_close = max(ev["ts"] for ev in b["tails"])
            closer = max(b["tails"], key=lambda ev: ev["ts"]).get("party")
            opens = b["heads"] or list(b["pubs"].values())
            if not opens:
                continue
            t_open = min(opens)
            barrier = max(0.0, t_close - t_open)
            absent = sorted(committee - set(b["pubs"]))
            if b["pubs"]:
                last_pub = max(b["pubs"], key=lambda p: b["pubs"][p])
            else:
                last_pub = None
            if absent and b["timed_out"]:
                # the round closed on timeout waiting for a party that
                # never published: IT is the straggler, and the whole
                # wait is chargeable to its absence
                straggler, s_absent, pub_ts = absent[0], True, t_close
            elif last_pub is None:
                continue
            else:
                straggler, s_absent = last_pub, False
                pub_ts = min(max(b["pubs"][last_pub], t_open), t_close)
            # leg 1: round open -> straggler publish (its compute path)
            retry1, fault1 = _attributed(evs, straggler, t_open, pub_ts, r)
            leg1 = pub_ts - t_open
            resid1 = max(0.0, leg1 - retry1 - fault1)
            # leg 2: straggler publish -> slowest fetcher's close
            retry2, fault2 = _attributed(evs, closer, pub_ts, t_close, r)
            leg2 = t_close - pub_ts
            resid2 = max(0.0, leg2 - retry2 - fault2)
            entry = {
                "ceremony_id": cid,
                "round": r,
                "barrier_s": barrier,
                "straggler": straggler,
                "straggler_absent": s_absent,
                "straggler_lag_s": leg1,
                "compute_s": 0.0 if s_absent else resid1,
                "transport_s": resid2,
                "retry_s": retry1 + retry2,
                "quarantine_s": fault1 + fault2 + (resid1 if s_absent else 0.0),
                "timed_out": b["timed_out"],
                "present": max(ev.get("present", 0) for ev in b["tails"]),
                "expected": len(committee),
            }
            report.append(entry)
            if registry is not None:
                registry.set_gauge(
                    "net_round_straggler_lag_seconds",
                    leg1,
                    ceremony_id=cid,
                    round=r,
                    straggler=straggler,
                )
    return report
