"""Ceremony observability: per-phase wall-clock, counters, profiler hooks.

The reference has no tracing/metrics/logging of any kind (SURVEY §5 —
errors are the only signal).  Here observability is first-class:

* :class:`CeremonyTrace` — structured per-phase timings + protocol
  counters (complaints filed/upheld, disqualifications, reconstructions),
  rendered as one JSON-able dict.
* :func:`phase_span` — context manager timing one phase; nests under a
  trace and (optionally) a ``jax.profiler.TraceAnnotation`` so device
  kernels show up named in TPU profiles.  Every completed span also
  observes the process-wide ``dkg_phase_seconds`` histogram
  (:mod:`~dkg_tpu.utils.metrics`) and, when the calling thread has an
  ambient flight recorder bound (:mod:`~dkg_tpu.utils.obslog`), emits a
  span event carrying the sub-timings accumulated during the phase.
* :func:`book_phase` — what a completed span books (trace entry +
  histogram observation), for time in which nothing ran to annotate.
* :data:`TIMELINE` — the process's last :data:`TIMELINE_DEPTH` convoy
  records (service/scheduler.py assembles one where a convoy ends):
  every stage's start and end, and every member's admission, pop and
  completion, on one clock.  Always on; read when a run ends.
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
import time
from dataclasses import dataclass, field

from . import metrics, obslog, runtimeobs


@dataclass
class CeremonyTrace:
    """Mutable trace of one ceremony run."""

    timings_s: dict = field(default_factory=dict)  # phase -> seconds
    counters: dict = field(default_factory=dict)  # name -> int
    meta: dict = field(default_factory=dict)
    # phase -> {sub -> seconds}; finer-grained than timings_s and kept
    # OUT of it so rates()/total_s never double-count a phase
    subtimings_s: dict = field(default_factory=dict)
    # (phase, start, end) of every span booked, in the order they
    # closed, on time.perf_counter(); timings_s is their sum by phase
    # (a phase that ran twice is two entries here and one there)
    spans: list = field(default_factory=list)

    def bump(self, name: str, by: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + by

    def record(self, phase: str, seconds: float) -> None:
        self.timings_s[phase] = self.timings_s.get(phase, 0.0) + seconds

    def record_sub(self, phase: str, sub: str, seconds: float) -> None:
        """Accumulate a sub-timing under ``phase`` (e.g. the fiat_shamir
        phase splits into ``digest`` and ``rho``)."""
        subs = self.subtimings_s.setdefault(phase, {})
        subs[sub] = subs.get(sub, 0.0) + seconds

    @property
    def total_s(self) -> float:
        return sum(self.timings_s.values())

    def rates(self, units: float) -> dict:
        """units/second for every recorded phase (zero-duration phases
        omitted) — e.g. ``trace.rates(n * (n - 1))`` gives per-phase
        pair-verify rates; one-off phases like ``tables`` (table-build,
        recorded by BatchedCeremony) are naturally separated from the
        steady-state ones by having their own key."""
        return {ph: units / s for ph, s in self.timings_s.items() if s > 0}

    def as_dict(self) -> dict:
        out = {
            "timings_s": dict(self.timings_s),
            "subtimings_s": {k: dict(v) for k, v in self.subtimings_s.items()},
            "total_s": self.total_s,
            "counters": dict(self.counters),
            "meta": dict(self.meta),
        }
        units = self.meta.get("units")
        if isinstance(units, (int, float)) and not isinstance(units, bool) and units > 0:
            out["rates_per_s"] = self.rates(units)
        wire = self.wire_summary()
        if wire is not None:
            out["wire"] = wire
        return out

    def wire_summary(self) -> dict | None:
        """Per-ceremony wire totals derived from the ``net.wire_bytes_*``
        counters the party/epoch publish-and-fetch paths bump, or None
        when this trace saw no transport.  ``bytes_per_pair`` normalizes
        the published payload by the n*(n-1) dealer->recipient pairs
        (meta ``n``) — the unit the O(n*t) data-plane scaling work is
        judged in (ROADMAP item 4)."""
        out_b = self.counters.get("net.wire_bytes_out")
        in_b = self.counters.get("net.wire_bytes_in")
        if out_b is None and in_b is None:
            return None
        wire: dict = {
            "wire_bytes_out": int(out_b or 0),
            "wire_bytes_in": int(in_b or 0),
            "wire_bytes": int(out_b or 0) + int(in_b or 0),
        }
        n = self.meta.get("n")
        if isinstance(n, int) and n > 1:
            wire["bytes_per_pair"] = (out_b or 0) / (n * (n - 1))
        return wire

    def json(self) -> str:
        return json.dumps(self.as_dict(), sort_keys=True)


#: Convoy records the process keeps.  Not a knob: a benchmark window
#: finishes some 2300 convoys at most, and a reader that finds the
#: oldest record younger than its window's start says that the ring
#: wrapped and reads nothing.
TIMELINE_DEPTH = 8192


class Timeline:
    """The last :data:`TIMELINE_DEPTH` convoy records of the process, in
    the order the convoys ended.  A record is plain data (ids, labels
    and times: nothing a request carried), all its times on
    ``time.perf_counter()``:

    * ``convoy`` (the engine's sequence number), ``slot`` (the worker
      that ran it; None off the worker's lane), ``bucket``, ``width``;
    * ``popped``: when the scheduler took its members off the queue;
    * ``spans``: the convoy trace's ``(phase, start, end)`` tuples;
    * ``members``: ``(ceremony id, admitted, completed, status)`` each.

    Process-wide like ``metrics.REGISTRY``, whose ``snapshot()["at"]``
    is on the same clock: two snapshots cut the ring to a window."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=TIMELINE_DEPTH)

    def append(self, record: dict) -> None:
        with self._lock:
            self._ring.append(record)

    def snapshot(self) -> list:
        with self._lock:
            return list(self._ring)

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()


#: The process-wide ring the scheduler appends to.
TIMELINE = Timeline()


def _clock_shift() -> float:
    """``time.perf_counter() - time.monotonic()``: both clocks are
    steady, so their difference is a constant of the process.  Read
    here, once, as the reading of the tightest of a few pairs, so that
    a thread switch between the two reads cannot shift a record."""
    best = None
    for _ in range(8):
        m0 = time.monotonic()
        p = time.perf_counter()
        m1 = time.monotonic()
        if best is None or m1 - m0 < best[0]:
            best = (m1 - m0, p - (m0 + m1) / 2)
    return best[1]


#: Added to a ``time.monotonic()`` stamp, gives the same instant on
#: ``time.perf_counter()``, the clock of the spans and of the records.
MONOTONIC_TO_SPAN_CLOCK = _clock_shift()


# jax.profiler availability, probed once per process: None = unprobed,
# False = unavailable, else the TraceAnnotation class.  phase_span runs
# per round in tight loops; the per-span import-and-try was measurable
# overhead and buried the one-time ImportError cost inside hot paths.
_ANNOTATION_CLS = None


def _annotation_cls():
    global _ANNOTATION_CLS
    if _ANNOTATION_CLS is None:
        try:
            import jax.profiler

            _ANNOTATION_CLS = jax.profiler.TraceAnnotation
        except Exception:  # pragma: no cover - profiler unavailable
            _ANNOTATION_CLS = False
    return _ANNOTATION_CLS


def book_phase(
    trace: CeremonyTrace | None, phase: str, start: float, end: float
) -> None:
    """Book the interval ``start``..``end`` (``time.perf_counter()``) to
    ``phase`` as a completed :func:`phase_span` does: its seconds into
    ``trace.timings_s``, the interval onto ``trace.spans``, and a
    ``dkg_phase_seconds`` observation.  Called directly for time that is
    a phase but holds no work to annotate (a dispatched convoy waiting
    for its worker to come back: ``convoy.hold``, service/scheduler.py)."""
    if trace is not None:
        trace.record(phase, end - start)
        trace.spans.append((phase, start, end))
    metrics.REGISTRY.observe("dkg_phase_seconds", end - start, phase=phase)


@contextlib.contextmanager
def phase_span(trace: CeremonyTrace | None, phase: str, annotate_device: bool = True):
    """Time a phase; also annotates the device profile when jax has a
    profiler available (no-op overhead otherwise)."""
    ann = contextlib.nullcontext()
    if annotate_device:
        cls = _annotation_cls()
        if cls:
            # the convoy's sequence number as the event's metadata (its
            # name stays): the key a profile's host event and a
            # TIMELINE record share
            seq = trace.meta.get("convoy") if trace is not None else None
            ann = (
                cls(f"dkg/{phase}")
                if seq is None
                else cls(f"dkg/{phase}", convoy=seq)
            )
    recorder = obslog.current()
    if recorder is not None and trace is not None:
        subs0 = dict(trace.subtimings_s.get(phase) or {})
    ts0 = time.time()
    t0 = time.perf_counter()
    with ann:
        yield
    t1 = time.perf_counter()
    dt = t1 - t0
    book_phase(trace, phase, t0, t1)
    # device/host memory watermark at the phase boundary (no-op unless
    # runtimeobs is installed; internally throttled)
    runtimeobs.maybe_sample(phase=phase)
    if recorder is not None:
        subs = None
        if trace is not None:
            now = trace.subtimings_s.get(phase) or {}
            subs = {
                k: v - subs0.get(k, 0.0)
                for k, v in now.items()
                if v - subs0.get(k, 0.0) > 0
            }
        recorder.emit_span(phase, ts0=ts0, mono0=t0, dur_s=dt, subs=subs or None)
