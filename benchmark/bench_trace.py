"""From the profiler's trace to numbers: device busy time, operations, modules, idle gaps.

Two steps, so that the second can be tested on a recorded trace:

* `flatten(path)` reads an `.xplane.pb` with `jax.profiler.ProfileData` into
  plain events `{"plane", "line", "name", "start_ns", "dur_ns"}`: every event
  of the device planes, and of the host planes the harness's own
  `bench:` annotations and whatever lasted a millisecond or more.  A device
  operation's name in the trace is its whole HLO text; `short_name` keeps
  the instruction's name and, for a custom call, its target;
* `reduce(events, host_window_s)` turns events into what the metrics read.  An
  operation is booked to the XLA module whose interval holds its start, so
  `fusion.3` of one program is not added to `fusion.3` of another.

The traced window is marked in the trace itself: the harness opens the span
`bench:traced_window` on its own thread as soon as the profiler's start has
returned, and closes it just before it calls the profiler's stop.  The profiler
records for about a millisecond on either side of that span, so a device that is
busy all the time read 100.1-100.2 % of what the host's clock gave for the
window (PR 24, calls 8-9), which no check can take.  So the window is the span,
in the trace's own time, and **every device event is cut to it** before anything
is added up: `busy_s` is the seconds inside the window in which an operation
ran, and cannot pass `window_s`.  Nothing is hidden by that: the same union over
the events as recorded (`busy_raw_s`), what they span (`span_s`) and the
window as the host's clock read it (`host_window_s`) stand beside it in the
result and in the run's log, where a wrong union or a shifted time base shows.
A trace without the span (one not made by the harness) takes what all its
events span as its window, and says so in `window_from`.  The profiler keeps a
bounded number of device events (two 4 s traces of PR 24 both stopped at 6.29
million, 1.1 s of a busy device).  A trace with `DEVICE_EVENT_CAP` events or
more is taken as cut, and its window then ends with its last device operation.

A slice begins and ends inside executions, and the trace shows those shortened.
`module_runs` holds, per XLA module name without its hash, the seconds of every
execution the slice holds whole: on each device the module that starts first
in the window and the one that ends last, which are those it cut, are left out
and kept apart in `module_runs_cut`.

Busy time of a device is the union of the intervals in which an operation
ran on it (its `XLA Ops` line; every line but `Steps` where a plane has no
such line), so nested or overlapping events are not counted twice.  Over
several devices it is the mean.  An idle gap is a stretch between two busy
intervals; it is labelled with the host events that overlapped it most.
"""

from __future__ import annotations

import bisect
import collections
import glob
import os

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SKIP_LINES = ("Steps", MODULES_LINE)
HOST_MIN_NS = 1_000_000
ANNOTATION_PREFIX = "bench:"
WINDOW_MARK = "bench:traced_window"
TARGET_KEY = 'custom_call_target="'
MOSAIC_TARGET = "[tpu_custom_call]"
DEVICE_EVENT_CAP = 6_200_000


def newest_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def short_name(name: str) -> str:
    """`%while.966 = (...) while(...)` -> `while.966`; a custom call keeps its target:
    `custom-call.7[tpu_custom_call]` is a Mosaic kernel, `[AllocateBuffer]` is not."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    head = head.lstrip("%")
    mark = rest.find(TARGET_KEY)
    if mark >= 0:
        start = mark + len(TARGET_KEY)
        head += f"[{rest[start:rest.find(chr(34), start)]}]"
    return head


def flatten(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            for ev in line.events:
                dur = int(ev.duration_ns)
                if not device and dur < HOST_MIN_NS and not ev.name.startswith(ANNOTATION_PREFIX):
                    continue
                events.append(
                    {
                        "plane": plane.name,
                        "line": line.name,
                        "name": short_name(ev.name) if device else ev.name,
                        "start_ns": int(ev.start_ns),
                        "dur_ns": dur,
                    }
                )
    return events


def line_counts(events: list[dict]) -> dict[str, int]:
    """Events per plane and line: what the trace held, for the run's log."""
    return dict(collections.Counter(f"{e['plane']}:{e['line']}" for e in events))


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    merged: list[tuple[int, int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _op_events(events: list[dict]) -> dict[str, list[dict]]:
    """Per device plane, the events that are operations."""
    by_plane: dict[str, list[dict]] = collections.defaultdict(list)
    for ev in events:
        if ev["plane"].startswith(DEVICE_PREFIX):
            by_plane[ev["plane"]].append(ev)
    out = {}
    for plane, evs in by_plane.items():
        ops = [e for e in evs if e["line"] == OPS_LINE]
        out[plane] = ops or [e for e in evs if e["line"] not in SKIP_LINES]
    return out


def _totals(evs, name=lambda e: e["name"]) -> dict[str, dict]:
    acc: dict[str, dict] = {}
    for e in evs:
        slot = acc.setdefault(name(e), {"seconds": 0.0, "count": 0})
        slot["seconds"] += e["dur_ns"] / 1e9
        slot["count"] += 1
    return acc


def is_pallas(name: str) -> bool:
    """A Mosaic kernel's event: the custom call XLA wraps every `pallas_call` in."""
    return name.endswith(MOSAIC_TARGET)


def _module_of(modules: list[dict]):
    """A function from (plane, start_ns) to the module running then, hash stripped."""
    by_plane: dict[str, list[dict]] = collections.defaultdict(list)
    for m in sorted(modules, key=lambda e: e["start_ns"]):
        by_plane[m["plane"]].append(m)
    starts = {plane: [m["start_ns"] for m in ms] for plane, ms in by_plane.items()}

    def lookup(plane: str, start_ns: int) -> str:
        i = bisect.bisect_right(starts.get(plane, []), start_ns) - 1
        if i >= 0:
            m = by_plane[plane][i]
            if start_ns < m["start_ns"] + m["dur_ns"]:
                return module_name(m["name"])
        return "no module"

    return lookup


def _is_device(ev: dict) -> bool:
    return ev["plane"].startswith(DEVICE_PREFIX)


def _end(ev: dict) -> int:
    return ev["start_ns"] + ev["dur_ns"]


def _busy_s(ops: dict[str, list[dict]], lo: int | None = None, hi: int | None = None):
    """Per device plane the seconds some operation ran, and the idle stretches between
    them (with `lo` and `hi`, those at the window's two ends as well)."""
    busy, gaps = [], []
    for evs in ops.values():
        merged = _union([(e["start_ns"], _end(e)) for e in evs])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        edges = [(lo, lo)] * (lo is not None) + merged + [(hi, hi)] * (hi is not None)
        gaps += [(b0, a1) for (_, b0), (a1, _) in zip(edges, edges[1:]) if a1 > b0]
    return busy, gaps


def _cut_to(events: list[dict], lo: int, hi: int) -> list[dict]:
    """Device events cut to [lo, hi]: those outside dropped, those across an edge
    shortened.  Host events stay as they are."""
    out = []
    for e in events:
        if not _is_device(e):
            out.append(e)
            continue
        start, end = max(e["start_ns"], lo), min(_end(e), hi)
        if end <= start:
            continue
        if (start, end) != (e["start_ns"], _end(e)):
            e = dict(e, start_ns=start, dur_ns=end - start)
        out.append(e)
    return out


def reduce(events: list[dict], host_window_s: float | None = None) -> dict:
    raw_ops = _op_events(events)
    cut = sum(1 for e in events if _is_device(e)) >= DEVICE_EVENT_CAP
    all_ops = [e for evs in raw_ops.values() for e in evs]
    span_s = (max(map(_end, events)) - min(e["start_ns"] for e in events)) / 1e9 if events else 0.0
    busy_raw, _ = _busy_s(raw_ops)
    mark = next((e for e in events if e["name"] == WINDOW_MARK and not _is_device(e)), None)
    if mark is not None:
        lo, hi, window_from = mark["start_ns"], _end(mark), "mark"
    else:
        lo = min((e["start_ns"] for e in events), default=0)
        hi, window_from = max(map(_end, events), default=0), "events"
    if cut and all_ops:
        hi = min(hi, max(map(_end, all_ops)))
    device = [e for e in events if _is_device(e)]
    edges = [(min(e["start_ns"] for e in device) - lo) / 1e9, (max(map(_end, device)) - hi) / 1e9] if device else [0.0, 0.0]
    events = _cut_to(events, lo, hi)
    ops = _op_events(events)
    busy, gaps_raw = _busy_s(ops, lo, hi)
    module_events = [e for e in events if _is_device(e) and e["line"] == MODULES_LINE]
    modules = _totals(module_events)
    whole_runs, cut_runs = _runs(module_events)
    module_of = _module_of(module_events)
    op_totals = _totals(
        (e for evs in ops.values() for e in evs),
        lambda e: f"{module_of(e['plane'], e['start_ns'])}/{e['name']}",
    )
    host = [e for e in events if not _is_device(e) and e["name"] != WINDOW_MARK]
    gaps = []
    for start, end in sorted(gaps_raw, key=lambda g: g[0] - g[1])[:10]:
        gaps.append([_label(host, start, end), (end - start) / 1e9])
    n_dev = max(1, len(ops))
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": (hi - lo) / 1e9,
        "window_from": window_from,
        "host_window_s": host_window_s,
        "busy_raw_s": sum(busy_raw) / max(1, len(raw_ops)),
        "span_s": span_s,
        "device_edges_s": edges,
        "cut": cut,
        "devices": len(ops),
        "ops": op_totals,
        "modules": modules,
        "module_runs": whole_runs,
        "module_runs_cut": cut_runs,
        "pallas_s": sum(v["seconds"] for k, v in op_totals.items() if is_pallas(k)) / n_dev,
        "device_ops": [
            [k, v["seconds"]]
            for k, v in sorted(op_totals.items(), key=lambda kv: -kv[1]["seconds"])[:10]
        ],
        "idle_gaps": gaps,
    }


def module_name(event_name: str) -> str:
    """`jit__deal_stack(12332771972938463820)` -> `jit__deal_stack`."""
    return event_name.split("(")[0]


def _runs(module_events: list[dict]) -> tuple[dict[str, list[float]], dict[str, list[float]]]:
    """Seconds of each module execution: those whole inside the slice, and those at its edges."""
    by_plane: dict[str, list[dict]] = collections.defaultdict(list)
    for m in module_events:
        by_plane[m["plane"]].append(m)
    whole: dict[str, list[float]] = collections.defaultdict(list)
    cut: dict[str, list[float]] = collections.defaultdict(list)
    for ms in by_plane.values():
        first = min(ms, key=lambda m: m["start_ns"])
        last = max(ms, key=lambda m: m["start_ns"] + m["dur_ns"])
        for m in ms:
            (cut if m is first or m is last else whole)[module_name(m["name"])].append(m["dur_ns"] / 1e9)
    return dict(whole), dict(cut)


def module_ms(trace: dict | None, name: str) -> float | None:
    """Device milliseconds of one execution of the XLA module named just `name`: the
    mean over the executions the slice holds whole.  Where it holds none whole, the
    longer of those at its edges, a lower bound (the run's log shows which it was)."""
    if trace is None:
        return None
    runs = trace["module_runs"].get(name)
    if runs:
        return sum(runs) / len(runs) * 1e3
    cut = trace["module_runs_cut"].get(name)
    return max(cut) * 1e3 if cut else None


def modules_s(trace: dict, names: tuple[str, ...]) -> float:
    """Device seconds inside the XLA modules named just so, cut executions included."""
    return sum(v["seconds"] for k, v in trace["modules"].items() if module_name(k) in names)


def _label(host: list[dict], start: int, end: int) -> str:
    """The harness span and the other host event that overlap [start, end) most."""

    def best(pool):
        top, top_ns = None, 0
        for e in pool:
            ov = min(end, e["start_ns"] + e["dur_ns"]) - max(start, e["start_ns"])
            if ov > top_ns:
                top, top_ns = e["name"], ov
        return top

    ours = best(e for e in host if e["name"].startswith(ANNOTATION_PREFIX))
    other = best(e for e in host if not e["name"].startswith(ANNOTATION_PREFIX))
    return " | ".join(x for x in (ours, other) if x) or "no host event"
