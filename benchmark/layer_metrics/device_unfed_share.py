"""service/scheduler.py: the share of the window's seconds inside no convoy's fed
interval (`benchmark/bench_timeline.py`: dispatch to the end of its wait, and `encode`):
nobody had a program dispatched and unfetched, so the chip idled for want of work,
whatever the profiler's slice saw.  A lower bound of the device's idle share, over the
whole window.  The log line gives the same share over the window's last
`trace_seconds`, the stretch the profiler's `busy_s` / `window_s` covers, beside that
idle share, and the three longest unfed stretches with what every worker was in."""

from bench_timeline import note, slots_during, unfed, unfed_share, window


def read(ctx: dict) -> float | None:
    cutout = window(ctx)
    if cutout is None:
        return None
    records, t_from, t_to = cutout
    if t_to <= t_from:
        return None
    gaps = unfed(records, t_from, t_to)
    share = sum(b - a for a, b in gaps) / (t_to - t_from)
    end = min(t_to, t_from + ctx["seconds"])
    last = float(ctx["cell"]["trace_seconds"])
    trace = ctx.get("trace")
    idle = None if not trace or not trace["window_s"] else 1.0 - trace["busy_s"] / trace["window_s"]
    note(
        f"device unfed: {share:.6f} of the window's {t_to - t_from:.3f}s; over its last {last}s "
        f"{unfed_share(records, end - last, end)}, where the profiler's idle share is {idle}"
    )
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:3]:
        note(f"unfed {(b - a) * 1e3:.3f} ms at +{a - t_from:.3f}s: {slots_during(records, a, b)}")
    return share
