#!/usr/bin/env python3
"""Run one cell of the benchmark once, in one process that holds the chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Progress goes to earlier lines of standard output; the last line is one JSON
object (`correct`, `attempted`, `failed`, `metrics`, `device`, with
`--trace 1` `breakdown`, and last `compared`: each count of the comparison, all with
the limit 0 but `shares_compared_ceremonies`, at least 1; the same on the last lines of
standard error).  `main` refuses to measure without a TPU;
`run_cell` beneath it is the whole loop and is what the tests rehearse on
the CPU with a tiny cell of their own.

Everything that belongs to one cell, configuration, traffic kind or metric is
a file found by its name under the directories `BENCHMARK.json` lists in
`paths` (first match wins):

* `workloads/<cell>.json`   — the cell: traffic kind and its parameters, `drain_s`,
                              `trace_seconds` (the slice a `--trace 1` run profiles);
* `configs/<config>.json`   — the deployment: curve, mix of committee shapes,
                              scheduler settings, guarantees;
* `traffic/<kind>.py`       — `plan(params, config, seed, seconds)` ->
                              `{"outstanding": int | None, "requests": iterator
                              of (due_s | None, request)}`;
* `end_to_end/<metric>.py`, `layer_metrics/<metric>.py` — `read(ctx)` -> a
  number, or None where there is nothing to read (the metric is then left out).

`ctx`, what a metric reader gets: `seconds` (the window), `setup_s`,
`records` (one per request that counts: `n`, `t`, `due_s`, `sent_s`,
`fetched_s`, `status`, `ok`, `engine_s`, `convoy_s`, `width`; seconds from the
window's start), `late_s` (generator lateness per request), `counters`
(`before`/`after` snapshots of the program's metrics registry around the
window), `trace` (bench_trace.reduce's result, or None), `cell`, `config`.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
REPO = BENCH_DIR.parent
for _p in (str(REPO), str(BENCH_DIR)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

TERMINAL = ("done", "failed", "expired", "poisoned")
POLL_S = 0.002
# Every tick asks the scheduler about the oldest requests in flight, not about all of
# them: a backlog of thousands (an open loop over its knee, or behind a hole of seconds)
# otherwise costs the one interpreter lock half a million `poll` calls a second and slows
# the workers it waits for (PR 41's first sweep: 16 convoys a second where 68 are served).
# Requests finish in the order admitted but for the convoys the workers hold at once
# (4 workers x 2 deep x 8), so every finished request is among these.
POLL_OLDEST = 128


def note(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS:7.1f}s] {msg}", flush=True)


# ---------------------------------------------------------------------------
# discovery: every named thing is a file under one of the manifest's paths
# ---------------------------------------------------------------------------


def load_manifest(path: pathlib.Path) -> tuple[dict, list[pathlib.Path]]:
    manifest = json.loads(path.read_text())
    return manifest, [(path.parent / p).resolve() for p in manifest["paths"]]


def find(roots: list[pathlib.Path], folder: str, name: str, suffix: str) -> pathlib.Path:
    for root in roots:
        hit = root / folder / f"{name}{suffix}"
        if hit.is_file():
            return hit
    raise FileNotFoundError(f"no {folder}/{name}{suffix} under {[str(r) for r in roots]}")


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metrics_for(manifest: dict, group: str, cell: str) -> list[dict]:
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# the loop
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def _aot_store(root: str):
    """The program's deployment setting for its executable store, for this run only."""
    old = os.environ.get("DKG_TPU_AOT_DIR")
    os.environ["DKG_TPU_AOT_DIR"] = os.path.join(root, "dkg_tpu_aot_store")
    try:
        yield os.environ["DKG_TPU_AOT_DIR"]
    finally:
        if old is None:
            del os.environ["DKG_TPU_AOT_DIR"]
        else:
            os.environ["DKG_TPU_AOT_DIR"] = old


def _must_not(aot, runtimeobs) -> dict:
    """What the window must leave unchanged, read from the program's own counters."""
    stats, snap = aot.stats(), runtimeobs.snapshot()
    return {
        "aot_builds": stats["builds"],
        "aot_disk_loads": stats["disk_loads"],
        "aot_errors": stats["errors"],
        "jax_stage_events": sum(v["count"] for v in snap["stages"].values()),
    }


def _warm_sets(config: dict, outstanding: int | None) -> list[tuple[int, int, int]]:
    """(n, t, width) of one throwaway convoy per bucket and convoy width the cell can form."""
    from dkg_tpu.service import buckets

    seen, sets = set(), []
    for m in config["mix"]:
        b = buckets.bucket_for(int(m["n"]), int(m["t"]))
        if b in seen:
            continue
        seen.add(b)
        cap = min(int(config["scheduler"]["batch_max"]), buckets.width_cap(b))
        if outstanding is not None:
            cap = min(cap, outstanding)
        sets += [(b.n, b.t, w) for w in buckets.WIDTHS if w <= cap]
    return sets


def _drive(sched, plan: dict, seconds: float, drain_s: float, to_request, tracer) -> dict:
    """Send the plan's requests and fetch their outcomes, from this one thread.

    Closed plans (`outstanding` set, no due times) stop sending when the
    window closes; what is then in flight is fetched and not counted.  Open
    plans send each request when it is due and count every one, last the whole
    window whatever finishes early, and fail what has not finished `drain_s`
    after it.
    """
    import jax

    from dkg_tpu.service.errors import QueueFullError

    requests, cap = plan["requests"], plan["outstanding"]
    flying: dict[str, dict] = {}
    records, late, uncounted = [], [], 0
    pending = next(requests, None)
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        tracer.tick(now)
        while pending is not None:
            due, req = pending
            if due is None:
                if now >= seconds or len(flying) >= cap:
                    break
            elif due > now:
                break
            rec = {"n": req["n"], "t": req["t"], "request": req}
            rec["due_s"] = time.perf_counter() - t0 if due is None else due
            with jax.profiler.TraceAnnotation("bench:submit"):
                try:
                    cid = sched.submit(to_request(req))
                except QueueFullError:
                    cid = None
            rec["sent_s"] = time.perf_counter() - t0
            late.append(rec["sent_s"] - rec["due_s"] if due is not None else 0.0)
            if cid is None:
                rec.update(status="refused", fetched_s=None, outcome=None)
                records.append(rec)
            else:
                flying[cid] = rec
            with jax.profiler.TraceAnnotation("bench:draw"):
                pending = next(requests, None)
            now = time.perf_counter() - t0
        for cid in [c for c in itertools.islice(flying, POLL_OLDEST) if sched.poll(c) in TERMINAL]:
            rec = flying.pop(cid)
            rec["outcome"] = sched.result(cid, timeout=5.0)
            rec["fetched_s"] = time.perf_counter() - t0
            rec["status"] = rec["outcome"].status
            if cap is not None and rec["fetched_s"] > seconds:
                uncounted += 1
            else:
                records.append(rec)
        now = time.perf_counter() - t0
        closed_done = cap is not None and now >= seconds
        if closed_done or (pending is None and not flying and now >= seconds) or now > seconds + drain_s:
            break
        wake = POLL_S if pending is None or pending[0] is None else min(POLL_S, max(0.0, pending[0] - now))
        with jax.profiler.TraceAnnotation("bench:wait_result"):
            time.sleep(wake)
    if cap is None:
        for rec in flying.values():
            rec.update(status="unfinished", fetched_s=None, outcome=None)
            records.append(rec)
        while pending is not None:  # the drain limit passed with requests still to send
            due, req = pending
            records.append(
                {"n": req["n"], "t": req["t"], "request": req, "due_s": due, "sent_s": None,
                 "status": "unsent", "fetched_s": None, "outcome": None}
            )
            pending = next(requests, None)
    else:
        uncounted += len(flying)
    return {"records": records, "late_s": late, "uncounted": uncounted}


class _Tracer:
    """Profiles the last `span_s` of the window, where asked to.  Stopping the
    profiler blocks this thread for a minute and more while it writes some
    hundred MB, so the slice ends with the window and `tick` only closes its
    mark: the profiler is stopped by `stop`, once the drain has fetched what the
    window left in flight (an open loop at 360/s leaves some forty requests, and
    a stop inside the drain put 42 s without an admission into the readers' window)."""

    def __init__(self, log_dir: pathlib.Path | None, start_s: float, span_s: float) -> None:
        self.log_dir, self.start_s, self.span_s = log_dir, start_s, span_s
        self.began: float | None = None
        self.window_s: float | None = None
        self.mark = None  # the span `bench:traced_window`: the window in the trace's own time

    def tick(self, now: float) -> None:
        if self.log_dir is None or self.window_s is not None:
            return
        import bench_trace
        import jax

        if self.began is None and now >= self.start_s:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            options.enable_hlo_proto = False  # tens of MB a program, read by nothing here
            jax.profiler.start_trace(str(self.log_dir), profiler_options=options)
            self.mark = jax.profiler.TraceAnnotation(bench_trace.WINDOW_MARK)
            self.mark.__enter__()
            self.began = time.perf_counter()
        elif self.began is not None and time.perf_counter() - self.began >= self.span_s:
            self.close()

    def close(self) -> None:
        """The slice's end: what the profiler records after it is cut off by the mark."""
        if self.began is not None and self.window_s is None:
            self.window_s = time.perf_counter() - self.began
            self.mark.__exit__(None, None, None)

    def stop(self) -> None:
        if self.began is None or self.mark is None:
            return
        import jax

        self.close()
        self.mark = None
        jax.profiler.stop_trace()


class _GcWatch:
    """The interpreter's collections inside the window, for one line of the log: every
    thread stands still through a collection, and a full one walks the whole heap, the
    programs' and the outcomes the harness keeps for the comparison.  A callback a
    collection: the generation, when it began (seconds from `t0`) and how long it took."""

    def __init__(self) -> None:
        self.t0 = time.perf_counter()
        self.began = 0.0
        self.count = [0, 0, 0]
        self.long: list[tuple[float, float, int]] = []  # (seconds, began, generation)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self.began = time.perf_counter()
            return
        took = time.perf_counter() - self.began
        self.count[info["generation"]] += 1
        if info["generation"] == 2 or took >= 0.02:
            self.long.append((took, self.began - self.t0, info["generation"]))

    def line(self) -> str:
        worst = sorted(self.long, reverse=True)[:4]
        return (
            f"collections in the window and its drain by generation {self.count}; the longest (and every full one): "
            + (", ".join(f"{took:.3f}s at {at:.3f}s (generation {gen})" for took, at, gen in worst) or "none over 0.02s")
        )


def _judge(records: list[dict], config: dict, seed: int) -> dict:
    """The comparison that decides `correct`: every outcome against the plain reference."""
    import bench_oracle

    rng = random.Random(seed ^ 0x5EED)
    totals: collections.Counter = collections.Counter(
        not_done=0, unqualified=0, complaints=0, master_mismatch=0, share_limbs_off=0
    )
    compared = 0
    for rec in records:
        out = rec.pop("outcome")
        if out is None:
            totals["not_done"] += 1
            rec["ok"] = False
            continue
        parties = sorted(rng.sample(range(1, rec["n"] + 1), int(config["share_check"]["parties"])))
        bad = bench_oracle.check_outcome(rec["request"], out, parties)
        compared += 1
        totals.update(bad)
        rec["ok"] = not any(bad.values())
        rec["engine_s"] = out.seconds
    totals["shares_compared_ceremonies"] = compared
    return dict(totals)


def compared_lines(compared: dict) -> list[str]:
    """Each number the comparison counted beside its limit: every count has the limit
    0 but the ceremonies compared, of which a run needs one."""
    return [
        f"compared {key} = {value} ({'at least 1' if key == 'shares_compared_ceremonies' else 'limit 0'})"
        for key, value in compared.items()
    ]


def _convoys(records: list[dict]) -> None:
    """A convoy's members carry the same float `seconds` (its wall time over its
    width), so counting equals gives back the width and the convoy's time."""
    same = collections.Counter(r["engine_s"] for r in records if r.get("engine_s"))
    for r in records:
        width = same.get(r.get("engine_s"), 0)
        r["width"] = width or None
        r["convoy_s"] = r["engine_s"] * width if width else None


def _pace(records: list[dict], seconds: float, uncounted: int) -> None:
    """The window's completions, convoy by convoy, on a line of the log: where a
    run reads low, it says whether one stretch was lost or the whole run was slower."""
    import bench_stats

    times = bench_stats.convoy_times(records)
    if len(times) < 2:
        return
    gaps = sorted((b - a, a) for a, b in zip(times, times[1:]))
    convoys = collections.Counter(min(9, int(t / seconds * 10)) for t in times)
    note(
        f"pace: {len(records)} ceremonies in {len(times)} convoys counted, {uncounted} ceremonies "
        f"not counted; first completion {times[0]:.3f}s, last {times[-1]:.3f}s; gap between "
        f"completions median {gaps[len(gaps) // 2][0]:.3f}s, longest {gaps[-1][0]:.3f}s at "
        f"{gaps[-1][1]:.3f}s; convoys per tenth of the window {[convoys[i] for i in range(10)]}; "
        f"ceremonies per tenth {bench_stats.tenths(records, seconds)}, their median as a rate "
        f"{bench_stats.pace_median_per_s(records, seconds):.3f}/s"
    )


def _load(records: list[dict], seconds: float) -> None:
    """An open-loop run's own line: whether the rate was held (bench_stats.open_loop_load)."""
    import bench_stats

    load = bench_stats.open_loop_load(records, seconds)
    ms = lambda v: "none" if v is None else f"{v * 1e3:.3f}"  # noqa: E731
    note(
        f"open loop: {len(records)} requests due, {load['refused']} refused, {load['unsent']} unsent, "
        f"{load['unfinished']} unfinished; mean latency of the requests due in each fifth of the window, ms "
        f"[{', '.join(ms(v) for v in load['latency_by_fifth_s'])}]; generator lateness after the first "
        f"second: p99 {ms(load['late_p99_s'])} ms, max {ms(load['late_max_s'])} ms"
    )


def run_cell(
    manifest_path: pathlib.Path,
    workload: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float | None = None,
) -> dict:
    """Set-up, window, comparison, result: one cell, once, on whatever device JAX has."""
    t_start = time.perf_counter() if t_start is None else t_start
    manifest, roots = load_manifest(pathlib.Path(manifest_path))
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"benchmark: no cell {workload!r} in {manifest_path}")
    cell = json.loads(find(roots, "workloads", workload, ".json").read_text())
    config = json.loads(find(roots, "configs", entry["config"], ".json").read_text())
    traffic = load_module(find(roots, "traffic", cell["traffic"]["kind"], ".py"))
    group = "per_layer" if trace else "end_to_end"
    readers = {
        m["name"]: (m, load_module(find(roots, "layer_metrics" if trace else "end_to_end", m["name"], ".py")))
        for m in metrics_for(manifest, group, workload)
    }

    from dkg_tpu.utils import compilecache, runtimeobs

    cache_root = compilecache.enable()
    with _aot_store(cache_root) as store:
        import jax

        from dkg_tpu.service import CeremonyRequest, CeremonyScheduler, WarmRuntime, aot, engine
        from dkg_tpu.utils.metrics import REGISTRY

        devices = jax.devices()
        if len(devices) < int(entry["chips"]):
            raise SystemExit(f"benchmark: {workload} needs {entry['chips']} chips, JAX has {len(devices)}")
        runtimeobs.install(force=True)
        note(f"{workload}: {devices[0].platform} x{len(devices)}, store {store}")

        def to_request(req: dict):
            return CeremonyRequest(
                req["curve"], req["n"], req["t"], seed=req["seed"], rho_bits=req["rho_bits"]
            )

        plan = traffic.plan(cell["traffic"], config, seed, seconds)
        runtime = WarmRuntime()
        tables = runtime.commitment(config["curve"], engine.DEFAULT_SHARED_STRING)
        jax.block_until_ready(tables[1:])
        note(f"tables ready; aot {aot.stats()}")
        for n, t, width in _warm_sets(config, plan["outstanding"]):
            t0 = time.perf_counter()
            warm = [
                to_request({"curve": config["curve"], "n": n, "t": t, "seed": (seed << 24) - 1 - i, "rho_bits": config["rho_bits"]})
                for i in range(width)
            ]
            outs = engine.run_convoy(runtime, warm)
            if any(o.status != "done" for o in outs):
                raise SystemExit(f"benchmark: warm-up convoy ({n},{t}) x{width} failed")
            note(f"warm ({n},{t}) x{width}: {time.perf_counter() - t0:.2f}s; aot {aot.stats()}")

        sched = CeremonyScheduler(runtime=runtime, **config["scheduler"])
        log_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench_trace_")) if trace else None
        span_s = min(float(cell["trace_seconds"]), float(seconds))
        tracer = _Tracer(log_dir, float(seconds) - span_s, span_s)
        try:
            before, counters_before = _must_not(aot, runtimeobs), REGISTRY.snapshot()
            setup_s = time.perf_counter() - t_start
            note(f"set-up {setup_s:.2f}s; window {seconds}s")
            watch = _GcWatch()
            gc.callbacks.append(watch)
            try:
                drive = _drive(sched, plan, float(seconds), float(cell.get("drain_s", 60.0)), to_request, tracer)
            finally:
                gc.callbacks.remove(watch)
            after, counters_after = _must_not(aot, runtimeobs), REGISTRY.snapshot()
        finally:
            tracer.stop()
            sched.close(drain=True)
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use") or 0 for d in devices)

    records = drive["records"]
    t0 = time.perf_counter()
    compared = _judge(records, config, seed)
    _convoys(records)
    for key in before:
        compared[f"window_{key}"] = after[key] - before[key]
    note(f"reference and comparison {time.perf_counter() - t0:.2f}s over {len(records)} outcomes")
    for line in compared_lines(compared):
        note(line)
    correct = compared["shares_compared_ceremonies"] >= 1 and not any(
        value for key, value in compared.items() if key != "shares_compared_ceremonies"
    )
    _pace(records, float(seconds), drive["uncounted"])
    note(watch.line())
    if plan["outstanding"] is None:
        _load(records, float(seconds))
    late = sorted(drive["late_s"])
    if late:
        note(
            f"generator lateness: median {late[len(late) // 2] * 1e3:.3f} ms, "
            f"max {late[-1] * 1e3:.3f} ms over {len(late)} requests; "
            f"{drive['uncounted']} in flight at the end, not counted"
        )

    reduced = None
    if trace and tracer.window_s is not None:
        import bench_trace

        t0 = time.perf_counter()
        events = bench_trace.flatten(bench_trace.newest_xplane(str(log_dir)))
        reduced = bench_trace.reduce(events, tracer.window_s)
        note(f"trace lines: {bench_trace.line_counts(events)}")
        note(
            f"trace reduced in {time.perf_counter() - t0:.1f}s: busy {reduced['busy_s']:.6f}s inside a window of "
            f"{reduced['window_s']:.6f}s (from {reduced['window_from']}; the host's clock read "
            f"{reduced['host_window_s']:.6f}s); as recorded, not cut to the window: busy "
            f"{reduced['busy_raw_s']:.6f}s, all events span {reduced['span_s']:.6f}s, device events from "
            f"{reduced['device_edges_s'][0]:+.6f}s of the window's start to {reduced['device_edges_s'][1]:+.6f}s of its end; "
            f"{reduced['devices']} device plane(s)"
            f"{' (trace cut at the event cap: the window ends with the last device operation)' if reduced['cut'] else ''}; "
            f"modules {reduced['modules']}; whole executions "
            f"{ {k: len(v) for k, v in reduced['module_runs'].items()} }, at the slice's edges "
            f"{ {k: len(v) for k, v in reduced['module_runs_cut'].items()} }"
        )
    if log_dir is not None:
        shutil.rmtree(log_dir, ignore_errors=True)
    ctx = {
        "seconds": float(seconds),
        "setup_s": setup_s,
        "records": records,
        "late_s": drive["late_s"],
        "counters": {"before": counters_before, "after": counters_after},
        "trace": reduced,
        "cell": cell,
        "config": config,
    }
    metrics = {}
    for name, (spec, module) in readers.items():
        value = module.read(ctx)
        if value is not None and math.isfinite(value):
            metrics[name] = {"value": float(value), "unit": spec["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(records),
        "failed": sum(1 for r in records if r["status"] != "done"),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = compared  # last: each number beside its limit (`compared_lines`)
    return result


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    found = [(d.platform, d.device_kind) for d in jax.devices()]
    if not found or found[0][0] != "tpu":
        raise SystemExit(f"benchmark: no TPU, JAX found {found[:1]}; nothing is measured off the chip")
    result = run_cell(
        REPO / "BENCHMARK.json", args.workload, args.seed, args.seconds, bool(args.trace), T_PROCESS
    )
    print(json.dumps(result), flush=True)
    print("\n".join(compared_lines(result["compared"])), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
