"""Stage spans inside a convoy, the scheduler's queue wait, hold and request
latency, the flight recorder's `convoy` span, and the benchmark's readers of
them (ISSUE 25); every span's interval, the convoy's record in
`tracing.TIMELINE` and the readers of the timeline (ISSUE 38).

One scheduler run on the CPU at the suite's smallest bucket (ristretto255
(5,2) -> (8,2)): a dozen seeded requests through one worker at convoy widths
1 and 2, so the worker's two-deep pipeline holds every convoy but the last.
One file, one module-scoped run: the two widths' programs compile once.
"""

from __future__ import annotations

import hashlib
import importlib.util
import math
import pathlib
import sys
import time

import jax
import numpy as np
import pytest

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.service import engine
from dkg_tpu.service import scheduler as scheduler_mod
from dkg_tpu.service.scheduler import CeremonyScheduler
from dkg_tpu.utils import obslog, tracing
from dkg_tpu.utils.metrics import REGISTRY, MetricsRegistry

ROOT = pathlib.Path(__file__).resolve().parents[1]
CURVE, N, T = "ristretto255", 5, 2
SEEDS = range(100, 112)
# blake2b-16 over master || final_shares of SEEDS in order, from
# engine.run_convoy at width 1 on the commit before the spans went in
GOLDEN = "048d565eb98e260caadc84acafabf664"
READERS = (
    "convoy_host_ms", "convoy_device_wait_ms", "convoy_hold_ms",
    "queue_wait_program_ms", "convoy_hold_ms.steady",
    "convoy_device_wait_ms.steady",
)
TAIL_READERS = (
    "tail_queue_wait_ms", "tail_hold_ms", "tail_device_wait_ms", "tail_host_ms", "tail_rest_ms",
)
TIMELINE_READERS = ("latency_p95_program_ms",) + TAIL_READERS + ("device_unfed_share", "longest_stall_ms")
# every stage but `blame`, which runs only where a dealer cheated
HONEST_STAGES = tuple(s for s in engine.CONVOY_STAGES if s != "blame")


def _hist(snap: dict, name: str, **labels: str) -> tuple[float, int]:
    """(sum, count) over every series of histogram `name` carrying `labels`."""
    total, count = 0.0, 0
    for series, h in snap["histograms"].items():
        base, _, rest = series.partition("{")
        if base == name and all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total, count = total + h["sum"], count + h["count"]
    return total, count


def _delta(run: dict, name: str, **labels: str) -> tuple[float, int]:
    b, a = _hist(run["before"], name, **labels), _hist(run["after"], name, **labels)
    return a[0] - b[0], a[1] - b[1]


def _requests():
    return [engine.CeremonyRequest(CURVE, N, T, seed=s) for s in SEEDS]


@pytest.fixture(scope="module")
def runtime():
    rt = engine.WarmRuntime()
    reqs = _requests()
    engine.run_convoy(rt, reqs[:1])  # compile both widths outside the run
    engine.run_convoy(rt, reqs[:2])
    return rt


@pytest.fixture(scope="module")
def run(runtime):
    """A dozen requests through one worker: the first alone (width 1), the
    rest queued together behind it (widths 2, and 1 for the odd one)."""
    reqs = _requests()
    log = obslog.ObsLog()
    before = REGISTRY.snapshot()
    sch = CeremonyScheduler(concurrency=1, batch_max=2, runtime=runtime, log=log)
    try:
        first = sch.submit(reqs[0])
        deadline = time.monotonic() + 60
        while sch.poll(first) == "queued" and time.monotonic() < deadline:
            time.sleep(0.002)
        with sch._cond:  # queued as one: the worker's next pops find pairs
            rest = [sch.submit(r) for r in reqs[1:]]
        outs = [sch.result(cid, timeout=300) for cid in [first] + rest]
    finally:
        sch.close()
    cids = {o.ceremony_id for o in outs}
    return {
        "outs": outs,
        "before": before,
        "after": REGISTRY.snapshot(),
        "events": log.events(),
        # the ring is the process's: this run's records are those of its requests
        "timeline": [r for r in tracing.TIMELINE.snapshot() if cids & {m[0] for m in r["members"]}],
    }


def test_every_stage_is_booked_and_encode_counts_the_convoys(run):
    convoys = _delta(run, "service_convoy_seconds")[1]
    widths = sorted({o.convoy_width for o in run["outs"]})
    assert widths == [1, 2] and convoys >= 7
    for stage in HONEST_STAGES:
        seconds, count = _delta(run, "dkg_phase_seconds", phase=f"convoy.{stage}")
        assert count == convoys, stage
        assert seconds > 0, stage
    assert _delta(run, "dkg_phase_seconds", phase="convoy.blame") == (0.0, 0)
    # one worker, two deep: every convoy but the last waits for the one before it
    held = _delta(run, "dkg_phase_seconds", phase="convoy.hold")[0]
    assert held > _delta(run, "dkg_phase_seconds", phase="convoy.draw")[0]


def test_stages_and_hold_account_for_the_convoys(run):
    booked = sum(
        _delta(run, "dkg_phase_seconds", phase=f"convoy.{s}")[0]
        for s in engine.CONVOY_STAGES
    )
    wall = _delta(run, "service_convoy_seconds")[0]
    assert 0.95 * wall <= booked <= 1.0001 * wall


def test_queue_wait_and_latency_once_per_done_request(run):
    outs = run["outs"]
    assert all(o.status == "done" for o in outs)
    wait_s, waits = _delta(run, "service_queue_wait_seconds", bucket="8x2")
    latency_s, latencies = _delta(run, "service_request_seconds", bucket="8x2")
    assert waits == latencies == len(outs)
    assert wait_s == pytest.approx(sum(o.queue_seconds for o in outs))
    # a request's latency is its queue wait and its whole convoy, not the
    # convoy over its width (service_ceremony_seconds, unchanged beside it)
    share_s, shares = _delta(run, "service_ceremony_seconds", bucket="8x2")
    assert shares == len(outs)
    assert share_s == pytest.approx(sum(o.seconds for o in outs))
    assert latency_s >= wait_s + sum(o.seconds * o.convoy_width for o in outs) * 0.95
    assert outs[0].queue_seconds < outs[-1].queue_seconds


def test_one_convoy_span_per_convoy_names_its_members(run):
    assert obslog.validate_events(run["events"], allow_unknown=True) == []
    spans = [e for e in run["events"] if e["kind"] == "span" and e["name"] == "convoy"]
    assert len(spans) == _delta(run, "service_convoy_seconds")[1]
    by_cid = {o.ceremony_id: o for o in run["outs"]}
    members = [cid for s in spans for cid in s["ceremonies"]]
    assert sorted(members) == sorted(by_cid)
    assert len({s["convoy"] for s in spans}) == len(spans)
    for s in spans:
        assert s["width"] == len(s["ceremonies"]) == len(s["queue_wait_s"])
        assert (s["bucket"], s["slot"]) == ("8x2", 0)
        assert [by_cid[c].queue_seconds for c in s["ceremonies"]] == s["queue_wait_s"]
        assert set(HONEST_STAGES) - {"hold"} <= set(s["subs"]) <= set(engine.CONVOY_STAGES)
        assert 0.95 * s["dur_s"] <= sum(s["subs"].values()) <= 1.0001 * s["dur_s"]
    assert sum("hold" in s["subs"] for s in spans) == len(spans)


def test_no_recorder_no_span(runtime, monkeypatch):
    monkeypatch.delenv("DKG_TPU_OBSLOG", raising=False)
    ambient = obslog.ObsLog()
    with obslog.use(ambient), CeremonyScheduler(concurrency=1, batch_max=1, runtime=runtime) as sch:
        assert sch._log is None
        out = sch.result(sch.submit(_requests()[3]), timeout=300)
    assert out.status == "done" and (out.convoy_width, out.queue_seconds > 0) == (1, True)
    assert ambient.events() == []  # a worker thread has no ambient recorder either


def test_masters_and_shares_bit_for_bit(run, runtime):
    h = hashlib.blake2b(digest_size=16)
    for out in run["outs"]:
        h.update(out.master)
        h.update(np.ascontiguousarray(out.final_shares).tobytes())
    assert h.hexdigest() == GOLDEN
    # the same request alone, at width 1, outside the scheduler
    (alone,) = engine.run_convoy(runtime, _requests()[5:6])
    assert run["outs"][5].convoy_width == 2
    assert alone.master == run["outs"][5].master
    assert np.array_equal(alone.final_shares, run["outs"][5].final_shares)


def test_width_one_rho_is_derive_rho(runtime):
    fl = engine.start_convoy(runtime, _requests()[:1])
    a, e, s, r = (np.asarray(x) for x in (fl.a, fl.e, fl.s, fl.r))
    want = ce.derive_rho(fl.cfg_pad, a[0], e[0], s[0], r[0], 128)
    trace = tracing.CeremonyTrace()
    got = engine.derive_rho_convoy(fl.cfg_pad, a, e, s, r, 128, trace)
    assert got.shape == (1,) + want.shape and np.array_equal(got[0], want)
    assert list(trace.timings_s) == ["convoy.digest_dispatch", "convoy.digest_wait", "convoy.rho_fold"]
    assert fl.trace.meta["width"] == 1 and fl.trace.meta["bucket"] == "8x2"
    assert list(fl.trace.timings_s) == ["convoy.draw", "convoy.deal_dispatch"]


def test_stacked_rho_is_derive_rho_on_every_lane(runtime):
    """Width 2, on the fixture's programs: the convoy's one row-digest pass
    and its per-ceremony folds give each member the rho it would get alone."""
    fl = engine.start_convoy(runtime, _requests()[:2])
    a, e, s, r = (np.asarray(x) for x in (fl.a, fl.e, fl.s, fl.r))
    k, n = s.shape[:2]
    assert (k, fl.trace.meta["width"]) == (2, 2)
    want = [ce.derive_rho(fl.cfg_pad, a[i], e[i], s[i], r[i], 128) for i in range(k)]
    series = "rho_lanes_total"
    before = REGISTRY.snapshot()["counters"][series]
    got = engine.derive_rho_convoy(fl.cfg_pad, a, e, s, r, 128)
    assert REGISTRY.snapshot()["counters"][series] == before + k * n
    assert got.shape == (k,) + want[0].shape
    for i in range(k):
        assert np.array_equal(got[i], want[i]), i
    assert not np.array_equal(got[0], got[1])  # two transcripts, two rhos


def _host_bytes() -> float:
    return REGISTRY.snapshot()["counters"].get("round1_host_bytes_total", 0)


@pytest.fixture(scope="module")
def device_leg_run(runtime, run):
    """Three of the run's requests again with the digest's device leg forced (the
    chip's leg: `DKG_TPU_DIGEST`, read by `device_hash.digest_dispatch()`), through one
    worker: a convoy of width 1 and one of width 2, on the fixture's programs."""
    reqs = _requests()[:3]
    mp = pytest.MonkeyPatch()
    mp.setenv("DKG_TPU_DIGEST", "device")
    before = _host_bytes()
    earlier = len(tracing.TIMELINE.snapshot())  # the ids are the run's own again: tell the records by their place
    try:
        sch = CeremonyScheduler(concurrency=1, batch_max=2, runtime=runtime)
        try:
            first = sch.submit(reqs[0])
            deadline = time.monotonic() + 60
            while sch.poll(first) == "queued" and time.monotonic() < deadline:
                time.sleep(0.002)
            with sch._cond:
                rest = [sch.submit(r) for r in reqs[1:]]
            outs = [sch.result(cid, timeout=300) for cid in [first] + rest]
        finally:
            sch.close()
    finally:
        mp.undo()
    return {
        "outs": outs,
        "fetched": _host_bytes() - before,
        "timeline": tracing.TIMELINE.snapshot()[earlier:],
    }


def test_on_the_device_leg_every_stage_runs_once_a_convoy_in_the_same_order(device_leg_run):
    """One placement for both legs: `CONVOY_STAGES`' order, `digest_dispatch` after
    `deal_wait`, whichever leg digests."""
    records = device_leg_run["timeline"]
    assert sorted(r["width"] for r in records) == [1, 2]
    for r in records:
        assert [p for p, _, _ in r["spans"]] == [f"convoy.{s}" for s in HONEST_STAGES]
        at = {p.removeprefix("convoy."): (a, b) for p, a, b in r["spans"]}
        assert at["hold"][1] <= at["deal_wait"][0] <= at["deal_wait"][1] <= at["digest_dispatch"][0]
        assert at["digest_dispatch"][1] <= at["digest_wait"][0] <= at["digest_wait"][1] <= at["rho_fold"][0]


def test_the_device_leg_serves_the_same_keys_and_fetches_no_round1_tensor(device_leg_run, run):
    for got, want in zip(device_leg_run["outs"], run["outs"]):
        assert got.status == "done" and got.master == want.master
        assert np.array_equal(got.final_shares, want.final_shares)
    assert device_leg_run["fetched"] == 0


def test_the_host_leg_books_the_four_tensors_bytes(runtime):
    before = _host_bytes()
    fl = engine.start_convoy(runtime, _requests()[:2])
    engine.finish_convoy(runtime, fl)
    assert _host_bytes() - before == sum(x.nbytes for x in (fl.a, fl.e, fl.s, fl.r))


@pytest.mark.parametrize("k", [1, 2])
def test_finish_convoy_digests_deals_own_device_arrays(runtime, monkeypatch, device_leg_run, k):
    """The digest leg is handed `fl.a`, `fl.e`, `fl.s`, `fl.r` themselves, not copies
    on the host, and the rho it folds is `derive_rho`'s, ceremony by ceremony."""
    monkeypatch.setenv("DKG_TPU_DIGEST", "device")
    handed, rhos = [], []
    rows_fn, rho_fn = ce._dealer_rows_device, engine.derive_rho_convoy
    monkeypatch.setattr(ce, "_dealer_rows_device", lambda cfg, *t, **kw: handed.append(t) or rows_fn(cfg, *t, **kw))
    monkeypatch.setattr(engine, "derive_rho_convoy", lambda *a, **kw: rhos.append(rho_fn(*a, **kw)) or rhos[-1])
    before = _host_bytes()
    fl = engine.start_convoy(runtime, _requests()[:k])
    outs = engine.finish_convoy(runtime, fl)
    assert [o.status for o in outs] == ["done"] * k and _host_bytes() == before
    ((a, e, s, r),) = handed
    assert a is fl.a and e is fl.e and s is fl.s and r is fl.r
    assert all(isinstance(x, jax.Array) for x in (a, e, s, r))
    (rho,) = rhos
    for i in range(k):
        alone = ce.derive_rho(fl.cfg_pad, fl.a[i], fl.e[i], fl.s[i], fl.r[i], 128)
        assert np.array_equal(rho[i], alone), (k, i)


def test_an_engine_stand_in_without_a_trace_is_served(monkeypatch):
    """tests/test_service.py's stand-ins return a dict: no trace, no span, no stage."""
    monkeypatch.setattr(scheduler_mod, "start_convoy", lambda rt, reqs, ids=None: {"reqs": reqs, "ids": ids})
    monkeypatch.setattr(
        scheduler_mod,
        "finish_convoy",
        lambda rt, fl: [
            engine.CeremonyOutcome(ceremony_id=c, status="done", bucket_n=8, bucket_t=2)
            for c in fl["ids"]
        ],
    )
    log, reg = obslog.ObsLog(), MetricsRegistry()
    with CeremonyScheduler(concurrency=1, batch_max=1, runtime=object(), log=log, metrics=reg) as sch:
        out = sch.result(sch.submit(_requests()[0]), timeout=10)
    assert (out.status, out.convoy_width) == ("done", 1)
    assert [e for e in log.events() if e["kind"] == "span"] == []
    hists = reg.snapshot()["histograms"]
    assert hists['service_request_seconds{bucket="8x2"}']["count"] == 1
    assert hists['service_queue_wait_seconds{bucket="8x2"}']["count"] == 1


def test_book_phase_books_trace_and_registry():
    trace = tracing.CeremonyTrace()
    series = 'dkg_phase_seconds{phase="test.book_phase"}'
    before = REGISTRY.snapshot()["histograms"].get(series, {"count": 0})["count"]
    tracing.book_phase(trace, "test.book_phase", 1.0, 1.25)
    tracing.book_phase(None, "test.book_phase", 2.0, 2.5)
    assert trace.timings_s == {"test.book_phase": 0.25}
    assert REGISTRY.snapshot()["histograms"][series]["count"] == before + 2


@pytest.fixture(scope="module")
def readers():
    bench = str(ROOT / "benchmark")
    sys.path.insert(0, bench)  # the readers import their sibling bench_spans
    try:
        loaded = {}
        for name in READERS + TIMELINE_READERS:
            spec = importlib.util.spec_from_file_location(
                f"reader_{name.replace('.', '_')}", ROOT / "benchmark" / "layer_metrics" / f"{name}.py"
            )
            loaded[name] = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(loaded[name])
        yield loaded
    finally:
        sys.path.remove(bench)


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_real_snapshots_and_nothing_from_none(run, readers, name):
    value = readers[name].read({"counters": {"before": run["before"], "after": run["after"]}})
    assert value is not None and math.isfinite(value) and value > 0
    empty = MetricsRegistry().snapshot()
    assert readers[name].read({"counters": {"before": empty, "after": empty}}) is None
    # a program that never had the series: the same window seen twice adds nothing
    assert readers[name].read({"counters": {"before": run["after"], "after": run["after"]}}) is None


def test_readers_split_the_convoy_without_overlap(run, readers):
    ctx = {"counters": {"before": run["before"], "after": run["after"]}}
    convoys = _delta(run, "service_convoy_seconds")[1]
    parts = sum(readers[n].read(ctx) for n in ("convoy_host_ms", "convoy_device_wait_ms", "convoy_hold_ms"))
    booked = sum(_delta(run, "dkg_phase_seconds", phase=f"convoy.{s}")[0] for s in engine.CONVOY_STAGES)
    assert parts == pytest.approx(booked / convoys * 1e3)  # the run drained: every convoy passed every stage
    assert readers["convoy_hold_ms.steady"].read(ctx) == readers["convoy_hold_ms"].read(ctx)
    assert readers["convoy_device_wait_ms.steady"].read(ctx) == readers["convoy_device_wait_ms"].read(ctx)
    wait_s, waits = _delta(run, "service_queue_wait_seconds")
    assert readers["queue_wait_program_ms"].read(ctx) == pytest.approx(wait_s / waits * 1e3)


def test_a_convoy_in_flight_at_the_windows_end_does_not_inflate_the_mean(readers):
    """Two convoys finished, a third has booked its first stages: a stage's
    seconds go over the convoys that passed it, not over those that finished."""

    def snap(draw, hold, encode):
        reg = MetricsRegistry()
        for seconds, phase in [(s, "convoy.draw") for s in draw] + [(s, "convoy.hold") for s in hold] + [
            (s, "convoy.encode") for s in encode
        ]:
            reg.observe("dkg_phase_seconds", seconds, phase=phase)
        return reg.snapshot()

    ctx = {"counters": {"before": snap([], [], []), "after": snap([0.010] * 3, [1.0] * 3, [0.020] * 2)}}
    assert readers["convoy_hold_ms"].read(ctx) == pytest.approx(1000.0)
    assert readers["convoy_host_ms"].read(ctx) == pytest.approx(30.0)


def _convoy_spans(run):
    return {e["convoy"]: e for e in run["events"] if e["kind"] == "span" and e["name"] == "convoy"}


def test_one_timeline_record_per_convoy_with_its_members(run):
    records, spans = run["timeline"], _convoy_spans(run)
    assert sorted(r["convoy"] for r in records) == sorted(spans)
    by_cid = {o.ceremony_id: o for o in run["outs"]}
    assert sorted(m[0] for r in records for m in r["members"]) == sorted(by_cid)
    for r in records:
        s = spans[r["convoy"]]
        assert [m[0] for m in r["members"]] == s["ceremonies"]
        assert (r["width"], r["bucket"], r["slot"]) == (len(r["members"]), "8x2", 0)
        assert all(m[3] == "done" for m in r["members"])
        for (cid, admitted, completed, _), waited in zip(r["members"], s["queue_wait_s"]):
            assert r["popped"] - admitted == pytest.approx(waited, abs=1e-6)
            assert completed - admitted >= by_cid[cid].queue_seconds + by_cid[cid].seconds * r["width"] * 0.95


def test_a_convoys_spans_are_in_order_and_add_up_to_its_stage_seconds(run):
    spans = _convoy_spans(run)
    for r in run["timeline"]:
        phases = [p for p, _, _ in r["spans"]]
        assert phases == [f"convoy.{s}" for s in HONEST_STAGES]  # each once, in the order they ran
        for (_, start, end), (_, nxt, _) in zip(r["spans"], r["spans"][1:]):
            assert start <= end <= nxt  # none overlaps the next: hold covers no stage of its own convoy
        for stage, seconds in spans[r["convoy"]]["subs"].items():  # the trace's timings_s
            assert sum(b - a for p, a, b in r["spans"] if p == f"convoy.{stage}") == pytest.approx(seconds, abs=1e-12)


def test_the_records_stamps_and_spans_are_on_one_clock(run):
    for r in run["timeline"]:
        first, last = r["spans"][0][1], r["spans"][-1][2]
        for _, admitted, completed, _ in r["members"]:
            assert admitted <= r["popped"] <= first
            assert last <= completed
        # a snapshot says when it was taken on the same clock: the run's two bracket its records
        assert run["before"]["at"] <= min(m[1] for m in r["members"])
        assert max(m[2] for m in r["members"]) <= run["after"]["at"]


def test_the_convoy_span_places_every_stage_where_it_ran(run):
    for r in run["timeline"]:
        s = _convoy_spans(run)[r["convoy"]]
        assert [x[0] for x in s["spans"]] == list(s["subs"])
        start = r["spans"][0][1] - s["spans"][0][1]  # the span's own start on the record's clock
        assert r["popped"] <= start + 1e-6
        for (stage, a, b), (phase, t0, t1) in zip(s["spans"], r["spans"]):
            assert phase == f"convoy.{stage}" and 0 <= a <= b <= s["dur_s"]
            assert (a, b) == (pytest.approx(t0 - start, abs=1e-9), pytest.approx(t1 - start, abs=1e-9))
    held = next(s for s in _convoy_spans(run).values() if s["subs"]["hold"] > 0.01)
    slices = {e["name"]: e for e in obslog.to_chrome_trace([held])["traceEvents"] if e["ph"] == "X"}
    offset = next(a for stage, a, _ in held["spans"] if stage == "hold")
    assert slices["convoy.hold"]["ts"] == pytest.approx(slices["convoy"]["ts"] + offset * 1e6)
    assert slices["convoy.hold"]["dur"] == pytest.approx(held["subs"]["hold"] * 1e6)


def test_the_ring_is_bounded_and_reset_empties_it():
    ring = tracing.Timeline()
    for i in range(tracing.TIMELINE_DEPTH + 5):
        ring.append({"convoy": i})
    kept = ring.snapshot()
    assert len(kept) == tracing.TIMELINE_DEPTH == 8192
    assert (kept[0]["convoy"], kept[-1]["convoy"]) == (5, tracing.TIMELINE_DEPTH + 4)
    ring.reset()
    assert ring.snapshot() == []
    assert isinstance(tracing.TIMELINE, tracing.Timeline)


def test_the_clock_shift_is_one_constant_of_the_process():
    """The scheduler's monotonic stamps go onto the spans' clock by a difference read
    once at import, not once a convoy: a thread switch cannot shift a record."""
    now = min(abs(time.perf_counter() - time.monotonic() - tracing.MONOTONIC_TO_SPAN_CLOCK) for _ in range(8))
    assert now < 1e-3
    assert tracing._clock_shift() == pytest.approx(tracing.MONOTONIC_TO_SPAN_CLOCK, abs=1e-3)


def test_phase_span_keeps_every_interval_and_timings_stay_their_sum():
    trace = tracing.CeremonyTrace(meta={"convoy": 7})
    t0 = time.perf_counter()
    for _ in range(2):  # a phase that runs twice: two entries, one sum
        with tracing.phase_span(trace, "test.twice"):
            pass
    tracing.book_phase(trace, "test.held", t0, t0 + 0.5)
    assert [p for p, _, _ in trace.spans] == ["test.twice", "test.twice", "test.held"]
    assert trace.timings_s["test.twice"] == sum(b - a for p, a, b in trace.spans if p == "test.twice")
    assert trace.spans[2] == ("test.held", t0, t0 + 0.5) and trace.timings_s["test.held"] == 0.5
    assert t0 <= trace.spans[0][1] <= trace.spans[0][2] <= trace.spans[1][1] <= time.perf_counter()
    assert "spans" not in trace.as_dict()  # as_dict is what it was


def _timeline_ctx(run):
    return {
        "counters": {"before": run["before"], "after": run["after"]},
        "seconds": run["after"]["at"] - run["before"]["at"],
        "cell": {"trace_seconds": 1.0, "drain_s": 60.0},
        "records": [], "trace": None,
    }


@pytest.mark.parametrize("name", TIMELINE_READERS)
def test_timeline_reader_reads_the_real_run_and_nothing_without_the_stamp(run, readers, name, capsys):
    value = readers[name].read(_timeline_ctx(run))
    assert value is not None and math.isfinite(value) and value > 0
    assert "[timeline] " in capsys.readouterr().out  # each reader says what it read on a line of the log
    old = {k: v for k, v in run["after"].items() if k != "at"}  # a program whose snapshots carry no "at"
    assert readers[name].read({**_timeline_ctx(run), "counters": {"before": old, "after": old}}) is None


def test_timeline_readers_agree_with_the_outcomes(run, readers):
    ctx = _timeline_ctx(run)
    latencies = sorted(m[2] - m[1] for r in run["timeline"] for m in r["members"])
    assert readers["latency_p95_program_ms"].read(ctx) == pytest.approx(latencies[-1] * 1e3)  # ceil(0.95 x 12) = 12
    # the tail is that one request: its five parts are its latency, to the float
    parts = {n: readers[n].read(ctx) for n in TAIL_READERS}
    assert sum(parts.values()) == pytest.approx(latencies[-1] * 1e3, abs=1e-9)
    worst = max(run["outs"], key=lambda o: o.queue_seconds)
    assert parts["tail_queue_wait_ms"] == pytest.approx(worst.queue_seconds * 1e3, abs=1e-3)
    # one worker: while it draws, folds rho or sits between convoys nobody feeds the chip
    assert 0.0 < readers["device_unfed_share"].read(ctx) < 1.0
    # a dozen requests admitted at once and finished convoy by convoy: the longest
    # stretch without a completion is about a convoy, and under the whole run
    stall_ms = readers["longest_stall_ms"].read(ctx)
    assert 0.0 < stall_ms < (run["after"]["at"] - run["before"]["at"]) * 1e3
