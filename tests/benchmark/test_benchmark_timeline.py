"""`benchmark/bench_timeline.py` and its readers on hand-made convoy records: a known
layout's unfed share, the tail's five parts against its mean latency, a stall that
ignores a stretch with nothing admitted, a wrapped ring, a program without the ring;
and the sixteen manifest entries the readers stand under.  No scheduler runs here
(the real run is `tests/test_convoy_spans.py`'s)."""

import json

import pytest

import bench_support

METRICS = (
    "latency_p95_program_ms", "tail_queue_wait_ms", "tail_hold_ms", "tail_device_wait_ms",
    "tail_host_ms", "tail_rest_ms", "device_unfed_share", "longest_stall_ms",
)
TAIL = METRICS[1:6]
LATENCY_CELLS = [
    "fleet_mix_reduced.steady", "ceremony_n1024.closed", "ceremony_bls_n1024.closed", "fleet_mix.saturated",
]
STAGES = (
    "draw", "deal_dispatch", "hold", "deal_wait", "digest_dispatch", "digest_wait", "rho_fold",
    "verify_dispatch", "verify_wait", "finalise_dispatch", "finalise_wait", "encode",
)


@pytest.fixture(scope="module")
def tl():
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_timeline

    return bench_timeline


def _reader(name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


def _convoy(seq, slot, popped, stage_s, admitted, gap=0.0, status="done"):
    """A record whose stages follow one another from `popped` + `gap`, `stage_s` seconds each
    (one number for all, or one a stage), every member completed `gap` after the last."""
    lengths = stage_s if isinstance(stage_s, (list, tuple)) else [stage_s] * len(STAGES)
    spans, at = [], popped + gap
    for stage, seconds in zip(STAGES, lengths):
        spans.append((f"convoy.{stage}", at, at + seconds))
        at += seconds
    return {
        "convoy": seq, "slot": slot, "bucket": "16x5", "width": len(admitted), "popped": popped,
        "spans": spans,
        "members": [(f"c{seq}-{i}", a, at + gap, status) for i, a in enumerate(admitted)],
    }


def _ctx(before_at, after_at, seconds=10.0, trace_seconds=1.0):
    return {
        "counters": {"before": {"at": before_at}, "after": {"at": after_at}},
        "seconds": seconds, "cell": {"trace_seconds": trace_seconds}, "records": [], "trace": None,
    }


def test_fed_intervals_pair_each_dispatch_with_its_wait_and_take_encode_whole(tl):
    rec = _convoy(0, 0, popped=0.0, stage_s=1.0, admitted=[0.0])
    # draw 0-1 | deal_dispatch 1-2, hold 2-3, deal_wait 3-4 | digest 4-6 | rho_fold 6-7 |
    # verify 7-9 | finalise 9-11 | encode 11-12
    assert tl.fed_intervals(rec) == [(1.0, 4.0), (4.0, 6.0), (7.0, 9.0), (9.0, 11.0), (11.0, 12.0)]
    assert tl.unfed([rec], 0.0, 14.0) == [(0.0, 1.0), (6.0, 7.0), (12.0, 14.0)]
    assert tl.unfed_share([rec], 0.0, 14.0) == pytest.approx(4.0 / 14.0)
    # a second convoy on another worker feeds the chip through the first one's rho_fold
    other = _convoy(1, 1, popped=2.5, stage_s=1.0, admitted=[2.0])  # its own rho_fold 8.5-9.5, encode to 14.5
    assert tl.unfed([rec, other], 0.0, 16.0) == [(0.0, 1.0), (14.5, 16.0)]
    assert tl.unfed_share([rec, other], 6.0, 7.0) == 0.0
    assert tl.unfed_share([rec], 3.0, 3.0) is None


def test_the_five_tail_parts_add_up_to_the_tails_mean_latency(tl, monkeypatch):
    # forty requests in convoys of two; every stage and gap its own length, so no part is another's
    records = [
        _convoy(
            i, i % 4, popped=i * 0.37 + 0.011 * (i % 7),
            stage_s=[0.001 * (1 + (i * (j + 3)) % 11) for j in range(len(STAGES))],
            admitted=[i * 0.37 - 0.013 * (i % 5), i * 0.37 - 0.002], gap=0.0007 * (i % 3),
        )
        for i in range(20)
    ]
    rows = tl.split(records, 0.0, 100.0)
    assert len(rows) == 40
    worst = tl.tail(rows)
    assert 2 <= len(worst) <= 4 and min(r["latency"] for r in worst) >= max(
        r["latency"] for r in rows if r not in worst
    )
    for r in rows:
        assert sum(tl.parts(r).values()) == pytest.approx(r["latency"], abs=1e-12)
        assert tl.parts(r)["rest"] >= -1e-12
    from dkg_tpu.utils import tracing

    monkeypatch.setattr(tracing, "TIMELINE", tracing.Timeline())
    for rec in records:
        tracing.TIMELINE.append(rec)
    ctx = _ctx(0.0, 100.0)
    values = {name: _reader(name).read(ctx) for name in TAIL}
    p95 = _reader("latency_p95_program_ms").read(ctx)
    assert {name: _reader(f"{name}.saturated").read(ctx) for name in TAIL} == values
    assert sum(values.values()) == pytest.approx(tl.mean(worst, "latency") * 1e3, abs=1e-9)
    assert all(v > 0 for v in values.values())
    assert p95 == pytest.approx(min(r["latency"] for r in worst) * 1e3)
    assert values["tail_queue_wait_ms"] == pytest.approx(tl.mean(worst, "queue") * 1e3)
    assert values["tail_hold_ms"] == pytest.approx(tl.mean(worst, "hold") * 1e3)


def test_a_request_outside_the_window_or_not_done_is_no_row(tl):
    done = _convoy(0, 0, popped=1.0, stage_s=0.01, admitted=[0.5])
    failed = _convoy(1, 0, popped=2.0, stage_s=0.01, admitted=[1.5], status="failed")
    late = _convoy(2, 0, popped=9.95, stage_s=0.01, admitted=[9.9])
    rows = tl.split([done, failed, late], 0.0, 10.0)
    assert [r["latency"] for r in rows] == [pytest.approx(1.0 + 0.12 - 0.5)]


def test_longest_stall_ignores_a_stretch_with_nothing_admitted(tl):
    a = _convoy(0, 0, popped=1.0, stage_s=0.0125, admitted=[1.0])  # completes at 1.15
    b = _convoy(1, 0, popped=6.0, stage_s=0.025, admitted=[6.0, 6.1])  # both complete at 6.3
    c = _convoy(2, 1, popped=6.2, stage_s=0.1, admitted=[6.2])  # completes at 7.4
    found = tl.stalls([a, b, c], 0.0, 10.0)
    # 1.15 -> 6.0 nothing was admitted: no stall, though it is the longest gap between completions
    assert found == [
        (pytest.approx(1.0), pytest.approx(1.15)),
        (pytest.approx(6.0), pytest.approx(6.3)),
        (pytest.approx(6.3), pytest.approx(7.4)),
    ]
    longest = max(found, key=lambda g: g[1] - g[0])
    assert longest == (pytest.approx(6.3), pytest.approx(7.4))
    # cut to the window at both ends
    assert tl.stalls([c], 6.5, 7.0) == [(6.5, 7.0)]
    assert tl.stalls([], 0.0, 10.0) == []
    said = tl.slots_during([a, b, c], 6.7, 7.4)  # past c's hold, which is no work of its worker
    assert said.startswith("slot 0: between convoys 100%; slot 1: ") and said.endswith("(convoy 2, 16x5 x1)")


def test_a_wrapped_ring_reads_nothing_and_says_so(tl, capsys):
    ring = [_convoy(i, 0, popped=float(i), stage_s=0.01, admitted=[float(i)]) for i in range(8)]
    assert tl.cut(ring, 16, 0.0) is ring  # not full: nothing can be lost
    assert tl.cut(ring, 8, 3.0) is ring  # full, but its oldest record ended before the window began
    assert tl.cut(ring, 8, 0.05) is None  # full, and the oldest ended inside the window
    assert "wrapped inside the window" in capsys.readouterr().out


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_nothing_from_a_program_without_the_ring_or_the_stamp(tl, monkeypatch, name):
    from dkg_tpu.utils import tracing

    reader = _reader(name)
    empty = {"counters": {}, "gauges": {}, "histograms": {}}  # the parent's snapshot: no "at"
    ctx = _ctx(0.0, 1.0)
    ctx["counters"] = {"before": empty, "after": empty}
    assert reader.read(ctx) is None
    monkeypatch.delattr(tracing, "TIMELINE")
    assert reader.read(_ctx(0.0, 1.0)) is None
    assert _reader(f"{name}.saturated").read(_ctx(0.0, 1.0)) is None


def test_the_manifest_has_each_metric_once_for_the_latency_cells_and_once_for_the_rate_cell():
    manifest = json.loads(bench_support.MANIFEST.read_text())
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in METRICS:
        base, twin = by_name[name], by_name[f"{name}.saturated"]
        # at least these cells: a later PR may append its own to either list
        assert base["moves"] == "latency_p95_ms" and set(LATENCY_CELLS) <= set(base["workloads"])
        assert twin["moves"] == "ceremonies_per_s" and "fleet_mix_reduced.saturated" in twin["workloads"]
        for entry in (base, twin):
            assert entry["source"] == "program_span" and entry["better"] == "lower"
            assert entry["layer"] in ("service/scheduler.py", "service/engine.py")
            assert (entry["unit"], entry["layer"]) == (twin["unit"], twin["layer"])
    assert len(by_name) == len(manifest["per_layer"])  # each name once, wherever in the list it stands
