"""`ceremony_n1024.closed`'s traffic kind and readers, rehearsed on the CPU with
the smallest bucket: one request in flight through `run_cell`, every party's
final share against the reference, the broken-engine control, each new reader on
a run that has its series and on a program that has not, and the traffic kind
stopping a program without the seam.  One file, so that one worker compiles the
(8,2) width-1 programs once."""

import json

import pytest

import bench_support

MANIFEST = bench_support.DATA.parent / "data_closed" / "manifest.json"
SPAN_READERS = ("convoy_host_ms.closed", "convoy_device_wait_ms.closed", "setup_programs_s.closed")
TRACE_READERS = {
    "deal_device_ms.closed": "jit_deal",
    "verify_device_ms.closed": "jit_verify_batch",
    "pallas_time_share.closed": None,
}


@pytest.fixture()
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(trace):
    return bench_support.bench_run().run_cell(MANIFEST, "tiny.closed", 2**31 + 27, 3.0, trace)


def _reader(name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


def test_one_request_in_flight_takes_the_width_1_programs_and_is_correct(cache_in_tmp, capsys):
    from dkg_tpu.service import aot

    result = _run(trace=False)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p95_ms", "setup_s"}
    printed = capsys.readouterr().out
    for name in ("master_mismatch", "share_limbs_off", "window_aot_builds", "window_jax_stage_events"):
        assert f"compared {name} = 0 (limit 0)" in printed
    # one in flight: only the width-1 set is warmed, and nothing wider is ever formed
    assert "warm (8,2) x1" in printed and "x2" not in printed
    kinds = {(key[0], key[4]) for key in aot._PROC if key[1:4] == ("secp256k1", 8, 2)}
    assert kinds == {("deal", 1), ("verify", 1), ("aggregate", 1), ("master", 1)}


@pytest.mark.parametrize("what", ["share", "master"])
def test_broken_timed_path_is_not_correct_at_this_load(cache_in_tmp, what):
    with bench_support.broken_engine(what):
        result = _run(trace=False)
    assert result["correct"] is False
    assert result["attempted"] >= 1


def test_span_readers_read_a_rehearsed_run(cache_in_tmp):
    result = _run(trace=True)
    assert result["correct"] is True
    for name in SPAN_READERS:
        assert result["metrics"][name]["value"] > 0, name
    # set-up built the four programs here (the store started empty) and traced the digest leg
    assert result["metrics"]["setup_programs_s.closed"]["unit"] == "s"
    # no device plane on the CPU: the device-trace readers find nothing and are left out
    assert not set(TRACE_READERS) & set(result["metrics"])


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_readers_return_none_on_a_program_without_the_series(name):
    empty = {"before": {"histograms": {}}, "after": {"histograms": {"service_convoy_seconds": {"sum": 1.0, "count": 1}}}}
    assert _reader(name).read({"counters": empty}) is None


def test_setup_programs_adds_builds_loads_and_the_digest_leg():
    hist = {
        'aot_build_stage_seconds{kind="verify",stage="trace"}': {"sum": 100.0, "count": 1},
        'aot_build_stage_seconds{kind="verify",stage="compile"}': {"sum": 20.0, "count": 1},
        "aot_load_seconds": {"sum": 3.0, "count": 2},
        'digest_leg_first_call_seconds{curve="secp256k1",shape="1024x342"}': {"sum": 7.5, "count": 1},
        'dkg_phase_seconds{phase="convoy.draw"}': {"sum": 9.0, "count": 1},
    }
    ctx = {"counters": {"before": {"histograms": dict(hist)}, "after": {"histograms": hist}}}
    # all of it was booked before the window, so the reader takes the snapshot whole, not a delta
    assert _reader("setup_programs_s.closed").read(ctx) == pytest.approx(130.5)
    warm = {k: v for k, v in hist.items() if not k.startswith("aot_build")}
    ctx = {"counters": {"before": {}, "after": {"histograms": warm}}}
    assert _reader("setup_programs_s.closed").read(ctx) == pytest.approx(10.5)


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
def test_trace_readers_on_a_recorded_slice_and_without_one(name):
    import bench_trace

    recorded = json.loads((bench_support.DATA / "trace_recorded.json").read_text())
    trace = bench_trace.reduce(recorded["events"], recorded["host_window_s"])
    module = TRACE_READERS[name]
    if module is not None:
        # the recorded slice is a width-8 convoy's: give it the width-1 program's module name
        stacked = {"jit_deal": "jit__deal_stack", "jit_verify_batch": "jit__verify_stack"}[module]
        for runs in (trace["module_runs"], trace["module_runs_cut"]):
            if stacked in runs:
                runs[module] = runs.pop(stacked)
    value = _reader(name).read({"trace": trace})
    assert value is not None and value > 0
    if module is None:
        assert value <= 100.0
    assert _reader(name).read({"trace": None}) is None
    if module is not None:
        assert _reader(name).read({"trace": dict(trace, module_runs={}, module_runs_cut={})}) is None


def test_the_traffic_kind_stops_a_program_without_the_seam():
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    kind = run.load_module(run.find(roots, "traffic", "closed_loop_prepared", ".py"))
    config = json.loads(run.find(roots, "configs", "secp256k1_n1024_t341", ".json").read_text())
    cell = json.loads(run.find(roots, "workloads", "ceremony_n1024.closed", ".json").read_text())
    plan = kind.plan(cell["traffic"], config, 2**31 + 5, 51.0)
    assert plan["outstanding"] == 1
    due, req = next(plan["requests"])
    assert due is None and (req["n"], req["t"], req["curve"]) == (1024, 341, "secp256k1")
    for needs in ({"module": "dkg_tpu.service.aot", "attribute": "no_such_seam"},
                  {"module": "dkg_tpu.service.no_such_module", "attribute": "x"}):
        with pytest.raises(SystemExit) as stop:
            kind.plan(dict(cell["traffic"], needs=needs), config, 1, 51.0)
        assert "cannot prepare secp256k1_n1024_t341 [(1024, 341)]" in str(stop.value.code)
