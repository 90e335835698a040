"""The reduction from trace events to busy time, modules, kernels and idle gaps:
on events made by hand, and on a slice recorded on the chip."""

import json

import pytest

import bench_support

bench_support.bench_run()
import bench_trace  # noqa: E402  (found through benchmark/ on sys.path, which run.py puts there)

DEV = "/device:TPU:0"
MS = 1_000_000


def ev(line, name, start_ms, dur_ms, plane=DEV):
    return {"plane": plane, "line": line, "name": name, "start_ns": int(start_ms * MS), "dur_ns": int(dur_ms * MS)}


def test_busy_is_a_union_and_gaps_are_labelled():
    events = [
        ev("XLA Modules", "jit_deal(1)", 0, 30),
        ev("XLA Ops", "fusion.1", 0, 10),
        ev("XLA Ops", "fusion.1", 5, 10),  # overlaps the first: 0..15 busy, not 20
        ev("XLA Ops", "custom-call.7[tpu_custom_call]", 20, 10),
        ev("XLA Ops", "custom-call.8[AllocateBuffer]", 29, 1),  # a custom call, not a kernel
        ev("XLA Ops", "fusion.2", 100, 5),
        ev("Steps", "step", 0, 200),  # never an operation
        ev("thread", "bench:wait_result", 25, 80, plane="/host:CPU"),
        ev("thread", "derive_rho", 40, 50, plane="/host:CPU"),
        ev("thread", bench_trace.WINDOW_MARK, 0, 200, plane="/host:CPU"),
    ]
    out = bench_trace.reduce(events, 0.1999)
    assert out["busy_s"] == pytest.approx(0.030) and out["busy_raw_s"] == pytest.approx(0.030)
    assert out["window_s"] == pytest.approx(0.2) and out["window_from"] == "mark" and out["host_window_s"] == 0.1999
    assert out["span_s"] == pytest.approx(0.2) and out["devices"] == 1
    assert out["pallas_s"] == pytest.approx(0.010)
    assert out["modules"] == {"jit_deal(1)": {"seconds": pytest.approx(0.030), "count": 1}}
    assert out["ops"]["jit_deal/fusion.1"]["count"] == 2
    assert out["ops"]["no module/fusion.2"]["count"] == 1
    assert out["device_ops"][0][0] == "jit_deal/fusion.1"
    # the idle stretch up to the window's end counts too, and the mark itself labels nothing
    assert out["idle_gaps"][0] == ["no host event", pytest.approx(0.095)]
    assert out["idle_gaps"][1] == ["bench:wait_result | derive_rho", pytest.approx(0.070)]
    assert out["idle_gaps"][2][1] == pytest.approx(0.005)


def test_busy_is_the_mean_over_device_planes():
    events = [ev("XLA Ops", "a", 0, 10), ev("XLA Ops", "a", 0, 30, plane="/device:TPU:1")]
    assert bench_trace.reduce(events, 1.0)["busy_s"] == pytest.approx(0.020)


def test_busy_is_counted_inside_the_marked_window_and_the_recorded_reading_stands_beside_it():
    # the profiler records a little before and after the harness's span: what lies outside it is
    # not the window's, so busy cannot pass the window, and the uncut union shows a wrong one
    events = [
        ev("XLA Modules", "jit_a(1)", 0, 30),
        ev("XLA Ops", "a", 0, 12),
        ev("XLA Ops", "b", 12, 18),
        ev("XLA Ops", "c", 40, 5),  # after the window: dropped
        ev("thread", bench_trace.WINDOW_MARK, 5, 20, plane="/host:CPU"),
    ]
    out = bench_trace.reduce(events, 0.0199)
    assert out["window_s"] == pytest.approx(0.020) and out["busy_s"] == pytest.approx(0.020)
    assert out["busy_s"] <= out["window_s"]
    assert out["busy_raw_s"] == pytest.approx(0.035) and out["span_s"] == pytest.approx(0.045)
    assert out["device_edges_s"] == [pytest.approx(-0.005), pytest.approx(0.020)]
    assert out["ops"]["jit_a/a"]["seconds"] == pytest.approx(0.007)  # 5..12 of 0..12
    assert out["modules"]["jit_a(1)"]["seconds"] == pytest.approx(0.020)
    assert out["idle_gaps"] == []


def test_a_trace_without_the_mark_takes_what_its_events_span():
    out = bench_trace.reduce([ev("XLA Ops", "a", 10, 30), ev("thread", "x", 0, 50, plane="/host:CPU")])
    assert out["window_from"] == "events" and out["window_s"] == pytest.approx(0.05)
    assert out["busy_s"] == pytest.approx(0.03) and out["host_window_s"] is None


def test_a_programs_time_is_of_whole_executions_under_its_exact_name():
    events = [
        ev("XLA Modules", "jit__deal_stack(1)", 0, 4),  # the slice began inside it
        ev("XLA Modules", "jit__deal_stack(1)", 10, 10),
        ev("XLA Modules", "jit__dealer_rows_device(2)", 20, 30),  # holds "deal", is not the deal program
        ev("XLA Modules", "jit__deal_stack(7)", 50, 12),  # another width: the same name, another hash
        ev("XLA Modules", "jit__verify_stack(3)", 62, 8),  # the slice ended inside it
    ]
    out = bench_trace.reduce(events, 0.07)
    assert out["module_runs"] == {
        "jit__deal_stack": [pytest.approx(0.010), pytest.approx(0.012)],
        "jit__dealer_rows_device": [pytest.approx(0.030)],
    }
    assert out["module_runs_cut"] == {"jit__deal_stack": [pytest.approx(0.004)], "jit__verify_stack": [pytest.approx(0.008)]}
    assert bench_trace.module_ms(out, "jit__deal_stack") == pytest.approx(11.0)
    # none whole in the slice: the cut one, a lower bound, so that the metric is not missing from the line
    assert bench_trace.module_ms(out, "jit__verify_stack") == pytest.approx(8.0)
    assert bench_trace.module_ms(out, "deal") is None and bench_trace.module_ms(None, "jit__deal_stack") is None
    assert bench_trace.modules_s(out, ("jit__deal_stack",)) == pytest.approx(0.026)  # shares count the cut ones too


def test_recorded_slice_from_the_chip():
    recorded = json.loads((bench_support.DATA / "trace_recorded.json").read_text())
    out = bench_trace.reduce(recorded["events"], recorded["host_window_s"])
    for key, want in recorded["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-9), key
    # the window is the harness's span as the profiler recorded it, 5 us from the host's own reading;
    # the device's events begin 2.0 ms before it and end 2.9 ms after it, and those parts are not busy time
    assert out["window_from"] == "mark" and out["window_s"] == pytest.approx(out["host_window_s"], abs=2e-5)
    assert out["device_edges_s"] == [pytest.approx(-0.002004279), pytest.approx(0.002909435)]
    assert out["busy_s"] < out["busy_raw_s"] and out["busy_s"] <= out["window_s"] < out["span_s"]
    canon = out["module_runs"]["jit_affine_canon"]
    assert len(canon) == 8 and max(canon) == pytest.approx(0.181541, abs=1e-6) and min(canon) > 0.044
    # the verify executions that the window's two ends cut (15.6 and 5.6 ms of 23.5) are left out of the mean
    assert sorted(out["module_runs_cut"]["jit__verify_stack"]) == [pytest.approx(0.005575466), pytest.approx(0.01555466)]
    run = bench_support.bench_run()
    ctx = {"trace": out}
    for name, want in recorded["expect_metrics"].items():
        read = run.load_module(bench_support.ROOT / "benchmark/layer_metrics" / f"{name}.py").read
        assert read(ctx) == pytest.approx(want, rel=1e-9), name
