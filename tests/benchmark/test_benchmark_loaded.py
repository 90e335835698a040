"""`fleet_mix_reduced.loaded` and what came with it (PR 41), on the CPU with no engine:
a burst longer than the default queue through the scheduler's admission at the depth
the fleet configurations state and at the default of 256; the arithmetic of the `pace`
and `open loop` lines on made records; the cell, its rate and the depth, as data.

The burst's engine is a stand-in that answers from the plain reference (Python ints, a
few ms a ceremony), so nothing compiles: what is held here is the admission path and the
harness around it (a refusal is a record without an outcome, `not_done`, limit 0), not
the arithmetic, which `test_benchmark_engine.py` and `test_benchmark_mix.py` hold."""

import json
import math
import re

import numpy as np
import pytest

import bench_support

MANIFEST = bench_support.DATA.parent / "data_loaded" / "manifest.json"
SEED = 2**31 + 41
COUNTS = (
    "not_done", "unqualified", "complaints", "master_mismatch", "share_limbs_off",
    "window_aot_builds", "window_aot_disk_loads", "window_aot_errors", "window_jax_stage_events",
)


@pytest.fixture()
def answered_from_the_reference(monkeypatch, tmp_path):
    """The scheduler, its queue and its one worker as they are; every convoy answered
    by `bench_oracle`, the tables left out."""
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    import dkg_tpu.service as service
    from dkg_tpu.service import CeremonyOutcome, engine
    from dkg_tpu.service import scheduler as scheduler_mod

    def answers(reqs, ids):
        outs = []
        for cid, r in zip(ids, reqs):
            sums = bench_oracle.column_sums(r.curve, r.n, r.t, r.seed)
            shares = [bench_oracle.share_limbs(bench_oracle.final_share(r.curve, sums, j), 16) for j in range(1, r.n + 1)]
            outs.append(
                CeremonyOutcome(
                    ceremony_id=cid, status="done", curve=r.curve, n=r.n, t=r.t, bucket_n=r.n, bucket_t=r.t,
                    master=bench_oracle.master_bytes(r.curve, sums), qualified=(True,) * r.n,
                    final_shares=np.stack(shares), seconds=1e-3,
                )
            )
        return outs

    class Runtime:
        def commitment(self, curve, shared_string):
            return (None,)

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(service, "WarmRuntime", Runtime)
    monkeypatch.setattr(engine, "run_convoy", lambda runtime, reqs: answers(reqs, [f"warm-{i}" for i in range(len(reqs))]))
    monkeypatch.setattr(scheduler_mod, "start_convoy", lambda rt, reqs, ids=None: {"reqs": list(reqs), "ids": list(ids)})
    monkeypatch.setattr(scheduler_mod, "finish_convoy", lambda rt, fl: answers(fl["reqs"], fl["ids"]))


def _run(cell, trace=False):
    return bench_support.bench_run().run_cell(MANIFEST, cell, SEED, 0.1, trace)


def _load_line(printed):
    due, refused, unsent, unfinished = map(int, re.search(
        r"open loop: (\d+) requests due, (\d+) refused, (\d+) unsent, (\d+) unfinished", printed).groups())
    return {"due": due, "refused": refused, "unsent": unsent, "unfinished": unfinished}


def test_a_burst_longer_than_256_is_admitted_whole_at_the_stated_depth(answered_from_the_reference, capsys):
    result = _run("tiny_deep.burst")
    printed = capsys.readouterr().out
    assert result["correct"] is True
    assert (result["attempted"], result["failed"]) == (400, 0)
    assert _load_line(printed) == {"due": 400, "refused": 0, "unsent": 0, "unfinished": 0}
    compared = dict(result["compared"])
    assert compared.pop("shares_compared_ceremonies") == 400
    assert compared == dict.fromkeys(COUNTS, 0)
    # the result's last key, and the same numbers beside their limits on the log
    assert list(result)[-1] == "compared"
    assert "compared not_done = 0 (limit 0)" in printed and "compared shares_compared_ceremonies = 400 (at least 1)" in printed
    # and what the interpreter's collector did meanwhile, beside the pace
    assert re.search(r"collections in the window and its drain by generation \[\d+, \d+, \d+\]; the longest", printed)


def test_the_same_burst_before_the_default_queue_is_incorrect_by_not_done_alone(answered_from_the_reference, capsys):
    result = _run("tiny_default.burst")
    load = _load_line(capsys.readouterr().out)
    assert result["correct"] is False
    assert result["attempted"] == load["due"] == 400
    assert load["refused"] == result["failed"] == result["compared"]["not_done"] >= 400 - 256 - 64
    assert (load["unsent"], load["unfinished"]) == (0, 0)
    assert result["compared"]["shares_compared_ceremonies"] == 400 - load["refused"]
    assert {k: result["compared"][k] for k in COUNTS[1:]} == dict.fromkeys(COUNTS[1:], 0)
    # a refused request is the worst latency there is: window plus drain
    assert result["metrics"]["latency_p95_ms"]["value"] == pytest.approx((0.1 + 120.0) * 1e3)


@pytest.mark.parametrize("what", ["share", "master"])
def test_an_answer_altered_where_it_is_produced_is_not_correct_under_open_loop_arrivals(answered_from_the_reference, what):
    with bench_support.broken_engine(what):
        result = _run("tiny_deep.burst")
    assert result["correct"] is False and (result["attempted"], result["failed"]) == (400, 0)
    assert result["compared"]["share_limbs_off" if what == "share" else "master_mismatch"] > 0
    assert result["compared"]["not_done"] == 0


def test_the_widths_the_arrivals_form_are_read_from_the_programs_counters(answered_from_the_reference):
    result = _run("tiny_deep.burst", trace=True)
    assert result["correct"] is True
    # 400 requests before one worker that pops up to 8: the ladder's widths, most of them 8
    assert 4.0 < result["metrics"]["convoy_width_mean.loaded"]["value"] <= 8.0
    assert result["metrics"]["convoy_width_mean.loaded"]["unit"] == "ceremonies"


# ---------------------------------------------------------------------------
# the arithmetic of the log's lines, on made records
# ---------------------------------------------------------------------------


def _stats():
    bench_support.bench_run()
    import bench_stats

    return bench_stats


def _uniform(seconds, per_tenth, skip=()):
    """`per_tenth` ceremonies fetched evenly in every tenth of the window but those in `skip`, in convoys of four."""
    records = []
    for tenth in range(10):
        if tenth in skip:
            continue
        for i in range(per_tenth):
            fetched = (tenth + (i // 4 * 4 + 0.5) / per_tenth) * seconds / 10
            records.append({"engine_s": 1e-3 * (1 + tenth * per_tenth + i // 4), "fetched_s": fetched, "status": "done", "ok": True})
    return records


def _reader(folder, name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, folder, name, ".py")).read


def test_pace_median_reads_the_whole_window_rate_on_a_uniform_run():
    records = _uniform(51.0, 2760)
    ctx = {"records": records, "seconds": 51.0}
    assert _stats().tenths(records, 51.0) == [2760] * 10
    assert _reader("layer_metrics", "pace_median_per_s")(ctx) == pytest.approx(27600 / 51.0)
    assert _reader("end_to_end", "ceremonies_per_s")(ctx) == pytest.approx(27600 / 51.0)


def test_pace_median_stays_where_a_run_lost_a_tenth_and_the_whole_window_reads_under_it_by_that_share():
    records = _uniform(51.0, 2760, skip=(3,))
    ctx = {"records": records, "seconds": 51.0}
    pace, whole = _reader("layer_metrics", "pace_median_per_s")(ctx), _reader("end_to_end", "ceremonies_per_s")(ctx)
    assert pace == pytest.approx(27600 / 51.0)
    assert whole == pytest.approx(0.9 * pace)
    # slower throughout: both read low together
    slow = {"records": _uniform(51.0, 2484), "seconds": 51.0}
    assert _reader("layer_metrics", "pace_median_per_s")(slow) == pytest.approx(_reader("end_to_end", "ceremonies_per_s")(slow))
    assert _reader("layer_metrics", "pace_median_per_s")(slow) == pytest.approx(0.9 * pace)


def test_pace_counts_what_convoy_times_counts_and_reads_nothing_from_nothing():
    stats = _stats()
    records = [
        {"engine_s": 0.01, "fetched_s": 0.5, "status": "done"},
        {"engine_s": 0.01, "fetched_s": 0.6, "status": "done"},
        {"engine_s": None, "fetched_s": None, "status": "refused"},  # no outcome: not counted
        {"engine_s": 0.02, "fetched_s": 10.5, "status": "done"},  # fetched in the drain: the last tenth's
    ]
    assert stats.tenths(records, 10.0) == [2, 0, 0, 0, 0, 0, 0, 0, 0, 1]
    assert len(stats.convoy_times(records)) == 2
    assert _reader("layer_metrics", "pace_median_per_s")({"records": records[2:3], "seconds": 10.0}) is None


def test_the_pace_line_and_the_reader_share_one_function(capsys):
    run = bench_support.bench_run()
    records = _uniform(10.0, 40, skip=(7,))
    run._pace(records, 10.0, 0)
    printed = capsys.readouterr().out
    assert f"ceremonies per tenth {[40] * 7 + [0] + [40] * 2}" in printed
    assert f"their median as a rate {_stats().pace_median_per_s(records, 10.0):.3f}/s" in printed


def test_open_loop_load_tells_a_backlog_that_grows_from_one_that_does_not():
    stats = _stats()

    def rec(due, wait, late=0.0, status="done"):
        fetched = None if status != "done" else due + wait
        return {"due_s": due, "sent_s": None if status == "unsent" else due + late, "fetched_s": fetched, "status": status}

    held = [rec(0.05 * i, 0.2, late=0.004 if i == 3 else 0.0005) for i in range(1000)]  # 50 s at 20/s
    load = stats.open_loop_load(held, 50.0)
    assert (load["refused"], load["unsent"], load["unfinished"]) == (0, 0, 0)
    assert load["latency_by_fifth_s"] == [pytest.approx(0.2)] * 5
    # the generator's lateness leaves out the window's first second, where request 3 was 4 ms late
    assert load["late_max_s"] == pytest.approx(0.0005) and load["late_p99_s"] == pytest.approx(0.0005)
    growing = [rec(0.05 * i, 0.2 + 0.01 * i) for i in range(1000)]
    fifths = stats.open_loop_load(growing, 50.0)["latency_by_fifth_s"]
    assert fifths[4] > 1.5 * fifths[1]
    missing = held[:10] + [rec(1.0, 0, status="refused"), rec(2.0, 0, status="unfinished"), rec(3.0, 0, status="unsent")]
    load = stats.open_loop_load(missing, 50.0)
    assert (load["refused"], load["unsent"], load["unfinished"]) == (1, 1, 1)
    assert load["latency_by_fifth_s"][1:] == [None] * 4


# ---------------------------------------------------------------------------
# the cell, its rate and the queue's depth, as data
# ---------------------------------------------------------------------------


def _load(folder, name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return json.loads(run.find(roots, folder, name, ".json").read_text())


def test_the_cell_states_its_rate_beside_the_knee_and_the_share():
    run = bench_support.bench_run()
    manifest, _ = run.load_manifest(bench_support.MANIFEST)
    entry = next(w for w in manifest["workloads"] if w["name"] == "fleet_mix_reduced.loaded")
    cell, steady = _load("workloads", "fleet_mix_reduced.loaded"), _load("workloads", "fleet_mix_reduced.steady")
    assert (entry["config"], entry["chips"], cell["config"]) == ("secp256k1_fleet_mix", 1, "secp256k1_fleet_mix")
    assert cell["traffic"]["kind"] == steady["traffic"]["kind"] == "open_loop_poisson"
    assert (cell["drain_s"], cell["trace_seconds"]) == (steady["drain_s"], steady["trace_seconds"]) == (60.0, 0.75)
    rule = cell["rate_rule"]
    assert rule["share_of_knee"] in (0.8, 0.7, 0.6, 0.5)
    assert cell["traffic"]["rate_per_s"] == round(rule["share_of_knee"] * rule["knee_per_s"])
    assert f"{cell['traffic']['rate_per_s']:.0f}/s" in entry["why"]
    assert [m["name"] for m in run.metrics_for(manifest, "end_to_end", entry["name"])] == ["latency_p95_ms", "setup_s"]
    layer = {m["name"] for m in run.metrics_for(manifest, "per_layer", entry["name"])}
    assert {"convoy_width_mean.loaded", "queue_wait_ms", "queue_wait_program_ms", "convoy_hold_ms.steady", "convoy_device_wait_ms.steady"} <= layer
    pace = next(m for m in manifest["per_layer"] if m["name"] == "pace_median_per_s")
    assert (pace["moves"], pace["workloads"], pace["source"]) == ("ceremonies_per_s", ["fleet_mix_reduced.saturated"], "host_clock")


@pytest.mark.parametrize("config", ["secp256k1_fleet_mix", "secp256k1_fleet_mix_full"])
def test_the_queue_holds_the_rate_through_the_longest_hole_on_record(config):
    cell, deployed = _load("workloads", "fleet_mix_reduced.loaded"), _load("configs", config)
    depth = deployed["scheduler"]["queue_depth"]
    # the smallest power of two at or above twice rate x 4.09 s, the longest hole on record (PERF.md section 4), stated under `assumed`
    assert depth == 2 ** math.ceil(math.log2(2 * cell["traffic"]["rate_per_s"] * 4.09))
    assert any(f"scheduler.queue_depth {depth}" in line for line in deployed["assumed"])


def test_the_bound_of_the_rate_is_one_of_the_rules_three():
    manifest = json.loads(bench_support.MANIFEST.read_text())
    rate = next(m for m in manifest["end_to_end"] if m["name"] == "ceremonies_per_s")
    assert rate["bound"] in (0.02, 0.03, 0.05) and rate["workloads"] == ["fleet_mix_reduced.saturated"]
