"""`fleet_mix.saturated` rehearsed on the CPU with a tiny mix of the tests' own:
secp256k1 (6,2) and (8,2) in bucket (8,2) beside (12,4) in bucket (16,4), with
`WIDTH_CAP_N` lowered to 16 so that the second bucket takes the width-1 path beside
a stacked one.  Every shape and one stacked convoy of two real sizes against the
plain reference; the tiny cell through `run_cell` closed loop, open loop under its
capacity, and open loop over a queue of depth 2 (what an overloaded open-loop run
trips in `correct`: ISSUE 36's reckoning of PR 32's refusal); the broken-engine
control; each new reader on a run that has its series and on a program that has
not; the configuration's file held to the reduced one's.  One file, so that one
worker compiles the (8,2) width-1 and width-2 and the (16,4) width-1 programs once."""

import collections
import json
import re

import numpy as np
import pytest

import bench_support

MANIFEST = bench_support.DATA.parent / "data_mix" / "manifest.json"
CURVE = "secp256k1"
SEED = 2**31 + 36
COUNTS = (
    "not_done", "unqualified", "complaints", "master_mismatch", "share_limbs_off",
    "window_aot_builds", "window_aot_disk_loads", "window_aot_errors", "window_jax_stage_events",
)
BUCKET_READERS = (
    "heavy_convoy_ms.mix", "light_convoy_ms.mix", "mid_convoy_width_mean.mix", "queue_wait_heavy_ms.mix",
)
STAGE_READERS = ("convoy_hold_ms.mix", "convoy_device_wait_ms.mix")


@pytest.fixture(autouse=True)
def heavy_from_16(monkeypatch):
    from dkg_tpu.service import buckets

    monkeypatch.setattr(buckets, "WIDTH_CAP_N", 16)


@pytest.fixture()
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(cell, trace=False, seconds=2.0):
    return bench_support.bench_run().run_cell(MANIFEST, cell, SEED, seconds, trace)


def _compared(printed):
    return {k: int(v) for k, v in re.findall(r"compared (\w+) = (\d+) \(", printed)}


def _reader(name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


def _against_the_reference(reqs, outs):
    import bench_oracle

    for req, out in zip(reqs, outs):
        assert out.status == "done" and len(out.qualified) == req.n and all(out.qualified)
        assert np.asarray(out.final_shares).shape == (req.n, 16)
        plain = {"curve": CURVE, "n": req.n, "t": req.t, "seed": req.seed}
        # master and EVERY final share, real lanes only, against Python ints
        assert not any(bench_oracle.check_outcome(plain, out, list(range(1, req.n + 1))).values())


@pytest.mark.parametrize("n,t,bucket", [(8, 2, (8, 2)), (6, 2, (8, 2)), (12, 4, (16, 4))], ids=["exact_8", "padded_6_in_8", "padded_12_in_16"])
def test_every_shape_of_the_mix_equals_the_plain_reference(n, t, bucket):
    bench_support.bench_run()  # puts benchmark/ on sys.path
    from dkg_tpu.service import WarmRuntime, engine

    req = engine.CeremonyRequest(CURVE, n, t, seed=2**33 + 36 + n)
    assert (req.bucket().n, req.bucket().t) == bucket
    outs = engine.run_convoy(WarmRuntime(), [req])
    _against_the_reference([req], outs)
    assert (outs[0].bucket_n, outs[0].bucket_t) == bucket


def test_one_stacked_convoy_of_two_real_sizes_equals_the_plain_reference():
    bench_support.bench_run()
    from dkg_tpu.service import WarmRuntime, engine

    reqs = [engine.CeremonyRequest(CURVE, n, 2, seed=2**33 + 360 + n) for n in (6, 8)]
    assert reqs[0].convoy_key() == reqs[1].convoy_key()
    _against_the_reference(reqs, engine.run_convoy(WarmRuntime(), reqs))


def test_closed_loop_over_two_buckets_is_correct(cache_in_tmp, capsys):
    from dkg_tpu.service import aot

    result = _run("tiny_mix.closed")
    printed = capsys.readouterr().out
    assert result["correct"] is True
    assert result["attempted"] >= 4 and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p95_ms", "setup_s"}
    compared = _compared(printed)
    assert compared.pop("shares_compared_ceremonies") == result["attempted"]
    assert compared == dict.fromkeys(COUNTS, 0)
    # one convoy of every (bucket, width) is warmed: the lowered cap keeps (16,4) at width 1
    for warmed in ("warm (8,2) x2", "warm (8,2) x1", "warm (16,4) x1"):
        assert warmed in printed
    assert "warm (16,4) x2" not in printed
    widths = collections.defaultdict(set)
    for key in aot._PROC:
        if key[1] == CURVE:
            widths[key[2:4]].add(key[4])
    assert widths[(8, 2)] >= {1, 2} and widths[(16, 4)] == {1}


def test_open_loop_under_capacity_is_correct(cache_in_tmp, capsys):
    result = _run("tiny_mix.open", seconds=3.0)
    compared = _compared(capsys.readouterr().out)
    assert result["correct"] is True
    assert (result["attempted"], result["failed"]) == (6, 0)
    assert compared.pop("shares_compared_ceremonies") == 6
    assert compared == dict.fromkeys(COUNTS, 0)


def test_open_loop_over_a_short_queue_is_incorrect_by_not_done_alone(cache_in_tmp, capsys):
    """What refused PR 32, as ISSUE 36 reckons it: an open-loop cell whose queue
    overflows (there a stall of seconds at 0.8 x the knee, here a queue of depth 2
    at a rate one worker cannot hold).  Every refused request is a record without
    an outcome: `not_done`, limit 0, and nothing else in the comparison moves."""
    result = _run("tiny_mix.overload", seconds=1.0)
    compared = _compared(capsys.readouterr().out)
    assert result["correct"] is False
    assert result["attempted"] == 200
    assert compared.pop("shares_compared_ceremonies") >= 1
    assert compared.pop("not_done") == result["failed"] > 100
    assert compared == dict.fromkeys(COUNTS[1:], 0)


@pytest.mark.parametrize("what", ["share", "master"])
def test_broken_timed_path_is_not_correct_on_the_mix(cache_in_tmp, capsys, what):
    with bench_support.broken_engine(what):
        result = _run("tiny_mix.closed")
    compared = _compared(capsys.readouterr().out)
    assert result["correct"] is False and result["attempted"] >= 4
    assert compared["share_limbs_off" if what == "share" else "master_mismatch"] > 0
    assert compared["not_done"] == 0


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


def test_traced_run_reports_the_stage_readers_and_leaves_out_what_has_no_series(cache_in_tmp):
    result = _run("tiny_mix.closed", trace=True)
    assert result["correct"] is True
    for name in STAGE_READERS:
        assert result["metrics"][name]["value"] > 0, name
    done = result["attempted"] - result["failed"]
    assert result["metrics"]["throughput_per_s.mix"] == {"value": done / 2.0, "unit": "ceremonies/s"}
    # the tiny mix has no bucket of the real mix, and the CPU no device plane
    assert not set(BUCKET_READERS + ("pallas_time_share.mix",)) & set(result["metrics"])


@pytest.fixture()
def served_mix(monkeypatch):
    """The real mix's five shapes through the scheduler itself with the engine taken
    out (no JAX work): the registry's snapshots around it, as `ctx["counters"]`."""
    from dkg_tpu.service import CeremonyOutcome, CeremonyRequest, CeremonyScheduler
    from dkg_tpu.service import buckets
    from dkg_tpu.service import scheduler as scheduler_mod
    from dkg_tpu.utils.metrics import MetricsRegistry

    monkeypatch.setattr(buckets, "WIDTH_CAP_N", 64)  # as shipped

    def finish(runtime, fl):
        return [
            CeremonyOutcome(
                ceremony_id=cid, status="done", curve=r.curve, n=r.n, t=r.t,
                bucket_n=r.bucket().n, bucket_t=r.bucket().t, qualified=(True,) * r.n,
            )
            for cid, r in zip(fl["ids"], fl["reqs"])
        ]

    monkeypatch.setattr(scheduler_mod, "start_convoy", lambda rt, reqs, ids=None: {"reqs": list(reqs), "ids": list(ids)})
    monkeypatch.setattr(scheduler_mod, "finish_convoy", finish)
    reg = MetricsRegistry()
    before = reg.snapshot()
    shapes = [(16, 5)] * 8 + [(24, 8), (32, 8), (24, 8)] + [(48, 16), (64, 16)]
    sch = CeremonyScheduler(concurrency=1, queue_depth=64, batch_max=8, runtime=object(), metrics=reg)
    try:
        with sch._cond:  # queued as one: the pops find whole convoys
            cids = [sch.submit(CeremonyRequest(CURVE, n, t, seed=i)) for i, (n, t) in enumerate(shapes)]
        assert all(sch.result(cid, timeout=30).status == "done" for cid in cids)
    finally:
        sch.close()
    return {"before": before, "after": reg.snapshot()}


def test_bucket_readers_read_the_series_the_scheduler_books(served_mix):
    bench_support.bench_run()
    hist = served_mix["after"]["histograms"]
    # light: one width-8 stack; thin: three of two sizes popped as 2 and 1; heavy: width 1 each
    assert {k for k in hist if k.startswith("service_convoy_seconds")} == {
        'service_convoy_seconds{bucket="16x5",width="8"}',
        'service_convoy_seconds{bucket="32x8",width="2"}',
        'service_convoy_seconds{bucket="32x8",width="1"}',
        'service_convoy_seconds{bucket="64x16",width="1"}',
    }
    assert hist['service_convoy_seconds{bucket="64x16",width="1"}']["count"] == 2
    ctx = {"counters": served_mix}
    heavy, light = _reader("heavy_convoy_ms.mix").read(ctx), _reader("light_convoy_ms.mix").read(ctx)
    assert heavy == pytest.approx(hist['service_convoy_seconds{bucket="64x16",width="1"}']["sum"] / 2 * 1e3)
    assert light == pytest.approx(hist['service_convoy_seconds{bucket="16x5",width="8"}']["sum"] * 1e3)
    assert _reader("mid_convoy_width_mean.mix").read(ctx) == pytest.approx(1.5)
    wait = hist['service_queue_wait_seconds{bucket="64x16"}']
    assert wait["count"] == 2
    assert _reader("queue_wait_heavy_ms.mix").read(ctx) == pytest.approx(wait["sum"] / 2 * 1e3)


@pytest.mark.parametrize("name", BUCKET_READERS + STAGE_READERS)
def test_mix_readers_return_none_on_a_program_without_the_series(name):
    # the parent books `service_convoy_seconds` by width alone, and nothing was popped
    parent = {"service_convoy_seconds{width=\"8\"}": {"sum": 1.0, "count": 4}}
    empty = {"before": {"histograms": {}}, "after": {"histograms": parent}}
    assert _reader(name).read({"counters": empty}) is None


def test_trace_reader_on_a_recorded_slice_and_without_one():
    bench_support.bench_run()
    import bench_trace

    recorded = json.loads((bench_support.DATA / "trace_recorded.json").read_text())
    trace = bench_trace.reduce(recorded["events"], recorded["host_window_s"])
    value = _reader("pallas_time_share.mix").read({"trace": trace})
    assert value == _reader("pallas_time_share").read({"trace": trace}) and 0 < value <= 100.0
    assert _reader("pallas_time_share.mix").read({"trace": None}) is None


# ---------------------------------------------------------------------------
# the configuration and the cell, as data
# ---------------------------------------------------------------------------


def _load(folder, name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return json.loads(run.find(roots, folder, name, ".json").read_text())


def test_the_full_mix_is_the_reduced_configuration_with_nothing_cut():
    full, reduced = _load("configs", "secp256k1_fleet_mix_full"), _load("configs", "secp256k1_fleet_mix")
    assert full["mix"] == reduced["source_mix"]
    for key in ("curve", "rho_bits", "scheduler", "share_check", "guarantees", "assumed"):
        assert full[key] == reduced[key], key
    assert full["reduced"] == [] and full["architecture"] is None
    manifest = json.loads(bench_support.MANIFEST.read_text())
    entry = next(c for c in manifest["configs"] if c["name"] == full["name"])
    assert entry["source"] == full["source"] and entry["reduced"] == []


def test_the_buckets_the_file_states_are_the_programs():
    from dkg_tpu.service import buckets

    full = _load("configs", "secp256k1_fleet_mix_full")
    stated = {tuple(row): tuple(b["bucket"]) for b in full["buckets"] for row in b["rows"]}
    for m in full["mix"]:
        b = buckets.bucket_for(m["n"], m["t"])
        assert stated[(m["n"], m["t"])] == (b.n, b.t)
    pairs = {tuple(b["bucket"]): b["share_pairs_per_1000"] for b in full["buckets"]}
    for b in full["buckets"]:
        n = b["bucket"][0]
        assert b["share_pairs_per_1000"] == n * n * b["requests_per_1000"]
        assert b["work_share"] == pytest.approx(b["share_pairs_per_1000"] / sum(pairs.values()), abs=0.005)


def test_the_cell_sends_the_mix_in_blocks_of_125(monkeypatch):
    from dkg_tpu.service import buckets

    monkeypatch.setattr(buckets, "WIDTH_CAP_N", 64)  # as shipped
    run = bench_support.bench_run()
    manifest, roots = run.load_manifest(bench_support.MANIFEST)
    entry = next(w for w in manifest["workloads"] if w["name"] == "fleet_mix.saturated")
    cell, full = _load("workloads", "fleet_mix.saturated"), _load("configs", "secp256k1_fleet_mix_full")
    twin = _load("workloads", "fleet_mix_reduced.saturated")
    assert (entry["config"], entry["chips"], cell["config"]) == (full["name"], 1, full["name"])
    assert cell["traffic"] == twin["traffic"] == {"kind": "closed_loop", "outstanding": 64}
    assert cell["drain_s"] == twin["drain_s"]
    # end to end the latency, and not the rate whose bound a host-paced cell cannot resolve
    assert [m["name"] for m in run.metrics_for(manifest, "end_to_end", entry["name"])] == ["latency_p95_ms", "setup_s"]
    kind = run.load_module(run.find(roots, "traffic", "closed_loop", ".py"))
    plan = kind.plan(cell["traffic"], full, 2**31 + 5, 51.0)
    blocks = [[next(plan["requests"]) for _ in range(125)] for _ in range(2)]
    for block in blocks:
        assert all(due is None for due, _ in block)
        shapes = collections.Counter((req["n"], req["t"]) for _, req in block)
        assert shapes == {(16, 5): 112, (24, 8): 7, (32, 8): 3, (48, 16): 2, (64, 16): 1}
    assert [r["n"] for _, r in blocks[0]] != [r["n"] for _, r in blocks[1]]  # shuffled block by block
    # one throwaway convoy of every (bucket, width) the cell can form: 4 + 4 + 1 program sets
    sets = run._warm_sets(full, plan["outstanding"])
    assert sets == [(16, 5, w) for w in (8, 4, 2, 1)] + [(32, 8, w) for w in (8, 4, 2, 1)] + [(64, 16, 1)]
