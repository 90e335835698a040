"""`ceremony_bls_n1024.closed` rehearsed on the CPU with the smallest bucket:
bls12_381_g1 (a 24-limb base field beside a 16-limb scalar field, where secp256k1
has 16 and 16) through `engine.run_convoy` against the plain reference and
`run_single_reference`, exact and padded; a tiny cell through `run_cell`, the
broken-engine control, and each of the cell's readers on a run that has its series
and on a program that has not.  One file, so that one worker compiles the
bls12_381_g1 (8,2) width-1 programs once (XLA:CPU takes about a minute for them)."""

import json

import numpy as np
import pytest

import bench_support

MANIFEST = bench_support.DATA.parent / "data_bls" / "manifest.json"
CURVE = "bls12_381_g1"
SPAN_READERS = (
    "convoy_host_ms.bls", "convoy_device_wait_ms.bls", "setup_programs_s.bls", "setup_tables_s.bls",
)
TRACE_READERS = {
    "deal_device_ms.bls": "jit_deal",
    "verify_device_ms.bls": "jit_verify_batch",
    "pallas_time_share.bls": None,
    "digest_time_share.bls": None,
}


@pytest.fixture()
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(trace):
    return bench_support.bench_run().run_cell(MANIFEST, "tiny_bls.closed", 2**31 + 34, 3.0, trace)


def _reader(name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


@pytest.mark.parametrize("n", [8, 6], ids=["exact", "padded_6_in_8"])
def test_served_bls_ceremony_equals_the_plain_reference(n):
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    from dkg_tpu.service import WarmRuntime, engine

    seed = 2**33 + 34 + n
    req = engine.CeremonyRequest(CURVE, n, 2, seed=seed)
    assert (req.bucket().n, req.bucket().t) == (8, 2)
    out = engine.run_convoy(WarmRuntime(), [req])[0]
    assert out.status == "done" and len(out.qualified) == n and all(out.qualified)
    # master and EVERY final share, real lanes only, against Python ints
    plain = {"curve": CURVE, "n": n, "t": 2, "seed": seed}
    assert not any(bench_oracle.check_outcome(plain, out, list(range(1, n + 1))).values())
    # the scalar field has 16 limbs, the base field 24: shares are scalars
    assert np.asarray(out.final_shares).shape == (n, 16)
    assert out.master == engine.run_single_reference(req)


def test_one_bls_request_in_flight_is_correct(cache_in_tmp, capsys):
    from dkg_tpu.service import aot

    result = _run(trace=False)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p95_ms", "setup_s"}
    printed = capsys.readouterr().out
    for name in ("master_mismatch", "share_limbs_off", "window_aot_builds", "window_jax_stage_events"):
        assert f"compared {name} = 0 (limit 0)" in printed
    kinds = {(key[0], key[4]) for key in aot._PROC if key[1:4] == (CURVE, 8, 2)}
    assert kinds == {("deal", 1), ("verify", 1), ("aggregate", 1), ("master", 1)}


@pytest.mark.parametrize("what", ["share", "master"])
def test_broken_timed_path_is_not_correct_on_bls(cache_in_tmp, what):
    with bench_support.broken_engine(what):
        result = _run(trace=False)
    assert result["correct"] is False
    assert result["attempted"] >= 1


def test_span_readers_read_a_rehearsed_bls_run(cache_in_tmp):
    from dkg_tpu.groups import precompute as gp
    from dkg_tpu.service import aot
    from dkg_tpu.utils.metrics import REGISTRY

    # a process that starts: no table, no program, no series
    gp.reset()
    aot.reset()
    REGISTRY.reset()
    result = _run(trace=True)
    assert result["correct"] is True
    for name in SPAN_READERS:
        assert result["metrics"][name]["value"] > 0, name
    assert result["metrics"]["setup_tables_s.bls"]["unit"] == "s"
    # two tables (g, h), each a host table built or loaded once: booked once a table
    hist = REGISTRY.snapshot()["histograms"]
    tables = {k: v["count"] for k, v in hist.items() if k.startswith("fixed_base_table_seconds")}
    assert sum(tables.values()) == 2 and all(f'curve="{CURVE}"' in k for k in tables)
    # the store's series carry the curve
    assert any(k.startswith("aot_build_stage_seconds{") and f'curve="{CURVE}"' in k for k in hist)
    # no device plane on the CPU: the device-trace readers find nothing and are left out
    assert not set(TRACE_READERS) & set(result["metrics"])


@pytest.mark.parametrize("name", SPAN_READERS)
def test_bls_span_readers_return_none_on_a_program_without_the_series(name):
    empty = {"before": {"histograms": {}}, "after": {"histograms": {"service_convoy_seconds": {"sum": 1.0, "count": 1}}}}
    assert _reader(name).read({"counters": empty}) is None


def test_setup_tables_adds_every_table_and_source():
    bench_support.bench_run()
    hist = {
        'fixed_base_table_seconds{curve="bls12_381_g1",source="disk"}': {"sum": 0.5, "count": 2},
        'fixed_base_table_seconds{curve="bls12_381_g1",source="compose"}': {"sum": 17.0, "count": 2},
        'aot_load_seconds{curve="bls12_381_g1"}': {"sum": 3.0, "count": 4},
    }
    ctx = {"counters": {"before": {"histograms": dict(hist)}, "after": {"histograms": hist}}}
    # all of it was booked before the window: the snapshot whole, not a delta
    assert _reader("setup_tables_s.bls").read(ctx) == pytest.approx(17.5)
    # the program series keep adding up under their new label
    hist['digest_leg_first_call_seconds{curve="bls12_381_g1",shape="1024x342"}'] = {"sum": 7.0, "count": 1}
    assert _reader("setup_programs_s.bls").read(ctx) == pytest.approx(10.0)
    assert _reader("setup_programs_s.closed").read(ctx) == pytest.approx(10.0)


@pytest.mark.parametrize("name", sorted(TRACE_READERS))
def test_bls_trace_readers_on_a_recorded_slice_and_without_one(name):
    bench_support.bench_run()
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
    if name == "pallas_time_share.bls":
        assert value <= 100.0
    if name == "digest_time_share.bls":
        # the recording keeps every module event and few operations, so its busy time is
        # not the modules': the reader is held to its formula, as `digest_time_share`'s is
        digest_s = bench_trace.modules_s(trace, ("jit_affine_canon", "jit__tree_from_words_jit"))
        assert digest_s > 0 and value == pytest.approx(100.0 * digest_s / trace["busy_s"])
    assert _reader(name).read({"trace": None}) is None
    if module is not None:
        assert _reader(name).read({"trace": dict(trace, module_runs={}, module_runs_cut={})}) is None


def test_the_bls_cell_is_the_large_cell_with_the_curve_as_the_only_difference():
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)

    def load(folder, name):
        return json.loads(run.find(roots, folder, name, ".json").read_text())

    bls, secp = load("configs", "bls12_381_g1_n1024_t341"), load("configs", "secp256k1_n1024_t341")
    for key in ("mix", "rho_bits", "scheduler", "share_check", "guarantees"):
        assert bls[key] == secp[key], key
    assert (bls["curve"], bls["reduced"], bls["published"]) == (CURVE, ["n", "t"], {"n": 16384, "t": 5461})
    cell, twin = load("workloads", "ceremony_bls_n1024.closed"), load("workloads", "ceremony_n1024.closed")
    assert cell["traffic"]["kind"] == twin["traffic"]["kind"] == "closed_loop_prepared"
    assert cell["traffic"]["outstanding"] == 1 and cell["drain_s"] == twin["drain_s"]
    kind = run.load_module(run.find(roots, "traffic", "closed_loop_prepared", ".py"))
    due, req = next(kind.plan(cell["traffic"], bls, 2**31 + 5, 51.0)["requests"])
    assert due is None and (req["n"], req["t"], req["curve"]) == (1024, 341, CURVE)
