"""The whole loop on the CPU with a tiny cell of the tests' own, and the
reference against the engine.  One file, so that one worker compiles the
(8,2) width-1 programs once."""

import numpy as np
import pytest

import bench_support


@pytest.fixture()
def cache_in_tmp(tmp_path, monkeypatch):
    # the store and the compile cache root go where the test can throw them
    # away; with the variable set the harness switches no compile cache on
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(trace):
    run = bench_support.bench_run()
    return run.run_cell(bench_support.TEST_MANIFEST, "tiny.burst", 2**31 + 11, 1.0, trace)


def test_rehearsal_whole_loop(cache_in_tmp, capsys):
    result = _run(trace=False)
    assert result["correct"] is True
    assert (result["attempted"], result["failed"]) == (3, 0)
    assert result["device"]["platform"] == "cpu"  # named for what it is, never a device metric
    assert set(result["metrics"]) == {"latency_p95_ms", "setup_s"}
    assert result["metrics"]["latency_p95_ms"]["value"] > 0
    printed = capsys.readouterr().out
    for name in ("master_mismatch", "share_limbs_off", "window_jax_stage_events"):
        assert f"compared {name} = 0 (limit 0)" in printed
    assert "pace: 3 ceremonies in 3 convoys counted, 0 ceremonies not counted" in printed


def test_additions_are_files_alone(cache_in_tmp, capsys):
    # the cell, its traffic kind and this metric exist only under tests/benchmark/data
    result = _run(trace=True)
    assert result["correct"] is True
    assert result["metrics"]["dummy_requests_seen"] == {"value": 3.0, "unit": "requests"}
    assert "deal_device_ms" not in result["metrics"]  # no device plane on the CPU: nothing to read
    # the traced window is the harness's own span as the profiler recorded it, and busy lies inside it
    assert "(from mark; the host's clock read" in capsys.readouterr().out
    assert 0 <= result["device"]["busy_s"] <= result["device"]["window_s"]
    assert 0 < result["device"]["window_s"] < 2.0


@pytest.mark.parametrize("what", ["share", "master"])
def test_broken_timed_path_is_not_correct(cache_in_tmp, what):
    with bench_support.broken_engine(what):
        result = _run(trace=False)
    assert result["correct"] is False
    assert result["attempted"] == 3


def test_reference_against_engine_and_one_limb_off(cache_in_tmp):
    import bench_oracle

    from dkg_tpu.service import WarmRuntime, engine

    bench_support.bench_run()
    req = {"curve": "secp256k1", "n": 8, "t": 2, "seed": 2**33 + 5}
    out = engine.run_convoy(WarmRuntime(), [engine.CeremonyRequest("secp256k1", 8, 2, seed=req["seed"])])[0]
    parties = [1, 5, 8]
    assert not any(bench_oracle.check_outcome(req, out, parties).values())
    out.final_shares = np.array(out.final_shares)
    out.final_shares[4, 3] ^= 1
    assert bench_oracle.check_outcome(req, out, parties)["share_limbs_off"] == 1
    out.master = out.master[:-1] + bytes([out.master[-1] ^ 1])
    assert bench_oracle.check_outcome(req, out, parties)["master_mismatch"] == 1
