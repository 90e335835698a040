"""The served path's sharded route (`buckets.shard_devices`, `engine._finish_sharded`) on a
mesh of four of the eight virtual CPU devices `conftest.py` forces, with `SHARD_MIN_N` set
down to the smallest bucket.

One module-scoped fixture a curve serves ONE (8,2) request through a `CeremonyScheduler`
on the sharded route (the four `shard_map` programs compile once, through the executable
store, with the digest's device leg forced so that `mesh_digest_rows` serves), then the same
request on the one-device route, then the request with a tampered share and with three
cheating dealers on both routes (the blame branch: `mesh_blame` and `mesh_finalise` compile
here, small at this size), and every assertion hangs on what it kept.  The block draw's
threshold is set down to the request's 24 scalars, so every request of the module draws
into tensors the runtime keeps (`engine.CoeffStaging`).  Not marked slow: nothing else
compiles a mesh program in tier 1.
"""

import dataclasses
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.fields import host as fh
from dkg_tpu.parallel import mesh as pm
from dkg_tpu.service import CeremonyRequest, CeremonyScheduler, WarmRuntime, aot, buckets, engine
from dkg_tpu.utils.metrics import REGISTRY

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
import bench_support  # noqa: E402

N, T, SEED, DEVICES = 8, 2, 2**33 + 44, 4
MESH_KINDS = ("mesh_deal_commitments", "mesh_deal_shares", "mesh_digest_rows", "mesh_verify_finalise")

pytestmark = pytest.mark.usefixtures("free_compiled_programs")


def _counter(name: str) -> float:
    return sum(v for k, v in REGISTRY.snapshot()["counters"].items() if k.split("{")[0] == name)


def _staging_counts():
    c = REGISTRY.snapshot()["counters"]
    return tuple(c.get(f'coeff_staging_total{{event="{e}"}}', 0) for e in ("alloc", "reuse"))


def _serve(runtime, req):
    sched = CeremonyScheduler(runtime=runtime, concurrency=1)
    try:
        return sched.result(sched.submit(req), timeout=900.0)
    finally:
        sched.close(drain=True)


# (dealer, recipient) pairs whose dealt share is altered after deal, 0-based: one cheat, and
# one more dealer than t = 2 allows
CHEATS = {"one": ((2, 5),), "too_many": ((1, 0), (3, 6), (6, 2))}


def _serve_tampered(runtime, req, pairs):
    """The request through the engine's two halves with `pairs` of the share matrix altered
    between them, where a cheating dealer's shares would differ: limb 0 of s[dealer,
    recipient] flipped, the array kept where it lay (a mesh's dealer sharding included)."""
    fl = engine.start_convoy(runtime, [req])
    lead = fl.s.ndim - 3  # the one-device route's ceremony axis
    s = fl.s
    for dealer, recipient in pairs:
        at = (0,) * lead + (dealer, recipient, 0)
        s = s.at[at].set(s[at] ^ 1)
    fl.s = jax.device_put(s, fl.s.sharding)
    (out,) = engine.finish_convoy(runtime, fl)
    return out


@pytest.fixture(scope="module", params=["secp256k1", "ristretto255"])
def served(request, tmp_path_factory):
    curve = request.param
    store = tmp_path_factory.mktemp(f"store_{curve}")
    mp = pytest.MonkeyPatch()
    mp.setattr(buckets, "SHARD_MIN_N", N)
    mp.setattr(buckets, "_local_device_count", lambda: DEVICES)
    mp.setenv("DKG_TPU_AOT_DIR", str(store))
    mp.setenv("DKG_TPU_DIGEST", "device")
    mp.setattr(fh, "BLOCK_MIN_SCALARS", N * (T + 1))
    aot.reset()
    kept = {"curve": curve, "store": str(store)}
    try:
        req = CeremonyRequest(curve, N, T, seed=SEED)
        runtime = WarmRuntime()
        fetched = _counter("round1_host_bytes_total")
        served_before = _counter("mesh_requests_total")
        kept["sharded"] = _serve(runtime, req)
        kept["round1_host_bytes"] = _counter("round1_host_bytes_total") - fetched
        kept["mesh_requests"] = _counter("mesh_requests_total") - served_before
        kept["aot"] = aot.stats()
        # another seed, then the first again, through the tensors the first request left
        staging = _staging_counts()
        other = dataclasses.replace(req, seed=SEED + 1)
        kept["other"] = (other, _serve(runtime, other), _serve(WarmRuntime(), other))
        kept["again"] = _serve(runtime, req)
        kept["staging"] = tuple(a - b for a, b in zip(_staging_counts(), staging))
        kept["snapshot"] = REGISTRY.snapshot()
        # the digest and rho of the same transcript, the mesh's leg beside the host leg
        mesh, g_mesh, h_mesh = runtime.mesh_route(curve, req.shared_string, req.bucket(), DEVICES)
        cfg = ce.CeremonyConfig(curve, N, T)
        store = engine.stored_mesh_program
        ca, cb = engine.draw_coeffs(cfg, engine.rng_for(req))
        a, e, s, r = pm.sharded_deal(cfg, mesh, *pm.place_coeffs(mesh, ca, cb), g_mesh, h_mesh, store)
        rows = pm.transcript_rows(cfg, mesh, a, e, s, r, store)
        kept["rows_are_device_arrays"] = all(isinstance(x, jax.Array) for x in rows)
        kept["rows_mesh"] = [np.asarray(x) for x in jax.device_get(list(rows))]
        kept["rho_mesh"] = pm.rho_from_rows(cfg, rows, 128)
        host = [np.asarray(x) for x in (a, e, s, r)]
        kept["rows_host"] = [np.asarray(x) for x in ce._dealer_rows_device(cfg, *host, dispatch="host")]
        kept["rho_host"] = ce.fiat_shamir_rho(cfg, ce._fold_digest_device(cfg, *kept["rows_host"]), 128)
        # the leg a CPU backend and a mesh across processes take: the per-shard loop
        mp.setenv("DKG_TPU_DIGEST", "host")
        fetched = _counter("round1_host_bytes_total")
        kept["rows_loop"] = [np.asarray(x) for x in pm.transcript_rows(cfg, mesh, a, e, s, r, store)]
        kept["loop_host_bytes"] = _counter("round1_host_bytes_total") - fetched
        mp.setenv("DKG_TPU_DIGEST", "device")
        # the blame branch on the mesh, then (below) on one device
        kept["blamed"] = {k: [_serve_tampered(runtime, req, pairs)] for k, pairs in CHEATS.items()}
        kept["aot_after_blame"] = aot.stats()
        kept["snapshot_after_blame"] = REGISTRY.snapshot()  # the registry is the process's: read deltas
        # the same bucket with one device: today's route (no store, the backend's own digest leg)
        mp.setattr(buckets, "_local_device_count", lambda: 1)
        mp.delenv("DKG_TPU_AOT_DIR")
        mp.delenv("DKG_TPU_DIGEST")
        served_before = _counter("mesh_requests_total")
        kept["one_device"] = _serve(runtime, req)
        kept["one_device_mesh_requests"] = _counter("mesh_requests_total") - served_before
        for k, pairs in CHEATS.items():
            kept["blamed"][k].append(_serve_tampered(runtime, req, pairs))
        yield kept
    finally:
        mp.undo()
        aot.reset()


def test_the_rule_is_the_bucket_and_the_local_devices(monkeypatch):
    big, small = buckets.Bucket(4096, 1365), buckets.Bucket(1024, 341)
    assert buckets.SHARD_MIN_N == 2048 and buckets.SHARD_MIN_DEVICES == 4
    for devices, want in ((1, 0), (2, 0), (4, 4), (8, 8), (3, 0), (5, 0)):
        monkeypatch.setattr(buckets, "_local_device_count", lambda d=devices: d)
        assert buckets.shard_devices(big) == want
        assert buckets.shard_devices(buckets.Bucket(2048, 682)) == want
    # a bucket under the crossover never asks what the process can see
    monkeypatch.setattr(buckets, "_local_device_count", lambda: pytest.fail("asked for the devices"))
    assert buckets.shard_devices(small) == 0 and buckets.shard_devices(buckets.Bucket(16, 5)) == 0
    assert pm.SERVED_FROM_STORE is True


def test_the_sharded_outcome_equals_the_one_device_route_bit_for_bit(served):
    sharded, one = served["sharded"], served["one_device"]
    assert sharded.status == one.status == "done"
    assert served["mesh_requests"] == 1 and served["one_device_mesh_requests"] == 0
    assert sharded.master == one.master
    np.testing.assert_array_equal(sharded.final_shares, one.final_shares)
    assert sharded.qualified == one.qualified == (True,) * N
    assert sharded.complaints == one.complaints == ()
    assert (sharded.bucket_n, sharded.bucket_t, sharded.convoy_width) == (one.bucket_n, one.bucket_t, 1)


def test_the_sharded_outcome_equals_the_plain_reference(served):
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    plain = {"curve": served["curve"], "n": N, "t": T, "seed": SEED}
    bad = bench_oracle.check_outcome(plain, served["sharded"], list(range(1, N + 1)))
    assert not any(bad.values()), bad
    sums = bench_oracle.column_sums(served["curve"], N, T, SEED)
    assert served["sharded"].master == bench_oracle.master_bytes(served["curve"], sums)


def test_kept_tensors_serve_seed_after_seed_on_the_sharded_route(served):
    """The runtime's one pair of coefficient tensors, rewritten request after request while
    `place_coeffs`' shards of the request before may alias it on this backend: another
    seed is the plain reference's and a fresh runtime's outcome, the first seed again is
    what it was."""
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    other, out, fresh = served["other"]
    plain = {"curve": other.curve, "n": N, "t": T, "seed": other.seed}
    assert not any(bench_oracle.check_outcome(plain, out, list(range(1, N + 1))).values())
    for got, want in ((out, fresh), (served["again"], served["sharded"])):
        assert got.status == want.status == "done" and got.master == want.master
        np.testing.assert_array_equal(got.final_shares, want.final_shares)
    assert out.master != served["sharded"].master
    # the module's first request allocated the pair; these two and the fresh runtime's one
    assert served["staging"] == (1, 2)


def test_the_meshs_digest_and_rho_are_the_host_legs(served):
    assert served["rows_are_device_arrays"]
    for mesh_rows, host_rows in zip(served["rows_mesh"], served["rows_host"]):
        assert mesh_rows.shape == (N, 8)
        np.testing.assert_array_equal(mesh_rows, host_rows)
    np.testing.assert_array_equal(served["rho_mesh"], served["rho_host"])


def test_no_round1_tensor_crossed_to_the_host(served):
    assert served["round1_host_bytes"] == 0


def test_the_per_shard_loop_is_the_same_digest_on_the_host(served):
    """`transcript_rows`'s other leg (`ce.sharded_dealer_rows`: a CPU backend's, a
    multi-process mesh's): the same rows, and it is the leg that fetches the tensors."""
    for loop_rows, mesh_rows in zip(served["rows_loop"], served["rows_mesh"]):
        np.testing.assert_array_equal(loop_rows, mesh_rows)
    assert served["loop_host_bytes"] > 0


def test_a_cheated_share_is_adjudicated_as_on_one_device(served):
    """One altered share: the batch check fails, `mesh_blame` names the pair, the dealer is
    out and `mesh_finalise` aggregates the rest — the one-device route's outcome bit for bit."""
    sharded, one = served["blamed"]["one"]
    ((dealer, recipient),) = CHEATS["one"]
    assert sharded.status == one.status == "done" and sharded.error == one.error == ""
    assert sharded.complaints == one.complaints == ((recipient + 1, dealer + 1),)
    assert sharded.qualified == one.qualified == tuple(j != dealer for j in range(N))
    assert sharded.master == one.master and sharded.master != served["sharded"].master
    np.testing.assert_array_equal(sharded.final_shares, one.final_shares)
    built = served["aot_after_blame"]["builds"] - served["aot"]["builds"]
    assert built == 2 and served["aot_after_blame"]["errors"] == 0  # mesh_blame, mesh_finalise


def test_too_many_cheats_fail_the_ceremony_as_on_one_device(served):
    """More than t dealers out: the outcome fails, and still reports who and what."""
    sharded, one = served["blamed"]["too_many"]
    assert sharded.status == one.status == "failed"
    assert sharded.error == one.error == "MISBEHAVIOUR_HIGHER_THRESHOLD"
    assert sharded.complaints == one.complaints
    assert sorted(sharded.complaints) == sorted((r + 1, d + 1) for d, r in CHEATS["too_many"])
    guilty = {d for d, _ in CHEATS["too_many"]}
    assert sharded.qualified == one.qualified == tuple(j not in guilty for j in range(N))
    assert sharded.master == one.master == b"" and sharded.final_shares is None is one.final_shares


def test_blame_is_booked_once(served):
    """`mesh_collective_seconds{op}`: blame's seconds under `blame`, not inside `verify_finalise` too."""
    before, after = served["snapshot"]["histograms"], served["snapshot_after_blame"]["histograms"]

    def delta(series):
        was = before.get(series, {"sum": 0.0, "count": 0})
        return after[series]["sum"] - was["sum"], after[series]["count"] - was["count"]

    blame_s, blames = delta('mesh_collective_seconds{op="blame"}')
    span_s, spans = delta('dkg_phase_seconds{phase="convoy.blame"}')
    verify_s, verifies = delta('mesh_collective_seconds{op="verify_finalise"}')
    wait_s, _ = delta('dkg_phase_seconds{phase="convoy.verify_wait"}')
    dispatch_s, _ = delta('dkg_phase_seconds{phase="convoy.verify_dispatch"}')
    assert blames == spans == len(CHEATS) and 0 < blame_s <= span_s
    # verify's phase ends where blame begins: its seconds are its two stages' and no more
    assert verifies == len(CHEATS)
    assert verify_s <= (dispatch_s + wait_s) * 1.05 + 0.05


def test_the_programs_came_through_the_store(served):
    stats = served["aot"]
    assert stats["builds"] == len(MESH_KINDS) and stats["errors"] == 0
    stored = sorted(name for name in os.listdir(served["store"]) if name.endswith(".npz"))
    for kind in MESH_KINDS:
        assert any(f"_{kind}_" in name for name in stored), (kind, stored)


def test_the_route_books_its_spans_and_counters(served):
    snap = served["snapshot"]
    hist, gauges = snap["histograms"], snap["gauges"]
    for op in ("deal_commitments", "deal_shares", "transcript_digest", "verify_finalise"):
        assert hist[f'mesh_collective_seconds{{op="{op}"}}']["count"] >= 1
    assert hist["mesh_place_seconds"]["count"] >= 1
    scalar_bytes = 2 * N * (T + 1) * ce.CeremonyConfig(served["curve"], N, T).cs.scalar.limbs * 4
    assert snap["counters"]["mesh_place_bytes_total"] >= scalar_bytes
    assert gauges[f'mesh_route{{bucket="{N}x{T}",devices="{DEVICES}"}}'] == 1
    for stage in (
        "draw", "deal_dispatch", "deal_wait", "digest_dispatch", "digest_wait", "rho_fold",
        "verify_dispatch", "verify_wait", "finalise_dispatch", "finalise_wait", "encode",
    ):
        assert hist[f'dkg_phase_seconds{{phase="convoy.{stage}"}}']["count"] >= 1, stage


def test_the_cell_runs_through_the_harness_on_the_sharded_route(served, monkeypatch, tmp_path):
    """`ceremony_sharded.closed`'s own traffic file and readers on a tiny configuration:
    `benchmark/run.py` as it stands asks `needs`, warms the one width, drives the scheduler
    and judges what it fetched; every request of the window rode the mesh, and the new
    span reader and the timeline's readers find their series."""
    run = bench_support.bench_run()
    real = json.loads(bench_support.MANIFEST.read_text())
    config = {
        "name": "tiny_sharded_n8_t2_all", "source": "test only", "curve": served["curve"],
        "mix": [{"n": N, "t": T, "count": 1}], "rho_bits": 128,
        "scheduler": {"concurrency": 4, "queue_depth": 256, "batch_max": 8},
        "share_check": {"parties": N},
    }
    cell = json.loads((bench_support.ROOT / "benchmark" / "workloads" / "ceremony_sharded.closed.json").read_text())
    cell.update(config=config["name"], trace_seconds=0.5)
    readers = ("shard_place_ms.sharded", "verify_phase_ms.sharded", "convoy_host_ms.bls", "convoy_device_wait_ms.bls", "latency_p95_program_ms", "tail_device_wait_ms")
    manifest = {
        "command": real["command"], "paths": [".", str(bench_support.ROOT / "benchmark")], "run_seconds": 3,
        "configs": [{"name": config["name"], "source": "test only", "file": "configs/tiny.json", "reduced": [], "why": "test only"}],
        "workloads": [{"name": "tiny_sharded.closed", "config": config["name"], "traffic": "closed", "chips": 4, "why": "test only"}],
        "end_to_end": [m for m in real["end_to_end"] if m["name"] in ("latency_p95_ms", "setup_s")],
        "per_layer": [dict(m, workloads=["tiny_sharded.closed"]) for m in real["per_layer"] if m["name"] in readers],
    }
    for m in manifest["end_to_end"]:
        m.pop("workloads", None)
    (tmp_path / "configs").mkdir()
    (tmp_path / "workloads").mkdir()
    (tmp_path / "configs" / f"{config['name']}.json").write_text(json.dumps(config))
    (tmp_path / "workloads" / "tiny_sharded.closed.json").write_text(json.dumps(cell))
    (tmp_path / "manifest.json").write_text(json.dumps(manifest))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    monkeypatch.setattr(buckets, "_local_device_count", lambda: DEVICES)
    before = _counter("mesh_requests_total")
    result = run.run_cell(tmp_path / "manifest.json", "tiny_sharded.closed", 2**31 + 44, 3.0, True)
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    # the warm-up request and every request of the window rode the mesh
    assert _counter("mesh_requests_total") - before >= result["attempted"] + 1
    assert set(readers) <= set(result["metrics"]), result["metrics"]
    assert result["metrics"]["shard_place_ms.sharded"]["value"] > 0
    assert result["metrics"]["verify_phase_ms.sharded"]["value"] > 0


SECOND_PROCESS = """
import json, os, sys
from dkg_tpu.parallel.hostmesh import force_cpu_mesh
force_cpu_mesh(8)
from dkg_tpu.service import CeremonyRequest, WarmRuntime, aot, buckets, engine
buckets.SHARD_MIN_N = {n}
buckets._local_device_count = lambda: {devices}
(out,) = engine.run_convoy(WarmRuntime(), [CeremonyRequest({curve!r}, {n}, {t}, seed={seed})])
print(json.dumps({{"status": out.status, "master": out.master.hex(), "aot": aot.stats()}}))
"""


def test_a_second_process_serves_it_from_the_store(served):
    env = dict(os.environ, DKG_TPU_AOT_DIR=served["store"], DKG_TPU_DIGEST="device", JAX_PLATFORMS="cpu")
    code = SECOND_PROCESS.format(n=N, t=T, devices=DEVICES, curve=served["curve"], seed=SEED)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=600, env=env, cwd=root
    )
    assert done.returncode == 0, done.stderr[-2000:]
    said = json.loads(done.stdout.strip().splitlines()[-1])
    assert said["status"] == "done" and said["master"] == served["sharded"].master.hex()
    assert said["aot"]["builds"] == 0 and said["aot"]["errors"] == 0
    assert said["aot"]["disk_loads"] == len(MESH_KINDS)
