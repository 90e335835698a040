"""`ceremony_ristretto_n256.closed` rehearsed on the CPU with the smallest bucket:
ristretto255 (extended Edwards coordinates, the reference crate's only curve) through
`engine.run_convoy` against the plain reference and `run_single_reference`, exact and
padded; a tiny cell through `run_cell`, the broken-engine control, the span readers it
shares with the BLS12-381 cell (`.bls`, held alone by `test_benchmark_bls.py`) on a run
that has their series; the kernels read by name from a reduced trace; and the roofline's
count of kernel blocks held to the program's own traced launches and trace-time counters.  One file, so that one worker
compiles the ristretto255 (8,2) width-1 programs once."""

import collections
import json

import numpy as np
import pytest

import bench_support

MANIFEST = bench_support.DATA.parent / "data_ristretto" / "manifest.json"
CURVE = "ristretto255"
CELL = "ceremony_ristretto_n256.closed"
# the closed cells' readers are curve-blind: this cell is on the lists of the `.bls` ones
SPAN_READERS = ("convoy_host_ms.bls", "convoy_device_wait_ms.bls", "setup_programs_s.bls", "setup_tables_s.bls")
TRACE_READERS = ("deal_device_ms.bls", "verify_device_ms.bls", "pallas_time_share.bls", "digest_time_share.bls")
NEW_READERS = ("ed_multi_kernel_time_share.ristretto", "pt_kernels_roofline_share.ristretto")
TIMELINE_READERS = (
    "latency_p95_program_ms", "tail_queue_wait_ms", "tail_hold_ms", "tail_device_wait_ms",
    "tail_host_ms", "tail_rest_ms", "device_unfed_share", "longest_stall_ms",
)
V5E = "TPU v5 lite"


@pytest.fixture()
def cache_in_tmp(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _run(trace):
    return bench_support.bench_run().run_cell(MANIFEST, "tiny_ristretto.closed", 2**31 + 42, 3.0, trace)


def _reader(name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


def _load(folder, name):
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    return json.loads(run.find(roots, folder, name, ".json").read_text())


@pytest.mark.parametrize("n", [8, 6], ids=["exact", "padded_6_in_8"])
def test_served_ristretto_ceremony_equals_the_plain_reference(n):
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    from dkg_tpu.service import WarmRuntime, engine

    seed = 2**33 + 42 + n
    req = engine.CeremonyRequest(CURVE, n, 2, seed=seed)
    assert (req.bucket().n, req.bucket().t) == (8, 2)
    out = engine.run_convoy(WarmRuntime(), [req])[0]
    assert out.status == "done" and len(out.qualified) == n and all(out.qualified)
    # master (the 32-byte canonical ristretto encoding) and EVERY final share, real lanes only
    plain = {"curve": CURVE, "n": n, "t": 2, "seed": seed}
    assert not any(bench_oracle.check_outcome(plain, out, list(range(1, n + 1))).values())
    assert len(out.master) == 32 and np.asarray(out.final_shares).shape == (n, 16)
    assert out.master == engine.run_single_reference(req)


def test_one_ristretto_request_in_flight_is_correct(cache_in_tmp, capsys):
    from dkg_tpu.service import aot
    from dkg_tpu.utils.metrics import REGISTRY

    lanes = 'service_convoy_lanes_total{bucket="8x2",kind="real"}'
    before = REGISTRY.snapshot()["counters"].get(lanes, 0)
    result = _run(trace=False)
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"latency_p95_ms", "setup_s"}
    printed = capsys.readouterr().out
    for name in ("master_mismatch", "share_limbs_off", "window_aot_builds", "window_jax_stage_events"):
        assert f"compared {name} = 0 (limit 0)" in printed
    kinds = {(key[0], key[4]) for key in aot._PROC if key[1:4] == (CURVE, 8, 2)}
    assert kinds == {("deal", 1), ("verify", 1), ("aggregate", 1), ("master", 1)}
    # a request issues its n lanes, one convoy each: the scheduler's own count
    real = REGISTRY.snapshot()["counters"][lanes] - before
    assert real % 8 == 0 and real // 8 >= result["attempted"]


@pytest.mark.parametrize("what", ["share", "master"])
def test_broken_timed_path_is_not_correct_on_ristretto(cache_in_tmp, what):
    with bench_support.broken_engine(what):
        result = _run(trace=False)
    assert result["correct"] is False
    assert result["attempted"] >= 1


def test_span_readers_read_a_rehearsed_ristretto_run(cache_in_tmp):
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
    snap = REGISTRY.snapshot()
    tables = {k: v["count"] for k, v in snap["histograms"].items() if k.startswith("fixed_base_table_seconds")}
    assert sum(tables.values()) == 2 and all(f'curve="{CURVE}"' in k for k in tables)
    assert any(k.startswith("aot_build_stage_seconds{") and f'curve="{CURVE}"' in k for k in snap["histograms"])
    # the tier this process serves the curve on, booked with the tables; the CPU composes
    assert snap["gauges"][f'point_kernel_tier{{curve="{CURVE}",msm="pippenger",tier="composed"}}'] == 1
    # no device plane on the CPU: the device-trace readers find nothing and are left out
    assert not set(TRACE_READERS + NEW_READERS) & set(result["metrics"])
    # a later process loads what this one stored: the loads carry the curve too
    aot.reset()
    assert _run(trace=False)["correct"] is True
    loads = {k: v["count"] for k, v in REGISTRY.snapshot()["histograms"].items() if k.startswith("aot_load_seconds")}
    assert loads == {f'aot_load_seconds{{curve="{CURVE}"}}': 4}


def _slice(kernels: dict[str, float], runs: dict[str, int]) -> dict:
    """A reduced trace of one device plane made from events: `runs[module]` executions of
    each program whole, a cut execution of another module at either end, and inside
    `jit_verify_batch`'s first execution one operation of `seconds` for every
    `kernels[name]`."""
    bench_support.bench_run()
    import bench_trace

    plane, events, at = "/device:TPU:0", [], 1_000
    modules = ["jit_convert_element_type"] + [m for m, count in runs.items() for _ in range(count)] + ["jit_reshape"]
    for i, module in enumerate(modules):
        events.append({"plane": plane, "line": "XLA Modules", "name": f"{module}(77)", "start_ns": at, "dur_ns": 9_000_000})
        if module == "jit_verify_batch" and modules[i - 1] != module:
            op_at = at
            for name, seconds in kernels.items():
                dur = int(seconds * 1e9)
                events.append({"plane": plane, "line": "XLA Ops", "name": name, "start_ns": op_at, "dur_ns": dur})
                op_at += dur
        at += 10_000_000
    events.append({"plane": "/host:CPU", "line": "main", "name": bench_trace.WINDOW_MARK, "start_ns": 0, "dur_ns": at})
    return bench_trace.reduce(events, at / 1e9)


FUSED = {
    "pt_add.120[tpu_custom_call]": 0.004, "pt_add.7[tpu_custom_call]": 0.001, "pt_madd.6[tpu_custom_call]": 0.001,
    "pt_window_step.5[tpu_custom_call]": 0.0005, "pt_ladder_mul_add.4[tpu_custom_call]": 0.0015,
    "mod_pow_const.1[tpu_custom_call]": 0.0005, "fusion.3": 0.0005,
}
COMPOSED = {"pt_add.120[tpu_custom_call]": 0.004, "pt_double.9[tpu_custom_call]": 0.003, "while.12": 0.002}
RUNS = {"jit_deal": 2, "jit_verify_batch": 2, "jit_master_key_from_bare": 2}


def test_kernels_are_read_by_name_from_the_slice():
    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_roofline

    trace = _slice(FUSED, RUNS)
    assert bench_roofline.kernel_seconds(trace) == pytest.approx(
        {"pt_add": 0.005, "pt_madd": 0.001, "pt_window_step": 0.0005, "pt_ladder_mul_add": 0.0015}
    )
    read = _reader("ed_multi_kernel_time_share.ristretto").read
    assert read({"trace": trace}) == pytest.approx(100.0 * 0.002 / trace["busy_s"])
    assert 0 < read({"trace": trace}) < 100
    # the composed tier launches neither multi-op kernel: 0, which is a reading, not a silence
    assert read({"trace": _slice(COMPOSED, RUNS)}) == 0.0
    assert read({"trace": None}) is None


def test_roofline_share_counts_whole_executions_over_every_kernel_second():
    bench_support.bench_run()
    import bench_roofline

    config = _load("configs", "ristretto255_n256_t85")
    trace = _slice(FUSED, RUNS)
    whole = {p: 2 for p in bench_roofline.PROGRAMS}
    assert {p: len(trace["module_runs"][p]) for p in bench_roofline.PROGRAMS} == whole
    least = bench_roofline.least_seconds(V5E, 256, 85, 128, whole)
    # a (256,85) request moves 1.67 ms of HBM traffic through its point kernels at 819 GB/s
    assert least / 2 == pytest.approx(1.673e-3, rel=1e-3)
    share = bench_roofline.roofline_share(trace, config, V5E)
    assert share == pytest.approx(100.0 * least / 0.008) and share < 100
    # the bytes bound is the larger for every kernel on the v5e
    ops_peak, bytes_peak = bench_roofline.PEAKS[V5E]
    for kernel in ("pt_add", "pt_madd", "pt_window_step", "pt_ladder_mul_add"):
        ops, nbytes = bench_roofline.block_cost(kernel, 9)
        assert nbytes / bytes_peak > ops / ops_peak, kernel
    # silent on the composed tier (another schedule), without a trace; an unknown device is an error
    assert bench_roofline.roofline_share(_slice(COMPOSED, RUNS), config, V5E) is None
    assert bench_roofline.roofline_share(None, config, V5E) is None
    with pytest.raises(KeyError, match="no peaks for device kind"):
        bench_roofline.roofline_share(trace, config, "cpu")


def _launches(jaxpr, mult=1, out=None):
    """Blocks every named Pallas kernel is launched on in a traced program: each
    `pallas_call`'s grid times the lengths of the scans around it."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"]] += mult * int(np.prod(eqn.params["grid_mapping"].grid))
            continue
        inner_mult = mult * int(eqn.params["length"]) if eqn.primitive.name == "scan" else mult
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _launches(sub, inner_mult, out)
    return out


@pytest.mark.parametrize("n,t", [(8, 2), (256, 85)], ids=str)
def test_the_rooflines_block_count_is_the_traced_programs(n, t, monkeypatch, request):
    """`bench_roofline.point_kernel_blocks` against the width-1 programs as the chip
    traces them (fused kernels on; traced, never compiled): kernel by kernel the blocks
    launched, and the lanes and bodies the program's own trace-time counters book."""
    import jax
    import jax.numpy as jnp

    bench_support.bench_run()
    import bench_roofline

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.utils.metrics import REGISTRY

    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    for name in ("DKG_TPU_RLC", "DKG_TPU_RLC_CHUNK", "DKG_TPU_MSM", "DKG_TPU_MUL"):
        monkeypatch.delenv(name, raising=False)
    cfg = ce.CeremonyConfig(CURVE, n, t)
    cs = cfg.cs
    L, C, S = cs.field.limbs, cs.ncoords, cs.scalar.limbs
    u = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)  # noqa: E731
    table = u(S, 1 << 16, C, L)  # the on-chip 16-bit fixed-base table
    # the jitted helpers inside (`eval_point_poly`) read the switch when they trace: one traced
    # earlier at these shapes with the kernels off must not answer here, nor this one a CPU caller
    jax.clear_caches()
    request.addfinalizer(jax.clear_caches)
    before = REGISTRY.snapshot()["counters"]
    traced = {
        "jit_deal": jax.make_jaxpr(lambda a, b, g, h: ce.deal.__wrapped__(cfg, a, b, g, h))(
            u(n, t + 1, S), u(n, t + 1, S), table, table
        ),
        "jit_verify_batch": jax.make_jaxpr(
            lambda e, s, r, rho, g, h: ce.verify_batch.__wrapped__(cfg, e, s, r, rho, 128, g, h)
        )(u(n, t + 1, C, L), u(n, n, S), u(n, n, S), u(n, S), table, table),
        "jit_master_key_from_bare": jax.make_jaxpr(lambda a, q: ce.master_key_from_bare.__wrapped__(cfg, a, q))(
            u(n, t + 1, C, L), jax.ShapeDtypeStruct((n,), jnp.bool_)
        ),
    }
    after = REGISTRY.snapshot()["counters"]
    counted = bench_roofline.point_kernel_blocks(n, t, 128)
    assert set(counted) == set(traced) == set(bench_roofline.PROGRAMS)
    for program, jaxpr in traced.items():
        launched = {k: v for k, v in _launches(jaxpr.jaxpr).items() if k and k.startswith("pt_")}
        assert launched == counted[program], program
    moved = {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}
    lanes = 'point_rlc_lanes_traced_total{kind="block",part="%s",stack="1"}'
    blocks = bench_roofline._blocks
    assert moved[lanes % "points"] == blocks(n * (t + 1)) * bench_roofline.BLOCK
    assert moved[lanes % "acc"] == blocks(t + 1) * bench_roofline.BLOCK
    # the window loops of the 16-bit table, a `pt_madd` a window: one body a traced multiply
    bodies = moved['fixed_base_traced_total{form="blocks",window="16"}']
    assert 1 <= bodies <= 4 and counted["jit_deal"]["pt_madd"] == 2 * bench_roofline.FIXED_WINDOWS * blocks(n * (t + 1))


def test_the_ristretto_cell_is_the_large_cell_but_for_curve_size_and_what_issue_42_names():
    ris, secp = _load("configs", "ristretto255_n256_t85"), _load("configs", "secp256k1_n1024_t341")
    for key in ("architecture", "rho_bits", "scheduler", "share_check", "guarantees", "reduced"):
        assert ris[key] == secp[key], key
    assert ris["architecture"] is None and ris["reduced"] == []
    assert (ris["curve"], ris["mix"], ris["published"]) == (CURVE, [{"n": 256, "t": 85, "count": 1}], {"n": 256, "t": 85})
    assert ris["reference"].startswith("benchmark/bench_oracle.py")
    assert len(ris["assumed"]) == 4 and "Feldman share-verify batch" in ris["assumed"][0]
    assert "Ristretto25519" in ris["source"] and "configs[1]" in ris["source"] and len(ris["source"]) <= 200
    cell, twin = _load("workloads", CELL), _load("workloads", "ceremony_n1024.closed")
    assert cell["traffic"] == twin["traffic"] and cell["traffic"]["outstanding"] == 1
    assert (cell["drain_s"], cell["trace_seconds"]) == (twin["drain_s"], 1.5)
    run = bench_support.bench_run()
    _, roots = run.load_manifest(bench_support.MANIFEST)
    kind = run.load_module(run.find(roots, "traffic", "closed_loop_prepared", ".py"))
    due, req = next(kind.plan(cell["traffic"], ris, 2**31 + 5, 51.0)["requests"])
    assert due is None and (req["n"], req["t"], req["curve"], req["rho_bits"]) == (256, 85, CURVE, 128)
    manifest = json.loads(bench_support.MANIFEST.read_text())
    entry = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["chips"]) == ("ristretto255_n256_t85", 1)
    lists = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"] if CELL in m.get("workloads", [])}
    assert {"latency_p95_ms", *SPAN_READERS, *TRACE_READERS, *NEW_READERS, *TIMELINE_READERS} <= lists
