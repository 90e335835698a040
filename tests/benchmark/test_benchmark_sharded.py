"""`ceremony_sharded.closed` as far as the CPU can hold it without a mesh program's compile
(the served route itself: `tests/test_sharded_route.py`): the configuration's file against
`secp256k1_n1024_t341`'s word for word, the manifest's entry, the cell's parameters, the
readers on reduced traces of four planes, and the bytes `bench_collectives` counts held to
the collectives in the programs' own traced jaxprs."""

import collections
import json

import numpy as np
import pytest

import bench_support

CELL = "ceremony_sharded.closed"
CONFIG = "secp256k1_sharded_v5e4"
V5E = "TPU v5 lite"
NEW_READERS = (
    "shard_place_ms.sharded", "collective_time_share.sharded", "collective_roofline_share.sharded",
    "shard_busy_skew.sharded", "deal_phase_ms.sharded", "digest_phase_ms.sharded", "verify_phase_ms.sharded",
    "setup_programs_s.sharded",
)
# curve-blind readers of the closed cells, and the timeline's eight: the cell is on their lists
SHARED_READERS = (
    "pallas_time_share.bls", "convoy_host_ms.bls", "convoy_device_wait_ms.bls",
    "setup_tables_s.bls", "latency_p95_program_ms", "tail_queue_wait_ms", "tail_hold_ms",
    "tail_device_wait_ms", "tail_host_ms", "tail_rest_ms", "device_unfed_share", "longest_stall_ms",
)
# they read `jit_deal` / `jit_verify_batch` / `jit_affine_canon`: the mesh programs carry other names;
# `setup_programs_s.bls` asks for a build or the digest leg's first call: a run from the store has neither
NOT_LISTED = ("deal_device_ms.bls", "verify_device_ms.bls", "digest_time_share.bls", "setup_programs_s.bls")


def _manifest():
    run = bench_support.bench_run()
    return run, *run.load_manifest(bench_support.MANIFEST)


def _load(folder, name):
    run, _, roots = _manifest()
    return json.loads(run.find(roots, folder, name, ".json").read_text())


def _reader(name):
    run, _, roots = _manifest()
    return run.load_module(run.find(roots, "layer_metrics", name, ".py"))


def test_the_file_is_the_one_chip_deployments_word_for_word():
    ours, theirs = _load("configs", CONFIG), _load("configs", "secp256k1_n1024_t341")
    for key in ("scheduler", "share_check", "guarantees", "curve", "rho_bits", "architecture"):
        assert ours[key] == theirs[key], key
    (shape,) = ours["mix"]
    assert (shape["n"], shape["t"], shape["count"]) in {(4096, 1365, 1), (2048, 682, 1)}
    assert shape["t"] == shape["n"] // 3
    assert ours["published"] == {"n": 4096, "t": 1365, "chips": 8} and ours["chips"] == 4
    cut = {"chips"} | ({"n", "t"} if shape["n"] != 4096 else set())
    assert set(ours["reduced"]) == cut
    assert "BASELINE.json configs[3]" in ours["source"] and len(ours["assumed"]) >= 3


def test_the_manifests_entries():
    """The cell's and the configuration's entries, looked up by name: no position, no length
    and no other cell is held here, and the lists are held as supersets, so a later PR may
    append a cell, a second four-chip cell or a reader for this one without an edit here."""
    _, manifest, _ = _manifest()
    (config,) = [c for c in manifest["configs"] if c["name"] == CONFIG]
    (entry,) = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert entry["chips"] == 4 and entry["config"] == CONFIG
    assert config["reduced"] == _load("configs", CONFIG)["reduced"]
    e2e = {m["name"] for m in manifest["end_to_end"] if CELL in m.get("workloads", [CELL])}
    assert {"latency_p95_ms", "setup_s"} <= e2e
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    listed = {name for name, m in by_name.items() if CELL in m.get("workloads", [])}
    assert set(NEW_READERS) | set(SHARED_READERS) <= listed
    # readers of modules and series the sharded route does not carry would read nothing
    assert not listed & set(NOT_LISTED)
    for name in NEW_READERS:
        assert CELL in by_name[name]["workloads"]
        assert by_name[name]["moves"] == ("setup_s" if name.startswith("setup_") else "latency_p95_ms")


def test_the_cells_parameters():
    cell = _load("workloads", CELL)
    assert cell["config"] == CONFIG and cell["drain_s"] == 60.0
    assert cell["traffic"] == {
        "kind": "closed_loop_prepared",
        "outstanding": 1,
        "needs": {"module": "dkg_tpu.parallel.mesh", "attribute": "SERVED_FROM_STORE"},
    }
    assert 0 < cell["trace_seconds"] <= 51


def test_a_program_without_the_seam_stops_in_plan(monkeypatch):
    """What the cell's traffic asks the program: without the attribute `plan` exits with
    the line that says why, before a table or a program is built."""
    run, _, roots = _manifest()
    from dkg_tpu.parallel import mesh as pm

    traffic = run.load_module(run.find(roots, "traffic", "closed_loop_prepared", ".py"))
    cell, config = _load("workloads", CELL), _load("configs", CONFIG)
    assert traffic.plan(cell["traffic"], config, 7, 51.0)["outstanding"] == 1
    monkeypatch.delattr(pm, "SERVED_FROM_STORE")
    with pytest.raises(SystemExit, match="cannot prepare secp256k1_sharded_v5e4"):
        traffic.plan(cell["traffic"], config, 7, 51.0)


def _slice(devices=4, requests=1):
    """A reduced trace of `requests` whole requests on `devices` planes, as
    `bench_trace.reduce` shapes it; seconds chosen so that every reader's sum shows."""
    ops = {
        "jit_mesh_verify_finalise/all-to-all.3": {"seconds": 0.08 * devices * requests, "count": 2 * devices * requests},
        "jit_mesh_verify_finalise/all_to_all.93": {"seconds": 0.08 * devices * requests, "count": 2 * devices * requests},
        "jit_mesh_verify_finalise/all_gather.11": {"seconds": 0.01 * devices * requests, "count": devices * requests},
        "jit_mesh_verify_finalise/all-gather-start.2": {"seconds": 0.01 * devices * requests, "count": devices * requests},
        "jit_mesh_verify_finalise/while.9": {"seconds": 3.0 * devices * requests, "count": devices * requests},
        "jit_mesh_deal_commitments/pt_madd.5[tpu_custom_call]": {"seconds": 2.0 * devices * requests, "count": 9},
        "jit_mesh_digest_rows/fusion.all-to-all-like": {"seconds": 9.9, "count": 1},  # not a collective
    }
    runs = {
        "jit_mesh_deal_commitments": [2.0, 2.1, 2.2, 2.5] * requests,
        "jit_mesh_deal_shares": [0.5, 0.5, 0.5, 0.5] * requests,
        "jit_mesh_digest_rows": [0.4, 0.5, 0.5, 0.5] * requests,
        "jit_mesh_verify_finalise": [4.0] * devices * requests,
    }
    return {
        "busy_s": 8.0 * requests, "window_s": 9.0 * requests, "devices": devices, "ops": ops,
        "module_runs": runs, "module_runs_cut": {}, "modules": {}, "pallas_s": 2.0 * requests,
    }


def test_the_collective_readers_on_a_slice_of_four_planes():
    bench_support.bench_run()
    import bench_collectives

    config = _load("configs", CONFIG)
    trace = _slice()
    assert bench_collectives.collective_seconds(trace) == {
        "all-to-all": pytest.approx(0.64), "all-gather": pytest.approx(0.08)
    }
    ctx = {"trace": trace, "config": config}
    assert _reader("collective_time_share.sharded").read(ctx) == pytest.approx(100 * 0.72 / 32.0)
    (shape,) = config["mix"]
    sent = sum(bench_collectives.collective_bytes(shape["n"], shape["t"], 4, 16, 48).values())
    want = 100 * (sent / 200e9) / 0.18
    # the slice's operations: two `all_to_all` a recipient chunk of 512 of 1024, two gathers, a plane
    assert bench_collectives.collective_counts(trace) == {"all-to-all": 16, "all-gather": 8}
    assert bench_collectives.collective_calls(shape["n"], 4, 512) == {"all-to-all": 4, "all-gather": 2}
    assert bench_collectives.roofline_share(trace, config, V5E, 16, 48, 512) == pytest.approx(want)
    assert 0 < want < 100
    # two whole requests in a slice twice as long read the same share
    assert bench_collectives.roofline_share(_slice(requests=2), config, V5E, 16, 48, 512) == pytest.approx(want)
    assert _reader("shard_busy_skew.sharded").read(ctx) == pytest.approx((2.0 + 0.5 + 0.4) / (2.5 + 0.5 + 0.5))
    with pytest.raises(KeyError, match="no interconnect peak"):
        bench_collectives.roofline_share(trace, config, "cpu", 16, 48, 512)
    # a slice that holds no program whole: the executions at its edge are read, a lower bound,
    # and the collectives by the operations that are there, whatever program they were part of
    edge = dict(trace, module_runs={}, module_runs_cut={"jit_mesh_deal_shares": [0.3, 0.4, 0.4, 0.4]})
    ctx = {"trace": edge, "config": config}
    assert _reader("shard_busy_skew.sharded").read(ctx) == pytest.approx(0.75)
    assert bench_collectives.roofline_share(edge, config, V5E, 16, 48, 512) == pytest.approx(want)
    # a request the slice cuts: the operations that fell outside bring neither bytes nor seconds
    half = {k: dict(v, seconds=v["seconds"] / 2, count=v["count"] // 2) for k, v in trace["ops"].items()}
    assert bench_collectives.roofline_share(dict(trace, ops=half), config, V5E, 16, 48, 512) == pytest.approx(want)
    # an operation cut at the slice's start (its `-done` alone is there): seconds, no bytes
    ops = dict(trace["ops"], **{"jit_mesh_verify_finalise/all-to-all-done.4": {"seconds": 0.18 * 4, "count": 4}})
    assert bench_collectives.roofline_share(dict(trace, ops=ops), config, V5E, 16, 48, 512) == pytest.approx(want / 2)


def test_the_readers_on_what_reduce_makes_of_four_planes():
    """The same readers behind `bench_trace.reduce` itself: four device planes, each with
    a cut request's tail, one whole request (deal x 2, digest, verify with its collectives)
    and a cut request's head, as the window's last slice holds them."""
    bench_support.bench_run()
    import bench_collectives
    import bench_trace

    ms = 1_000_000
    events = [{"plane": "/host:CPU", "line": "thread", "name": bench_trace.WINDOW_MARK, "start_ns": 0, "dur_ns": 20_000 * ms}]

    def ev(plane, line, name, start, dur):
        events.append({"plane": plane, "line": line, "name": name, "start_ns": int(start * ms), "dur_ns": int(dur * ms)})

    for d in range(4):
        plane, slow = f"/device:TPU:{d}", 1.0 + 0.1 * d  # shard d is 10 d % slower where it works alone
        ev(plane, "XLA Modules", "jit_mesh_verify_finalise(7)", 0, 2000)  # the request before, cut
        ev(plane, "XLA Ops", "while.3", 0, 2000)
        at = 3000.0
        for name, dur in (("jit_mesh_deal_commitments", 2000 * slow), ("jit_mesh_deal_shares", 500), ("jit_mesh_digest_rows", 1000 * slow)):
            ev(plane, "XLA Modules", f"{name}(1)", at, dur)
            ev(plane, "XLA Ops", "fusion.1", at, dur)
            at += dur
        ev(plane, "XLA Modules", "jit_mesh_verify_finalise(7)", 8000, 5000)
        ev(plane, "XLA Ops", "all-gather.1", 8000, 10)
        ev(plane, "XLA Ops", "all-to-all.2", 8010, 100)
        ev(plane, "XLA Ops", "all_to_all.93", 8110, 100)
        ev(plane, "XLA Ops", "while.3", 8210, 4780)
        ev(plane, "XLA Ops", "all-gather.7", 12990, 10)
        ev(plane, "XLA Modules", "jit_mesh_deal_commitments(1)", 16000, 2000)  # the next request, cut
        ev(plane, "XLA Ops", "fusion.1", 16000, 2000)
    trace = bench_trace.reduce(events, 20.0)
    assert trace["devices"] == 4 and trace["window_s"] == pytest.approx(20.0)
    assert len(trace["module_runs"]["jit_mesh_verify_finalise"]) == 4  # one whole a plane
    config = _load("configs", CONFIG)
    ctx = {"trace": trace, "config": config}
    assert bench_collectives.collective_seconds(trace) == {"all-gather": pytest.approx(0.08), "all-to-all": pytest.approx(0.8)}
    busy_all = trace["busy_s"] * 4
    assert _reader("collective_time_share.sharded").read(ctx) == pytest.approx(100 * 0.88 / busy_all)
    (shape,) = config["mix"]
    sent = sum(bench_collectives.collective_bytes(shape["n"], shape["t"], 4, 16, 48).values())
    # one request's operations a plane where verify is not chunked: two `all_to_all`, two gathers
    assert bench_collectives.roofline_share(trace, config, V5E, 16, 48, 0) == pytest.approx(100 * sent / 200e9 / 0.22)
    assert _reader("shard_busy_skew.sharded").read(ctx) == pytest.approx((2.0 + 0.5 + 1.0) / (2.6 + 0.5 + 1.3))


def test_the_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent's program (no mesh series, no mesh module in a trace) and an untraced
    run: every new reader leaves its metric out and none raises."""
    one_chip = {
        "busy_s": 1.0, "window_s": 1.1, "devices": 1, "pallas_s": 0.5, "modules": {}, "module_runs_cut": {},
        "ops": {"jit_deal/pt_madd.5[tpu_custom_call]": {"seconds": 0.5, "count": 3}},
        "module_runs": {"jit_deal": [0.6]},
    }
    counters = {"before": {"histograms": {}}, "after": {"histograms": {}}}
    config = _load("configs", CONFIG)
    for trace in (None, one_chip):
        ctx = {"trace": trace, "config": config, "counters": counters}
        for name in NEW_READERS:
            assert _reader(name).read(ctx) is None, name


def test_the_set_up_reader_reads_a_run_that_only_loaded():
    """From the store a sharded run books loads and nothing else: still a reading."""
    loads = 'aot_load_seconds{curve="secp256k1"}'
    after = {"histograms": {loads: {"sum": 12.5, "count": 4}}}
    ctx = {"counters": {"before": {"histograms": {}}, "after": after}}
    assert _reader("setup_programs_s.sharded").read(ctx) == pytest.approx(12.5)
    assert _reader("setup_programs_s.bls").read(ctx) is None  # why the cell is not on its list
    build = 'aot_build_stage_seconds{curve="secp256k1",kind="mesh_deal_shares",stage="compile"}'
    after["histograms"][build] = {"sum": 30.0, "count": 1}
    assert _reader("setup_programs_s.sharded").read(ctx) == pytest.approx(42.5)


def test_the_placement_reader_takes_the_windows_requests():
    series = "mesh_place_seconds"
    counters = {
        "before": {"histograms": {series: {"sum": 1.0, "count": 1}}},
        "after": {"histograms": {series: {"sum": 1.9, "count": 4}}},
    }
    assert _reader("shard_place_ms.sharded").read({"counters": counters}) == pytest.approx(300.0)


def test_the_phase_readers_take_the_windows_requests():
    """Deal's, the digest's and verify's seconds come from the program's own phase spans,
    over the whole window: a traced slice of this cell is shorter than a request."""
    def series(op):
        return f'mesh_collective_seconds{{op="{op}"}}'

    before = {series(op): {"sum": s, "count": 1} for op, s in (("deal_commitments", 2.0), ("deal_shares", 0.9), ("transcript_digest", 1.2), ("verify_finalise", 4.7))}
    after = {series(op): {"sum": s, "count": 5} for op, s in (("deal_commitments", 10.0), ("deal_shares", 4.5), ("transcript_digest", 6.0), ("verify_finalise", 23.5))}
    after[series("deal_shares")] = {"sum": 3.6, "count": 4}  # the window closed between deal's two programs
    ctx = {"counters": {"before": {"histograms": before}, "after": {"histograms": after}}}
    assert _reader("deal_phase_ms.sharded").read(ctx) == pytest.approx(2000.0 + 900.0)
    assert _reader("digest_phase_ms.sharded").read(ctx) == pytest.approx(1200.0)
    assert _reader("verify_phase_ms.sharded").read(ctx) == pytest.approx(4700.0)


def test_the_trace_readers_on_the_builders_run_of_the_cell():
    """`bench_trace.reduce` of the builder's traced run on four chips (PR 44, review round:
    the collective operations under the names the v5e gives them, with their counts and
    seconds, and what the slice held of the modules): the readers give what that run's
    result line gave."""
    bench_support.bench_run()
    import bench_collectives

    trace = json.loads((bench_support.DATA.parent / "data_sharded" / "reduced_slice_pr44.json").read_text())
    config = _load("configs", CONFIG)
    assert trace["devices"] == 4 and not trace["module_runs"].get("jit_mesh_verify_finalise")
    # a plane's collectives of one request: the slice held verify's tail, so all of them
    assert bench_collectives.collective_counts(trace) == {"all-to-all": 16, "all-gather": 8}
    seconds = bench_collectives.collective_seconds(trace)
    assert seconds["all-to-all"] == pytest.approx(0.024702529) and seconds["all-gather"] == pytest.approx(0.000426172)
    ctx = {"trace": trace, "config": config}
    assert _reader("collective_time_share.sharded").read(ctx) == pytest.approx(0.25436801686220006)
    assert bench_collectives.roofline_share(trace, config, V5E, 16, 48, 512) == pytest.approx(32.10994281001632)
    assert _reader("shard_busy_skew.sharded").read(ctx) == pytest.approx(0.9997899096036342)
    assert _reader("pallas_time_share.bls").read(ctx) == pytest.approx(86.67790332218667)


def _collectives(jaxpr, mult=1, out=None):
    """Bytes a shard sends in every collective of a traced `shard_map` body: each
    `all_to_all` / `all_gather` operand times the lengths of the scans around it; and, under
    `<primitive> calls`, how often it executes."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("all_to_all", "all_gather"):
            (operand,) = eqn.invars
            words = int(np.prod(operand.aval.shape))
            out[eqn.primitive.name] += mult * words * operand.aval.dtype.itemsize
            out[f"{eqn.primitive.name} calls"] += mult
            continue
        inner_mult = mult * int(eqn.params["length"]) if eqn.primitive.name == "scan" else mult
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _collectives(sub, inner_mult, out)
    return out


@pytest.mark.parametrize("n,t,chunk", [(8, 2, None), (16, 5, 2), (24, 7, 4), (64, 21, 4)], ids=str)
def test_the_bytes_counted_are_the_traced_programs_collectives(n, t, chunk, monkeypatch):
    """`bench_collectives.collective_bytes` against the mesh programs as they trace on a
    mesh of four (traced, never compiled): an `all_to_all` operand leaves the chip but for
    the part addressed to itself, an `all_gather` operand goes to each of the others; the
    recipient chunking (forced here at small n) moves no byte more; deal and the digest
    hold no collective."""
    import jax
    import jax.numpy as jnp

    bench_support.bench_run()
    import bench_collectives

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.parallel import mesh as pm
    from dkg_tpu.utils import envknobs

    for name in envknobs.PROGRAM_SHAPING:
        monkeypatch.delenv(name, raising=False)
    if chunk is not None:
        monkeypatch.setattr(pm, "_verify_chunk_default", lambda cfg, block: chunk)
    devices = 4
    cfg = ce.CeremonyConfig("secp256k1", n, t)
    cs = cfg.cs
    mesh = pm.make_mesh(devices)
    u32 = jnp.uint32
    L, CL = cs.scalar.limbs, (cs.ncoords, cs.field.limbs)
    table = jax.ShapeDtypeStruct((L * 2, 256) + CL, u32)
    coeffs = jax.ShapeDtypeStruct((n, t + 1, L), u32)
    points = jax.ShapeDtypeStruct((n, t + 1) + CL, u32)
    matrix = jax.ShapeDtypeStruct((n, n, L), u32)
    knobs = ("test", n, t, chunk)  # a cache key of its own: the chunk rule is patched
    verify = pm._verify_finalise_prog(cfg, mesh, 128, knobs)
    traced = jax.make_jaxpr(verify)(
        jax.ShapeDtypeStruct((n,) + CL, u32), points, matrix, matrix, table, table,
        jax.ShapeDtypeStruct((n, L), u32),
    )
    found = _collectives(traced.jaxpr)
    away = devices - 1
    sent = {
        "all-to-all": found["all_to_all"] * away // devices,
        "all-gather": found["all_gather"] * away,
    }
    assert sent == bench_collectives.collective_bytes(n, t, devices, L, cs.ncoords * cs.field.limbs)
    calls = {"all-to-all": found["all_to_all calls"], "all-gather": found["all_gather calls"]}
    assert calls == bench_collectives.collective_calls(n, devices, pm._verify_chunk_default(cfg, n // devices))
    for prog, args in (
        (pm._deal_commitments_prog(cfg, mesh, knobs), (coeffs, coeffs, table, table)),
        (pm._deal_shares_prog(cfg, mesh, knobs), (coeffs, coeffs)),
        (pm._digest_rows_prog(cfg, mesh, knobs), (points, points, matrix, matrix)),
    ):
        assert not _collectives(jax.make_jaxpr(prog)(*args).jaxpr)


def test_the_sharded_sizes_collectives_are_what_the_issue_reckoned():
    bench_support.bench_run()
    import bench_collectives

    sent = bench_collectives.collective_bytes(4096, 1365, 4, 16, 48)
    assert sent["all-to-all"] == 2 * 1024 * 3072 * 64 == 402_653_184  # 403 MB a chip leaving
    assert sent["all-gather"] == 3 * 1367 * 192
