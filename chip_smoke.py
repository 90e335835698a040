#!/usr/bin/env python3
"""One ceremony and one served request on the chip: the quickest proof
that the system still starts there.

Drives the main path through the entry points a user calls, in ONE
process, on whatever accelerator JAX finds — and fails (non-zero, no
result line) when that is not a TPU.  No CPU path, no caught phase.

    python chip_smoke.py            # one chip: phases `served`, `ceremony`
    python chip_smoke.py --digest   # one chip: canonicalisation, digest and rho only
    python chip_smoke.py --mesh     # four chips: the sharded ceremony only
    python chip_smoke.py --curve bls12_381_g1 [--digest]   # the same on another curve

* ``served`` — an in-process ``CeremonyScheduler`` over one
  ``WarmRuntime`` (examples/serve.py's shape, one worker): three seeded
  requests submitted, polled, fetched; each master compared with
  ``engine.run_single_reference`` and the host oracle; then
  ``service.sign`` (proved, the default) over four messages against
  ``secret * H(m)``.  Served as a deployment serves, through the
  executable store (``DKG_TPU_AOT_DIR``, by default beside the compile
  cache), and FIRST, so that this process builds the four programs
  there: the phase's line carries ``setup_split_s``, each stored
  program's build seconds by stage (trace, lower, compile, serialize),
  the loads, and the digest leg's first call.  The requests use the
  ceremony's own (n, t): a first call of any new shape costs a build,
  and the whole script must fit 1200 s cold.
* ``ceremony`` — ``BatchedCeremony(--curve, n=1024, t=341)`` from a
  fixed seed (BASELINE.json config 3), run twice; every batch check
  passes, no complaints, and the master key equals the host oracle
  (sum of the seeded constant coefficients times the generator, big-int
  arithmetic in groups/host.py) bit for bit.  Its programs are the
  served phase's, already traced: ``first_call_s`` no longer holds
  their build (until PR 27 it did: 540 s), ``warm_s`` is what it was.
* ``--digest`` — the Fiat-Shamir leg alone, which no fetched outcome
  shows: ``gd.affine_canon`` against its host big-int twin at a width-8
  (16,5) convoy's two shapes and at two lane counts whose Montgomery
  scan is 85 and 4 rows long, identity lanes spliced in, limb for limb;
  the 16-bit table's hash (256 rows); then one such convoy through ``engine.run_convoy`` (masters against
  the host oracle, ``affine_canon_calls_total`` three up on the fused
  path) and its transcript digests and rho from the device leg against
  the host leg, bit for bit.  A minute from a baked executable store
  (``DKG_TPU_AOT_DIR``); the digests are printed, so two commits run on
  one seed can be compared.
* ``--mesh`` — ``run_sharded_ceremony`` on a 4-device mesh, twice:
  master key and the final shares of eight parties against the host
  oracle (``benchmark/bench_oracle.py``: Python ints), every recipient's
  batch check, all qualified; fails unless every sharded input really
  spans four devices.  The run's phase log (logger
  ``dkg_tpu.parallel.mesh``: a line as each phase is entered) goes to
  stderr, so a run cut by its time limit names the phase it was in, and
  the result line carries both calls' ``phases_s``.  (Device 0's
  one-device ceremony, which this phase ran beside it until PR 44, built
  the one-device programs too, minutes more of four chips; that equality
  is tier 1's, ``tests/test_sharded_route.py``.)

Each phase prints one JSON line; the LAST line is
``{"ok": true, "device": {...}}`` and nothing else.  ``--rehearse`` is
for the sandbox (forces the CPU backend, tiny ``--n/--t``, Pallas in
interpret mode where forced on) and never prints that line.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import math
import os
import pathlib
import random
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

SHARED = b"chip_smoke"


_T0 = time.perf_counter()


def _emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def _require(cond, msg: str) -> None:
    """A failed check fails the script (an ``assert`` would vanish under -O)."""
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def _note(msg: str) -> None:
    """Progress on stderr, so a run cut by its time limit shows where."""
    print(f"[chip_smoke +{time.perf_counter() - _T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def _compile_delta(before: dict, after: dict) -> dict:
    """What the JAX runtime traced, lowered and compiled between two
    runtimeobs snapshots (seconds per stage; ``backend_compile`` wraps
    the persistent-cache lookup, so it is small on a hit; ``trace``
    events nest, so their sum over-counts and can exceed the wall)."""
    stages = {
        k: round(v["sum_s"] - before["stages"].get(k, {"sum_s": 0.0})["sum_s"], 3)
        for k, v in after["stages"].items()
    }
    return {
        "compiles": after["compiles_total"] - before["compiles_total"],
        "stages_s": stages,
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "cache_misses": after["cache_misses"] - before["cache_misses"],
    }


def _seeded_secret(fs, n: int, t: int, seed: int) -> int:
    """Sum of the dealers' constant coefficients, re-drawn from the seed
    in BatchedCeremony's order (all of a, row by row) — independent of
    anything the device computed."""
    rng = random.Random(seed)
    total = 0
    for _ in range(n):
        row = [fs.rand_int(rng) for _ in range(t + 1)]
        total += row[0]
    return total % fs.modulus


def _host_pubkey(curve: str, secret: int) -> bytes:
    from dkg_tpu.groups import host as gh

    group = gh.ALL_GROUPS[curve]
    return group.encode(group.scalar_mul_vartime(secret, group.generator()))


def _encode_point(cs, pt) -> bytes:
    import numpy as np

    from dkg_tpu.groups import device as gd

    return gd.encode_batch(cs, np.asarray(pt)[None])[0].tobytes()


def _path_facts(cs, table) -> dict:
    """Which formulations the traced programs resolved to."""
    from dkg_tpu import native
    from dkg_tpu.fields import device as fd
    from dkg_tpu.groups import device as gd
    from dkg_tpu.ops import pallas_field as pf
    from dkg_tpu.utils.metrics import REGISTRY

    fused, interpret = bool(fd.fused_kernels_active()), not fd._on_tpu()
    return {
        "fused_kernels_active": fused,
        "point_kernel_tier": gd.point_kernel_tier(),
        "pallas_interpret": interpret,
        "kernel_mul_core": pf.rows_mul_dispatch(cs.field, interpret) if fused else None,
        "xla_mul": fd.mul_dispatch_mode(cs.field),
        "table_window_bits": int(math.log2(table.shape[1])),
        "fixed_base_traced": {
            k: v for k, v in REGISTRY.snapshot()["counters"].items() if k.startswith("fixed_base_traced_total")
        },
        "native_library": bool(native.available()),
    }


def phase_ceremony(args, dev) -> None:
    import jax
    import numpy as np

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.utils import runtimeobs
    from dkg_tpu.utils.tracing import CeremonyTrace

    n, t = args.n, args.t
    snap0 = runtimeobs.snapshot()
    t0 = time.perf_counter()
    cer = ce.BatchedCeremony(args.curve, n, t, SHARED, random.Random(args.seed))
    jax.block_until_ready((cer.g_table, cer.h_table, cer.coeffs_a, cer.coeffs_b))
    setup_s = time.perf_counter() - t0
    snap1 = runtimeobs.snapshot()
    _note(f"ceremony set-up done: tables {cer.table_seconds:.1f}s, window table {cer.g_table.shape}")

    first, warm = CeremonyTrace(), CeremonyTrace()
    out = cer.run(trace=first)
    snap2 = runtimeobs.snapshot()
    _note(f"ceremony first call done: {first.timings_s}")
    out_warm = cer.run(trace=warm)
    snap3 = runtimeobs.snapshot()
    _note(f"ceremony warm call done: {warm.timings_s}")

    cs = cer.cfg.cs
    want = _host_pubkey(args.curve, _seeded_secret(cs.scalar, n, t, args.seed))
    got, got_warm = _encode_point(cs, out["master"]), _encode_point(cs, out_warm["master"])
    stats = dev.memory_stats() or {}
    phases = ("deal", "fiat_shamir", "verify", "finalise")
    _emit(
        {
            "phase": "ceremony",
            "curve": args.curve,
            "n": n,
            "t": t,
            "setup_s": round(setup_s, 3),
            "tables_s": round(cer.table_seconds, 3),
            "table_cache": cer.table_stats,
            "first_call_s": {p: round(first.timings_s.get(p, 0.0), 3) for p in phases},
            "warm_s": {p: round(warm.timings_s.get(p, 0.0), 3) for p in phases},
            "compile_setup": _compile_delta(snap0, snap1),
            "compile_first_call": _compile_delta(snap1, snap2),
            "compile_warm_call": _compile_delta(snap2, snap3),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "all_ok": bool(np.asarray(out["ok"]).all()),
            "complaints": len(out["complaints"]),
            "master": got.hex(),
            "master_matches_host_oracle": got == want,
            **_path_facts(cs, cer.g_table),
        }
    )
    _require(
        bool(np.asarray(out["ok"]).all()) and bool(np.asarray(out_warm["ok"]).all()),
        "a recipient's batch check failed",
    )
    _require(out["complaints"] == [] and out_warm["complaints"] == [], "complaints were raised")
    _require(got == want and got_warm == want, "master key differs from the host oracle")


def _setup_split() -> dict:
    """What the served path's set-up cost so far, from the program's own
    series: each stored program's build by stage, the loads, the digest
    leg's first call per shape, and the fixed-base tables by source
    (seconds; docs/observability.md)."""
    from dkg_tpu.utils.metrics import REGISTRY

    split: dict = {}
    for series, h in sorted(REGISTRY.snapshot()["histograms"].items()):
        name, _, labels = series.partition("{")
        if name in (
            "aot_build_stage_seconds", "aot_load_seconds", "digest_leg_first_call_seconds",
            "fixed_base_table_seconds",
        ):
            split.setdefault(name, {})[labels.rstrip("}") or "all"] = round(h["sum"], 3)
    return split


def phase_served(args, dev) -> None:
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh
    from dkg_tpu.service import CeremonyRequest, CeremonyScheduler, WarmRuntime, aot, engine
    from dkg_tpu.sign.hash2curve import hash_to_curve_host
    from dkg_tpu.utils import runtimeobs

    # served as a deployment serves: through the executable store, beside
    # the compile cache unless the operator has put it elsewhere
    os.environ.setdefault("DKG_TPU_AOT_DIR", aot.cache_dir())

    n, t = args.served_n or args.n, args.served_t or args.t
    group = gh.ALL_GROUPS[args.curve]
    reqs = [
        CeremonyRequest(args.curve, n, t, shared_string=SHARED, seed=args.seed + 1 + i)
        for i in range(args.served_requests)
    ]
    msgs = [b"chip_smoke message %d" % i for i in range(4)]
    snap0 = runtimeobs.snapshot()
    t0 = time.perf_counter()
    lifecycle: list[str] = []
    request_s = []
    with CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1, runtime=WarmRuntime()
    ) as service:
        cids = [service.submit(r) for r in reqs]
        while (status := service.poll(cids[0])) not in ("done", "failed", "expired", "poisoned"):
            if not lifecycle or lifecycle[-1] != status:
                lifecycle.append(status)
            time.sleep(0.05)
        lifecycle.append(status)
        outs = []
        for cid in cids:
            outs.append(service.result(cid, timeout=900))
            request_s.append(round(time.perf_counter() - t0, 3))
            _note(f"served {cid}: {outs[-1].status} after {request_s[-1]}s")
        snap1 = runtimeobs.snapshot()
        t1 = time.perf_counter()
        sigs = service.sign(cids[0], msgs)
        sign_s = time.perf_counter() - t1
    snap2 = runtimeobs.snapshot()
    _note(f"signed {len(sigs)} messages in {sign_s:.1f}s")

    t2 = time.perf_counter()
    refs = [engine.run_single_reference(r) for r in reqs]
    reference_s = time.perf_counter() - t2
    snap3 = runtimeobs.snapshot()
    _note(f"{len(refs)} reference ceremonies in {reference_s:.1f}s")

    fs = gd.ALL_CURVES[args.curve].scalar
    secrets = [_seeded_secret(fs, n, t, r.seed) for r in reqs]
    masters_ref = [o.master == ref for o, ref in zip(outs, refs)]
    masters_host = [o.master == _host_pubkey(args.curve, s) for o, s in zip(outs, secrets)]
    want_sigs = [
        group.encode(group.scalar_mul_vartime(secrets[0], hash_to_curve_host(group, m)))
        for m in msgs
    ]
    stats = dev.memory_stats() or {}
    _emit(
        {
            "phase": "served",
            "curve": args.curve,
            "n": n,
            "t": t,
            "requests": len(reqs),
            "lifecycle": lifecycle,
            "statuses": [o.status for o in outs],
            "fetched_after_s": request_s,
            "engine_s": [round(o.seconds, 3) for o in outs],
            "qualified": [int(sum(o.qualified)) for o in outs],
            "sign_s": round(sign_s, 3),
            "signatures": len(sigs),
            "reference_s": round(reference_s, 3),
            "compile_requests": _compile_delta(snap0, snap1),
            "compile_sign": _compile_delta(snap1, snap2),
            "compile_reference": _compile_delta(snap2, snap3),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "aot": aot.stats(),
            "setup_split_s": _setup_split(),
            "masters_match_reference": masters_ref,
            "masters_match_host_oracle": masters_host,
            "signatures_match_host_oracle": [g == w for g, w in zip(sigs, want_sigs)],
        }
    )
    _require(all(o.status == "done" for o in outs), f"served request failed: {[o.error for o in outs]}")
    _require(
        all(sum(o.qualified) == n and not o.complaints for o in outs),
        "a served ceremony disqualified a dealer",
    )
    _require(all(masters_ref) and all(masters_host), "served master differs from its reference")
    _require(len(sigs) == len(msgs) and sigs == want_sigs, "signature differs from secret*H(m)")


def projective_batch(cs, shape: tuple, rng) -> "np.ndarray":
    """(*shape, C, L) limbs: eight real group elements tiled over the
    lanes, each lane rescaled by its own random nonzero factor (another
    representative of the same element), an identity (Z = 0) in the
    first lane and about one lane in seven after it.  The parity test
    (tests/test_digest_dispatch.py) builds its batches with it too."""
    import numpy as np

    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh

    group, p = gh.ALL_GROUPS[cs.name], cs.field.modulus
    base = [group.scalar_mul(rng.randrange(1, 1 << 64), group.generator()) for _ in range(8)]
    ints = fh.decode(cs.field, np.asarray(gd.from_host(cs, base)))  # (8, C) Python ints
    ident = np.asarray(gd.identity(cs))
    n = math.prod(shape)
    out = np.empty((n, cs.ncoords, cs.field.limbs), np.uint32)
    for i in range(n):
        if i == 0 or rng.randrange(7) == 0:
            out[i] = ident
        else:
            lam = rng.randrange(1, p)
            out[i] = fh.encode(cs.field, [int(c) * lam % p for c in ints[i % 8]])
    return out.reshape(tuple(shape) + out.shape[1:])


def _round1_host_bytes() -> int:
    """``round1_host_bytes_total``: what the digest's host leg fetched so far."""
    from dkg_tpu.utils.metrics import REGISTRY

    return REGISTRY.snapshot()["counters"].get("round1_host_bytes_total", 0)


def _canon_counts() -> dict:
    from dkg_tpu.utils.metrics import REGISTRY

    return {
        k: v for k, v in REGISTRY.snapshot()["counters"].items() if k.startswith("affine_canon_")
    }


def phase_digest(args, dev) -> None:
    import jax.numpy as jnp
    import numpy as np

    from dkg_tpu.crypto import device_hash as dh
    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh
    from dkg_tpu.service import CeremonyRequest, WarmRuntime, aot, engine

    cs = gd.ALL_CURVES[args.curve]
    n, t = args.served_n or 16, args.served_t or 5
    k = 2 if args.rehearse else 8
    # the convoy's two shapes (one row: no Montgomery scan), then lane
    # counts whose scan is neither absent nor the table build's 256 rows:
    # a four-chip n=1024 t=341 ceremony's shard (85 rows) and
    # hybrid_batch's n*n KEM points at n=64 (4 rows)
    scanned = ((2050,),) if args.rehearse else ((256, 342), (64, 64))
    for shape in ((k * n, t + 1), (k,)) + scanned:
        pts = projective_batch(cs, shape, random.Random(args.seed + len(shape)))
        before = _canon_counts()
        got = np.asarray(gd.affine_canon(cs, jnp.asarray(pts)))
        booked = {k: v - before.get(k, 0) for k, v in _canon_counts().items() if v != before.get(k, 0)}
        equal = bool(np.array_equal(got, gd.affine_canon_host(cs, pts)))
        # the label booked against the program that ran: the compiled
        # module holds a Mosaic launch exactly when the counter said fused
        program = gd._affine_canon_jit.lower(cs, gd._canon_path(), jnp.asarray(pts)).compile()
        kernel_in_program = "tpu_custom_call" in program.as_text()
        fused_booked = any('path="fused"' in key for key in booked)
        _emit(
            {
                "phase": "canon",
                "shape": list(pts.shape),
                "rows": gd._canon_rows(math.prod(shape)),
                "identity_lanes": int((pts[..., 2, :] == 0).all(axis=-1).sum()),
                "equals_host_twin": equal,
                "booked": booked,
                "kernel_in_program": kernel_in_program,
            }
        )
        _require(equal, f"affine_canon differs from affine_canon_host at {pts.shape}")
        _require(
            kernel_in_program == fused_booked,
            f"affine_canon booked {sorted(booked)} but kernel_in_program={kernel_in_program}",
        )

    # the table build's lane count: the 256-row Montgomery scan over the
    # same inversion; canonical limbs are unique, so two commits that
    # both build the table right print one hash
    window = 8 if args.rehearse else 16
    t0 = time.perf_counter()
    table = np.asarray(gd.fixed_base_table_dev(cs, gh.ALL_GROUPS[args.curve].generator(), window))
    _emit(
        {
            "phase": "table",
            "window_bits": window,
            "shape": list(table.shape),
            "build_s": round(time.perf_counter() - t0, 3),
            "blake2b": hashlib.blake2b(table.tobytes(), digest_size=16).hexdigest(),
        }
    )

    reqs = [
        CeremonyRequest(args.curve, n, t, shared_string=SHARED, seed=args.seed + 100 + i)
        for i in range(k)
    ]
    runtime = WarmRuntime()
    engine.run_convoy(runtime, reqs)  # warm: whatever traces or loads does so here
    before = _canon_counts()
    t0 = time.perf_counter()
    outs = engine.run_convoy(runtime, reqs)
    convoy_s = time.perf_counter() - t0
    after = _canon_counts()
    fs = cs.scalar
    masters = [
        o.master == _host_pubkey(args.curve, _seeded_secret(fs, n, t, r.seed))
        for o, r in zip(outs, reqs)
    ]

    fl = engine.start_convoy(runtime, reqs)
    cfg = fl.cfg_pad
    tensors = [np.asarray(x) for x in (fl.a, fl.e, fl.s, fl.r)]
    flat = [x.reshape((k * cfg.n,) + x.shape[2:]) for x in tensors]
    legs = {}
    for leg in ("device", "host"):
        rows = [
            np.asarray(x).reshape(k, cfg.n, -1)
            for x in ce._dealer_rows_device(cfg, *flat, dispatch=leg)
        ]
        digests = [ce._fold_digest_device(cfg, *(r[i] for r in rows)) for i in range(k)]
        rho = np.stack([ce.fiat_shamir_rho(cfg, d, reqs[0].rho_bits) for d in digests])
        legs[leg] = (digests, rho)
    # the served leg: the device arrays as deal left them
    fetched = _round1_host_bytes()
    served_rho = engine.derive_rho_convoy(cfg, fl.a, fl.e, fl.s, fl.r, reqs[0].rho_bits)
    fetched = _round1_host_bytes() - fetched
    same_digest = legs["device"][0] == legs["host"][0]
    same_rho = bool(
        np.array_equal(legs["device"][1], legs["host"][1])
        and np.array_equal(served_rho, legs["host"][1])
    )
    _emit(
        {
            "phase": "digest",
            "curve": args.curve,
            "n": n,
            "t": t,
            "width": k,
            "convoy_s": round(convoy_s, 3),
            "aot": aot.stats() if aot.enabled() else None,
            "statuses": [o.status for o in outs],
            "masters_match_host_oracle": masters,
            "affine_canon_counters_one_convoy": {
                key: after[key] - before.get(key, 0) for key in after
            },
            "transcript_digests": [d.hex() for d in legs["device"][0]],
            "device_leg_digests_equal_host_leg": same_digest,
            "served_rho_equals_host_leg": same_rho,
            "served_leg": dh.digest_dispatch(),
            "round1_host_bytes_served_leg": fetched,
        }
    )
    _require(all(o.status == "done" for o in outs) and all(masters), "a convoy master is wrong")
    _require(same_digest, "transcript digest: device leg differs from host leg")
    _require(same_rho, "rho: device leg differs from host leg")
    _require(
        args.rehearse or (dh.digest_dispatch() == "device" and fetched == 0),
        f"the served digest leg left the device: leg {dh.digest_dispatch()}, fetched {fetched} bytes",
    )


def phase_mesh(args, dev) -> None:
    import jax
    import numpy as np

    import logging

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.parallel import mesh as pm
    from dkg_tpu.utils import runtimeobs

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "benchmark"))
    import bench_oracle

    # the phase log: a run that does not return names its phase
    handler = logging.StreamHandler(sys.stderr)
    handler.setFormatter(logging.Formatter("[mesh %(relativeCreated)9.0fms] %(message)s"))
    logging.getLogger(pm.__name__).addHandler(handler)
    logging.getLogger(pm.__name__).setLevel(logging.INFO)

    # through the executable store, as the served route runs these programs
    from dkg_tpu.service import aot, engine

    os.environ.setdefault("DKG_TPU_AOT_DIR", aot.cache_dir())

    n, t = args.n, args.t
    _require(jax.device_count() == 4, f"--mesh needs 4 devices, found {jax.device_count()}")
    mesh = pm.make_mesh(4)
    cer = ce.BatchedCeremony(args.curve, n, t, SHARED, random.Random(args.seed))
    cs = cer.cfg.cs

    # place the inputs the way run_sharded_ceremony does and look at
    # where they really landed: code that has only seen one chip may
    # put everything on the first
    placed = {
        "coeffs_a": pm.place_sharded(mesh, cer.coeffs_a),
        "coeffs_b": pm.place_sharded(mesh, cer.coeffs_b),
        "g_table": pm.place_sharded(mesh, cer.g_table, pm.P()),
        "h_table": pm.place_sharded(mesh, cer.h_table, pm.P()),
    }
    shard_devices = {
        k: sorted(sh.device.id for sh in v.addressable_shards) for k, v in placed.items()
    }
    shard_rows = {k: [sh.data.shape[0] for sh in v.addressable_shards] for k, v in placed.items()}

    _note(f"inputs placed: {shard_devices}")

    def sharded() -> tuple[dict, float]:
        t0 = time.perf_counter()
        res = pm.run_sharded_ceremony(
            cer.cfg, mesh, *placed.values(), ceremony_id="chip_smoke", run=engine.stored_mesh_program
        )
        jax.block_until_ready((res["master"], res["final_shares"]))
        return res, time.perf_counter() - t0

    snap0 = runtimeobs.snapshot()
    res, first_s = sharded()
    first_phases = {k: round(v, 3) for k, v in res["phases_s"].items()}
    snap1 = runtimeobs.snapshot()
    _note(f"sharded first call done in {first_s:.1f}s: {first_phases}")
    res, warm_s = sharded()
    warm_phases = {k: round(v, 3) for k, v in res["phases_s"].items()}
    snap2 = runtimeobs.snapshot()
    sums = bench_oracle.column_sums(args.curve, n, t, args.seed)
    want = bench_oracle.master_bytes(args.curve, sums)
    _require(sums[0] == _seeded_secret(cs.scalar, n, t, args.seed), "the two host oracles differ")
    shares_h = np.asarray(res["final_shares"])
    parties = sorted(random.Random(args.seed ^ 0x5EED).sample(range(1, n + 1), min(8, n)))
    share_limbs_off = sum(
        int((shares_h[j - 1] != bench_oracle.share_limbs(bench_oracle.final_share(args.curve, sums, j), shares_h.shape[1])).sum())
        for j in parties
    )
    _note(
        f"sharded warm call done in {warm_s:.1f}s: {warm_phases}; master == host oracle: "
        f"{_encode_point(cs, res['master']) == want}; share limbs off over parties {parties}: {share_limbs_off}"
    )

    out_devices = sorted(sh.device.id for sh in res["final_shares"].addressable_shards)
    _emit(
        {
            "phase": "mesh",
            "curve": args.curve,
            "n": n,
            "t": t,
            "mesh_devices": [d.id for d in mesh.devices.flat],
            "input_shard_device_ids": shard_devices,
            "input_shard_rows": shard_rows,
            "final_shares_device_ids": out_devices,
            "first_call_s": round(first_s, 3),
            "first_phases_s": first_phases,
            "warm_s": round(warm_s, 3),
            "warm_phases_s": warm_phases,
            "compile_first_call": _compile_delta(snap0, snap1),
            "compile_warm_call": _compile_delta(snap1, snap2),
            "aot": aot.stats(),
            "peak_bytes_in_use": [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()],
            "all_ok": bool(np.asarray(res["ok"]).all()),
            "all_qualified": bool(np.asarray(res["qualified"]).all()),
            "master_matches_host_oracle": _encode_point(cs, res["master"]) == want,
            "share_limbs_off_host_oracle": share_limbs_off,
            "share_parties": parties,
            **_path_facts(cs, cer.g_table),
        }
    )
    for k, ids in shard_devices.items():
        _require(len(set(ids)) == 4, f"{k} is on devices {ids}, not spread over four")
    _require(
        all(r == n // 4 for r in shard_rows["coeffs_a"] + shard_rows["coeffs_b"]),
        f"coefficients are not split in four equal blocks: {shard_rows}",
    )
    _require(len(set(out_devices)) == 4, f"final shares came back on devices {out_devices}")
    _require(bool(np.asarray(res["ok"]).all()), "a recipient's batch check failed")
    _require(bool(np.asarray(res["qualified"]).all()), "a dealer was disqualified")
    _require(_encode_point(cs, res["master"]) == want, "master key differs from the host oracle")
    _require(share_limbs_off == 0, f"{share_limbs_off} final share limbs differ from the host oracle")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=1024, help="ceremony committee size")
    ap.add_argument("--t", type=int, default=341, help="ceremony threshold")
    ap.add_argument("--served-n", type=int, default=None, help="default: --n")
    ap.add_argument("--served-t", type=int, default=None, help="default: --t")
    ap.add_argument("--served-requests", type=int, default=3)
    ap.add_argument("--seed", type=int, default=22)
    ap.add_argument(
        "--curve", default="secp256k1", choices=("secp256k1", "ristretto255", "bls12_381_g1"),
        help="the curve of every phase (the default is the north star's)",
    )
    ap.add_argument("--mesh", action="store_true", help="four chips: the sharded ceremony only")
    ap.add_argument(
        "--digest", action="store_true",
        help="one chip: canonicalisation, transcript digest and rho only, at (16,5) x8",
    )
    ap.add_argument(
        "--rehearse", action="store_true",
        help="sandbox rehearsal on the CPU backend; never prints the ok line",
    )
    args = ap.parse_args()

    from dkg_tpu.utils import compilecache, runtimeobs

    if args.rehearse:
        from dkg_tpu.parallel.hostmesh import force_cpu_mesh

        force_cpu_mesh(4 if args.mesh else 1)
    cache_dir = compilecache.enable()

    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if not args.rehearse and dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU, JAX found {device}")
    runtimeobs.install(force=True)
    _emit({"phase": "start", "device": device, "jax": jax.__version__, "compile_cache": cache_dir})

    t0 = time.perf_counter()
    if args.mesh:
        phase_mesh(args, dev)
    elif args.digest:
        phase_digest(args, dev)
    else:
        phase_served(args, dev)
        phase_ceremony(args, dev)
    _emit({"phase": "end", "total_s": round(time.perf_counter() - t0, 3)})
    if args.rehearse:
        _emit({"rehearsal": True, "device": device})
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
