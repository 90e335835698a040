#!/usr/bin/env python
"""DKG round-kernel benchmark — prints ONE JSON line.

Workload: the share-verification round, the ceremony's dominant cost
(SURVEY §6: n·(n-1) size-(t+1) MSM checks in the reference,
committee.rs:292-296).  Here it is the RLC batch-verify kernel
(dkg_tpu.dkg.ceremony.verify_batch), which validates all n·(n-1) pair
relations at once; the reported rate is pair-verifications per second
on one chip.

vs_baseline: the reference publishes no numbers (BASELINE.md), so the
ratio is against the driver-defined north star — a full n=4096 ceremony
in < 10 s on a v5e-8, i.e. 4096^2/10/8 ≈ 209,715 pair-verifies/s/chip.
value/209715 > 1 means the verification round is on budget.

The dealing round's hybrid-encryption leg is measured alongside
(``config.pairs_sealed_per_s``): all n*n (dealer, recipient) pairs
sealed through the vectorized host DEM (dkg.hybrid_batch), with the
per-pair scalar reference leg timed on the same KEM tensors — the
resulting ``config.dem.speedup`` isolates the DEM the batch path
replaces — and the chunk-overlapped KEM+DEM pipeline's wall time as
``config.dem.pipeline_s`` (docs/perf.md "Dealing pipeline";
scripts/perf_regress.py gates pairs_sealed_per_s too).

One process, one chip: everything runs in this process (a chip belongs
to one process at a time, so nothing is measured in a child), every
timing ends in ``block_until_ready``, and a run that finds no TPU
FAILS — there is no CPU leg and no placeholder line.  ROADMAP D1
schedules this harness for replacement by the benchmark PR (S0).
"""

from __future__ import annotations

import json
import os
import random
import sys
import time

import jax
import jax.numpy as jnp

NORTH_STAR_RATE_PER_CHIP = 4096 * 4096 / 10.0 / 8.0

#: BASELINE.json config 3 — the one shape ever measured on a chip
SHAPE = ("secp256k1", 1024, 341)


def _bench_repeats() -> int:
    try:
        return max(1, int(os.environ.get("DKG_TPU_BENCH_REPEATS", "3")))
    except ValueError:
        return 3


def timed(fn, *args):
    """Warm once, then time ``DKG_TPU_BENCH_REPEATS`` passes (default 3)
    and keep the FASTEST.  Elapsed-time noise on a shared box is
    strictly additive (scheduler preemption, cache pollution from the
    neighbouring phase), so min is the standard location estimator for
    the code's own cost — six single-shot runs of an identical build
    swung individual phase rates by >20% on the 1-core CI box, past
    perf_regress's own tolerance, which is exactly the flakiness this
    buys back for ~6s of extra rung wall."""
    out = fn(*args)
    jax.block_until_ready(out)  # the compile
    best = float("inf")
    for _ in range(_bench_repeats()):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return out, best


def parity_check(curve: str = "secp256k1", n: int = 64, t: int = 21) -> bool:
    """TPU-vs-CPU bit-exact parity on identical inputs (north-star
    requirement, BASELINE.json): deal + batch-verify on the default
    (TPU, fused-kernel) path and on the CPU XLA path.

    Scalars (share/hiding matrices, verdicts) must be LIMB-exact.
    Points (commitment tensors) are compared on their CANONICAL
    encodings: the two legs legitimately run different addition
    schedules (16-bit device tables vs 8-bit host tables, Straus vs
    bit ladder), which yield projectively-equal points with different
    Z scales — byte-equality of the compressed encodings is the
    protocol-boundary bit-exactness that matters.  Returns True iff
    both hold.
    """
    import numpy as np

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh

    rng = random.Random(0x9A71)
    c = ce.BatchedCeremony(curve, n, t, b"parity", rng)
    cfg = c.cfg
    group = gh.ALL_GROUPS[curve]

    def canon_points(arr: np.ndarray) -> list[bytes]:
        cs = cfg.cs
        flat = arr.reshape(-1, cs.ncoords, cs.field.limbs)
        return [group.encode(p) for p in gd.to_host(cs, flat)]

    def leg():
        a, e, s, r = ce.deal(cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
        rho = jnp.asarray(ce.derive_rho(cfg, a, e, s, r, 64))
        ok = ce.verify_batch(cfg, e, s, r, rho, 64, c.g_table, c.h_table)
        return (
            canon_points(np.asarray(a)),
            canon_points(np.asarray(e)),
            [np.asarray(x) for x in (s, r, ok)],
        )

    tpu_out = leg()
    # CPU leg: pure-XLA path — disable BOTH fused-kernel families AND
    # pin the bit-ladder RLC schedule so the cross-check is against an
    # independent formulation of every hot op (Pallas point kernels,
    # MXU int8 field matmul, Straus point-RLC).
    prev = {
        k: os.environ.get(k)
        for k in ("DKG_TPU_PALLAS", "DKG_TPU_MXU", "DKG_TPU_RLC")
    }
    os.environ["DKG_TPU_PALLAS"] = "0"
    os.environ["DKG_TPU_MXU"] = "0"
    os.environ["DKG_TPU_RLC"] = "bits"
    try:
        cpu = jax.devices("cpu")[0]
        with jax.default_device(cpu):
            c.g_table = jax.device_put(c.g_table, cpu)
            c.h_table = jax.device_put(c.h_table, cpu)
            c.coeffs_a = jax.device_put(c.coeffs_a, cpu)
            c.coeffs_b = jax.device_put(c.coeffs_b, cpu)
            cpu_out = leg()
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    a_t, e_t, scalars_t = tpu_out
    a_c, e_c, scalars_c = cpu_out
    return (
        a_t == a_c
        and e_t == e_c
        and all(bool((x == y).all()) for x, y in zip(scalars_t, scalars_c))
    )


def _seal_rates(cfg, c, shares, hidings, rng, n: int) -> dict:
    """Dealing DEM leg, measured where the vectorization lives: the host
    DEM (point compression -> Blake2b KDF -> ChaCha20) of all n*n pairs,
    batch vs per-pair scalar reference, BOTH on the same materialized
    KEM tensors — so ``dem_speedup`` isolates the DEM and is not diluted
    by the (unchanged) device KEM, which at the CPU rung costs ~100x the
    batch DEM itself.  ``seal_s`` / ``pairs_sealed_per_s`` is the batch
    DEM leg; the chunk-overlapped device-KEM+DEM pipeline's wall time is
    recorded separately (``pipeline_s``).

    The scalar leg runs over a dealer subset at large n (full at the CPU
    rung shape) to bound its Python-loop cost.
    """
    import numpy as np

    from dkg_tpu.dkg import hybrid_batch as hb
    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh

    g = gh.ALL_GROUPS[cfg.curve]
    fs = cfg.cs.scalar
    # recipient communication keys derived on device: one fixed-base
    # batch mult instead of n host ladder walks
    sks = jnp.asarray(fh.encode(fs, [fs.rand_int(rng) for _ in range(n)]))
    pks_dev = gd.fixed_base_mul(cfg.cs, c.g_table, sks)
    r_enc = jnp.asarray(
        fh.encode(fs, [[fs.rand_int(rng) for _ in range(n)] for _ in range(n)])
    )
    shares_np = np.asarray(shares)
    hidings_np = np.asarray(hidings)
    # materialize the KEM tensors once; both DEM legs consume these
    c1, kem = hb.kem_batch(cfg, pks_dev, r_enc, c.g_table)
    c1, kem = np.asarray(c1), np.asarray(kem)
    _, seal_s = timed(
        lambda: hb.seal_shares_batch(g, cfg, shares_np, hidings_np, c1, kem)
    )
    # scalar reference leg: one pass (host Python, nothing to warm)
    m_sc = min(n, max(1, 4096 // n))
    t0 = time.perf_counter()
    hb.seal_shares(
        g, cfg, shares_np[:m_sc], hidings_np[:m_sc], c1[:m_sc], kem[:m_sc]
    )
    scalar_s = time.perf_counter() - t0
    # full pipeline wall time (KEM kernels already compiled above, so a
    # single pass is representative without a second ~n² KEM warmup)
    t0 = time.perf_counter()
    jax.block_until_ready(
        hb.seal_shares_pipeline(
            g, cfg, shares_np, hidings_np, pks_dev, r_enc, c.g_table
        )
    )
    pipeline_s = time.perf_counter() - t0
    pairs, sc_pairs = n * n, m_sc * n
    batch_rate = pairs / max(seal_s, 1e-9)
    scalar_rate = sc_pairs / max(scalar_s, 1e-9)
    return {
        "seal_s": seal_s,
        "pairs": pairs,
        "scalar_s": scalar_s,
        "scalar_pairs": sc_pairs,
        "speedup": batch_rate / max(scalar_rate, 1e-9),
        "pipeline_s": pipeline_s,
    }


def run(curve: str, n: int, t: int, rho_bits: int = 128):
    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.utils.tracing import CeremonyTrace

    rng = random.Random(0xBE7C)
    c = ce.BatchedCeremony(curve, n, t, b"bench", rng)
    cfg = c.cfg

    (a, e, s, r), t_deal = timed(
        lambda ca, cb: ce.deal_chunked(cfg, ca, cb, c.g_table, c.h_table),
        c.coeffs_a,
        c.coeffs_b,
    )
    # dealing DEM leg: batch seal of all n*n pairs + scalar reference
    seal = _seal_rates(cfg, c, s, r, rng, n)
    # sound Fiat-Shamir: rho from the full round-1 transcript digest.
    # Deliberately COLD (single un-warmed call): a ceremony derives rho
    # exactly once, so first-call cost — compile on the device leg,
    # nothing on the numpy host leg — IS the production cost.  The trace
    # splits it into digest/rho sub-timings and records which dispatch
    # leg (device|host) ran.
    fs_trace = CeremonyTrace()
    t0 = time.perf_counter()
    rho = jnp.asarray(ce.derive_rho(cfg, a, e, s, r, rho_bits, trace=fs_trace))
    t_rho = time.perf_counter() - t0
    # The host leg (numpy BLAKE2s) has nothing to warm — the cold-call
    # doctrine above is about device-leg compile cost — so it gets the
    # same best-of-N treatment as every timed() phase.  The device leg
    # stays a single cold call: its first-call compile IS the cost.
    if fs_trace.meta.get("digest_dispatch") == "host":
        for _ in range(_bench_repeats() - 1):
            tr_i = CeremonyTrace()
            t0 = time.perf_counter()
            rho_i = jnp.asarray(
                ce.derive_rho(cfg, a, e, s, r, rho_bits, trace=tr_i)
            )
            dt = time.perf_counter() - t0
            if dt < t_rho:
                t_rho, fs_trace, rho = dt, tr_i, rho_i
    fs_sub = {
        "sub_s": dict(fs_trace.subtimings_s.get("fiat_shamir", {})),
        "dispatch": fs_trace.meta.get("digest_dispatch"),
    }
    ok, t_verify = timed(
        lambda e_, s_, r_, rho_: ce.verify_batch(
            cfg, e_, s_, r_, rho_, rho_bits, c.g_table, c.h_table
        ),
        e, s, r, rho,
    )
    assert bool(jnp.all(ok)), "batch verification failed in bench"
    # XLA cost probes on the hot executables: estimated FLOPs/bytes
    # land in the runtime block next to the measured seconds above
    # (best-effort — a failed lowering returns None, never raises)
    from dkg_tpu.utils import runtimeobs

    runtimeobs.probe_jitted(
        "deal", ce.deal, cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table
    )
    runtimeobs.probe_jitted(
        "verify_batch", ce.verify_batch,
        cfg, e, s, r, rho, rho_bits, c.g_table, c.h_table,
    )
    table = {"seconds": c.table_seconds, "stats": dict(c.table_stats)}
    return t_deal, t_verify, t_rho, fs_sub, table, seal


def main() -> int:
    from dkg_tpu.fields import device as fd
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh
    from dkg_tpu.utils import compilecache, runtimeobs, serde

    compilecache.enable()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py: no TPU, JAX found {device}")
    runtimeobs.install(force=True)
    curve, n, t = SHAPE
    t_deal, t_verify, t_rho, fs_sub, table, seal = run(curve, n, t)
    runtimeobs.sample_memory()
    parity = parity_check()
    cs = gd.ALL_CURVES[curve]
    pairs = n * (n - 1)
    rate = pairs / t_verify
    wire_total = serde.ceremony_wire_bytes(gh.ALL_GROUPS[curve], n, t)
    print(
        json.dumps(
            {
                "metric": "share_verify_pairs_per_sec_per_chip",
                "value": round(rate, 1),
                "unit": "pair-verifications/s",
                "vs_baseline": round(rate / NORTH_STAR_RATE_PER_CHIP, 4),
                "device": device,
                "config": {
                    "curve": curve,
                    "n": n,
                    "t": t,
                    "platform": dev.platform,
                    "deal_s": round(t_deal, 6),
                    "verify_s": round(t_verify, 6),
                    "fiat_shamir_s": round(t_rho, 6),
                    "fiat_shamir_sub_s": {k: round(v, 6) for k, v in fs_sub["sub_s"].items()},
                    "digest_dispatch": fs_sub["dispatch"],
                    "seal_s": round(seal["seal_s"], 6),
                    "table_s": round(table["seconds"], 6),
                    "pairs_sealed_per_s": round(seal["pairs"] / seal["seal_s"], 1),
                    "wire_bytes": wire_total,
                    "bytes_per_pair": round(wire_total / pairs, 1),
                    "dem": {
                        "scalar_s": round(seal["scalar_s"], 6),
                        "scalar_pairs": seal["scalar_pairs"],
                        "speedup": round(seal["speedup"], 2),
                        "pipeline_s": round(seal["pipeline_s"], 6),
                    },
                    # warm == the fixed-base tables came from a cache
                    # (disk or process): zero from-scratch builds
                    "warm": table["stats"].get("builds", 0) == 0,
                    "table_stats": table["stats"],
                    "pallas_ceremony": bool(gd.fused_kernels_active()),
                    "mul_dispatch": {
                        "base": fd.mul_dispatch_mode(cs.field),
                        "scalar": fd.mul_dispatch_mode(cs.scalar),
                    },
                    "checkpoint": bool(os.environ.get("DKG_TPU_CHECKPOINT_DIR")),
                    "flags": {},
                    "tpu_cpu_bit_exact": parity,
                },
                "runtime": runtimeobs.snapshot(),
            }
        )
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
