#!/usr/bin/env python
"""Host-memory proof for the streaming DEM/transport leg.

The device-side scale claim is MEMPROOF.json (scripts/memproof.py):
no O(n*t) tensor is ever replicated on a chip.  This script proves the
matching HOST-side claim for the sealing leg the north-star ceremony
runs (``dkg.hybrid_batch.seal_shares_mesh``): the dealing round's
(n, n, L) share and hiding tensors are walked mesh shard by mesh shard,
so the host only ever materialises O(n^2/ndev) slab bytes at a time —
never the full O(n^2) matrices that a naive ``np.asarray(shares)``
would pin (34+ GB at BLS12-381 n=16384, which is what keeps the
n=16384 dealing round inside a host).

Two legs, one artifact (default MEMPROOF_STREAM.json at the repo root):

1. ANALYTIC at the target shape (default BLS12-381 G1, n=16384,
   t=5461, 8-way mesh) — pure arithmetic over the limb layout, no
   allocation: peak resident slab bytes (current shard + the one
   prefetching under it, shares + hidings each) plus the bounded
   per-chunk DEM working set, versus the full-tensor bytes the
   unsharded path pins.
2. MEASURED at a feasible shape (default secp256k1 n=64, t=21 over the
   same 8-way mesh) — ``tracemalloc`` peaks around the real
   ``seal_shares_mesh`` call on mesh-sharded device arrays versus
   ``seal_shares_pipeline`` on the fully materialised host tensors,
   with a byte-exact compare of the sealed (share, hiding) ciphertext
   pairs between the two paths (shard blocks are independent dealer
   rows, so streaming may not change a single wire byte).

Exit is non-zero if the target-shape streaming peak misses the host
budget, the full tensors DO fit it (the claim would be vacuous), or the
measured paths disagree on any sealed byte.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tracemalloc

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

if __name__ == "__main__":  # virtual mesh before jax init
    # A STATIC analysis (lower + compile, never execute) over a virtual
    # CPU mesh; the device count must match --ndev, so peek at argv.
    from dkg_tpu.parallel.hostmesh import force_cpu_mesh

    _ndev = 8
    for _i, _a in enumerate(sys.argv):
        if _a == "--ndev" and _i + 1 < len(sys.argv):
            _ndev = int(sys.argv[_i + 1])
        elif _a.startswith("--ndev="):
            _ndev = int(_a.split("=", 1)[1])
    force_cpu_mesh(_ndev)

import random

import numpy as np

from dkg_tpu.dkg import ceremony as ce


def analytic(cfg: ce.CeremonyConfig, ndev: int, dem_chunk: int | None) -> dict:
    """Peak host bytes of seal_shares_mesh at (cfg.n, ndev), by layout
    arithmetic.

    Resident at any instant: shard k's share+hiding slabs (being
    sealed) AND shard k+1's (transfer started before k's DEM blocks),
    each (n/ndev, n, L) u32 — plus one DEM chunk's working set, which
    is bounded by the ~4096-pairs-per-chunk default regardless of n.
    The unsharded pipeline pins both full (n, n, L) tensors instead.
    """
    fs = cfg.cs.scalar
    n = cfg.n
    limb_bytes = fs.limbs * 4  # u32 limb vector per scalar
    slab_rows = n // ndev
    slab_bytes = slab_rows * n * limb_bytes  # one tensor, one shard
    # current + prefetching shard, shares + hidings each
    resident_slab_bytes = 4 * slab_bytes

    chunk_dealers = dem_chunk if dem_chunk else max(1, 4096 // n)
    pairs = chunk_dealers * n
    # per sealed pair: plaintext + ciphertext for both tags (4 *
    # fs.nbytes), the encoded KEM point keying the KDF, and the derived
    # key/nonce pair per tag (Blake2b state rows) — 3 point-encodings'
    # worth covers all three comfortably
    dem_pair_bytes = 4 * fs.nbytes + 3 * (cfg.cs.field.limbs * 4)
    dem_working_bytes = pairs * dem_pair_bytes

    full_tensor_bytes = 2 * n * n * limb_bytes
    streaming_peak = resident_slab_bytes + dem_working_bytes
    return {
        "scalar_limb_bytes": limb_bytes,
        "slab_bytes_per_tensor": slab_bytes,
        "resident_slab_bytes": resident_slab_bytes,
        "dem_chunk_dealers": chunk_dealers,
        "dem_working_bytes": dem_working_bytes,
        "streaming_peak_bytes": streaming_peak,
        "full_tensor_bytes": full_tensor_bytes,
        "reduction_factor": full_tensor_bytes / streaming_peak,
    }


def measured(curve: str, n: int, t: int, ndev: int) -> dict:
    """tracemalloc peaks around the two real sealing paths at a shape
    this box can run, plus the sealed-byte equality between them."""
    import jax.numpy as jnp

    from dkg_tpu.crypto import Keypair
    from dkg_tpu.dkg import hybrid_batch as hb
    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh
    from dkg_tpu.parallel import mesh as pmesh

    rng = random.Random(0x57E4)
    g = gh.ALL_GROUPS[curve]
    c = ce.BatchedCeremony(curve, n, t, b"memproof-stream", rng)
    cfg = c.cfg
    fs = cfg.cs.scalar
    mesh = pmesh.make_mesh(ndev)

    keys = [Keypair.generate(g, rng) for _ in range(n)]
    pks_dev = gd.from_host(cfg.cs, [k.pk for k in keys])
    r_enc = jnp.asarray(
        fh.encode(fs, [[fs.rand_int(rng) for _ in range(n)] for _ in range(n)])
    )

    ca = pmesh.place_sharded(mesh, jnp.asarray(c.coeffs_a))
    cb = pmesh.place_sharded(mesh, jnp.asarray(c.coeffs_b))
    gt = pmesh.place_sharded(mesh, jnp.asarray(c.g_table), pmesh.P())
    ht = pmesh.place_sharded(mesh, jnp.asarray(c.h_table), pmesh.P())
    s_sh, r_sh = pmesh.sharded_deal_shares(cfg, mesh, ca, cb)

    def flat(sealed) -> bytes:
        out = []
        for row in sealed:
            for share_ct, hiding_ct in row:
                for ct in (share_ct, hiding_ct):
                    out.append(g.encode(ct.e1) + ct.ciphertext)
        return b"".join(out)

    def peak_of(fn):
        tracemalloc.start()
        try:
            sealed = fn()
            _, peak = tracemalloc.get_traced_memory()
        finally:
            tracemalloc.stop()
        return sealed, int(peak)

    # warm the compile caches first so neither peak counts jit metadata
    hb.seal_shares_mesh(g, cfg, mesh, s_sh, r_sh, pks_dev, r_enc, gt)
    s_full, r_full = np.asarray(s_sh), np.asarray(r_sh)
    hb.seal_shares_pipeline(g, cfg, s_full, r_full, pks_dev, r_enc, gt)

    sealed_stream, peak_stream = peak_of(
        lambda: hb.seal_shares_mesh(g, cfg, mesh, s_sh, r_sh, pks_dev, r_enc, gt)
    )
    sealed_full, peak_full = peak_of(
        lambda: hb.seal_shares_pipeline(
            g, cfg, np.asarray(s_sh), np.asarray(r_sh), pks_dev, r_enc, gt
        )
    )
    return {
        "curve": curve,
        "n": n,
        "t": t,
        "n_devices": ndev,
        "streaming_peak_bytes": peak_stream,
        "full_pipeline_peak_bytes": peak_full,
        "bit_exact": flat(sealed_stream) == flat(sealed_full),
        "note": (
            "tracemalloc peaks over host allocations only (device "
            "buffers excluded); at small n the bounded DEM chunk "
            "working set dominates both paths, so the slab-vs-full "
            "gap is the analytic leg's claim, not this one's — this "
            "leg pins that streaming costs no EXTRA host memory and "
            "not a single sealed wire byte"
        ),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--curve", default="bls12_381_g1")
    ap.add_argument("--n", type=int, default=16384)
    ap.add_argument("--t", type=int, default=5461)
    ap.add_argument("--ndev", type=int, default=8)
    ap.add_argument("--dem-chunk", type=int, default=None)
    ap.add_argument("--host-budget-gb", type=float, default=32.0)
    ap.add_argument("--measure-curve", default="secp256k1")
    ap.add_argument("--measure-n", type=int, default=64)
    ap.add_argument("--measure-t", type=int, default=21)
    ap.add_argument("--skip-measure", action="store_true")
    ap.add_argument(
        "--out",
        default=str(
            pathlib.Path(__file__).parent.parent / "MEMPROOF_STREAM.json"
        ),
    )
    args = ap.parse_args()

    cfg = ce.CeremonyConfig(args.curve, args.n, args.t)
    ana = analytic(cfg, args.ndev, args.dem_chunk)
    budget = int(args.host_budget_gb * (1 << 30))
    report = {
        "config": {
            "curve": args.curve,
            "n": args.n,
            "t": args.t,
            "n_devices": args.ndev,
            "host_budget_bytes": budget,
        },
        "analytic": ana,
        "streaming_fits_budget": ana["streaming_peak_bytes"] < budget,
        "full_tensors_fit_budget": ana["full_tensor_bytes"] < budget,
    }
    if not args.skip_measure:
        report["measured"] = measured(
            args.measure_curve, args.measure_n, args.measure_t, args.ndev
        )

    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    ok = report["streaming_fits_budget"] and not report[
        "full_tensors_fit_budget"
    ]
    if "measured" in report:
        ok = ok and report["measured"]["bit_exact"]
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
