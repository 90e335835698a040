#!/usr/bin/env python
"""Hybrid-encryption leg benchmark: device KEM + host DEM.

The headline bench (bench.py) measures the mesh-internal ceremony,
where share limbs move between shards of ONE trust domain in plaintext
(see docs/performance.md "Which ceremony mode the numbers describe").
The reference's dealing instead pays 4n KEM scalar-mults per dealer on
the wire path (reference: elgamal.rs:134-145, committee.rs:163-186).
This script measures that leg as built here (dkg/hybrid_batch.py):

1. device KEM for ALL n^2 (dealer, recipient) pairs — two batched
   kernels, ``c1 = g*r`` (fixed-base) + ``kem = pk_i*r`` (variable
   base); reported as KEM pair-seals per second (each pair seals one
   (share, hiding) ciphertext pair, 2 scalar-mults — the reference
   costs 4 per pair because it runs one KEM per ciphertext);
2. host DEM (compress -> Blake2b KDF -> ChaCha20, native C++ when
   built) for one dealer row, reported as sealed pairs/s;
3. recipient-side open_share round-trip correctness for a spot pair.

Writes KEM_BENCH.json at the repo root and prints it.

Usage: python scripts/kem_bench.py [--n 256] [--curve secp256k1]
"""

from __future__ import annotations

import argparse
import json
import pathlib
import random
import sys
import time

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))

import bench  # noqa: E402 — timed()
from dkg_tpu.utils import compilecache  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--curve", default="secp256k1")
    ap.add_argument("--out", default=str(_REPO / "KEM_BENCH.json"))
    args = ap.parse_args()

    compilecache.enable()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.dkg import hybrid_batch as hb
    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh

    n, curve = args.n, args.curve
    rng = random.Random(0x4B454D)  # "KEM"
    cfg = ce.CeremonyConfig(curve, n, 0)
    cs, group = cfg.cs, gh.ALL_GROUPS[curve]
    fs = cs.scalar

    # recipient communication keys (host CSPRNG, like the protocol)
    sks = [fs.rand_int(rng) for _ in range(n)]
    pk_pts = [group.scalar_mul(sk, group.generator()) for sk in sks]
    pks_dev = gd.from_host(cs, pk_pts)
    g_table = gd.fixed_base_table(cs, group.generator())

    # fresh encryption randomness for all n^2 pairs
    r_ints = [[fs.rand_int(rng) for _ in range(n)] for _ in range(n)]
    r_limbs = jnp.asarray(fh.encode(fs, r_ints))

    import jax

    kem_fn = jax.jit(lambda r: hb.kem_batch(cfg, pks_dev, r, g_table))
    (c1, kem), kem_s = bench.timed(kem_fn, r_limbs)
    pairs = n * n
    kem_rate = pairs / max(kem_s, 1e-6)

    # host DEM over one dealer row (the per-dealer wire cost)
    shares = np.asarray(fh.encode(fs, [[fs.rand_int(rng) for _ in range(n)]]))
    hidings = np.asarray(fh.encode(fs, [[fs.rand_int(rng) for _ in range(n)]]))
    c1_np, kem_np = np.asarray(c1[:1]), np.asarray(kem[:1])
    t0 = time.perf_counter()
    sealed = hb.seal_shares(group, cfg, shares, hidings, c1_np, kem_np)
    dem_s = time.perf_counter() - t0
    dem_rate = n / max(dem_s, 1e-6)

    # spot-check: recipient 0 opens dealer 0's pair
    s0, h0 = hb.open_share(group, sks[0], sealed[0][0])
    ok = s0 == int(fh.decode_int(fs, shares[0, 0])) and h0 == int(
        fh.decode_int(fs, hidings[0, 0])
    )

    from dkg_tpu import native

    report = {
        "curve": curve,
        "n": n,
        "pairs": pairs,
        "platform": jax.default_backend(),
        "kem_s": round(kem_s, 4),
        "kem_pairs_per_sec": round(kem_rate, 1),
        "dem_row_s": round(dem_s, 4),
        "dem_pairs_per_sec": round(dem_rate, 1),
        "dem_native": bool(native.available()),
        "roundtrip_ok": bool(ok),
    }
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    if not ok:
        sys.exit(1)


if __name__ == "__main__":
    main()
