#!/usr/bin/env python
"""AOT TPU compile lab: validate the single-chip bench programs against
the REAL TPU compiler without a chip.

Round-4 discovery: ``jax.experimental.topologies.get_topology_desc``
works locally (libtpu compile-only, no device needed), and the first
AOT compile of the sharded program caught a layout problem invisible to
XLA:CPU — TPU tiling T(4,128) pads the minor ``(C, L)`` point dims of
big resting tensors ~7x (u32[11186176,3,24] -> 21.3 GB).  This lab
AOT-compiles the SINGLE-CHIP deal/verify programs at bench shapes and
reports per-buffer HBM so layout regressions are caught before a chip
window is spent on an OOM.

Usage (no chip needed — the TPU compiler runs against a described topology):

    JAX_PLATFORMS=cpu python scripts/aot_lab.py [n t curve]

Knobs (utils.envknobs): ``DKG_TPU_AOT_TOPOLOGY`` picks the chip-less topology to compile for
(default ``v5e:2x2``), ``DKG_TPU_ASSUME_BACKEND`` the flag-resolution
backend.

Prints one JSON line per compiled phase with memory analysis.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

# Compile-only: keep the process itself on the CPU backend.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

from dkg_tpu.utils import envknobs  # noqa: E402

# Resolve every backend-sensitive dispatch (fused kernels, MXU, table
# width, RLC schedule) as if on the chip, so the compiled program is
# the one the chip actually runs.  Override with DKG_TPU_ASSUME_BACKEND=cpu
# to model the conservative flag set.
if not envknobs.choice(
    "DKG_TPU_ASSUME_BACKEND", ("cpu", "tpu"), "flag-resolution backend"
):
    os.environ["DKG_TPU_ASSUME_BACKEND"] = "tpu"

import jax
import jax.numpy as jnp

from jax.experimental import topologies as jtop

from dkg_tpu.dkg import ceremony as ce

N = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
T = int(sys.argv[2]) if len(sys.argv) > 2 else 1365
CURVE = sys.argv[3] if len(sys.argv) > 3 else "secp256k1"
WINDOW = 16  # the fixed-base window of the on-chip default (gd.default_fixed_window)
TOPOLOGY = (
    envknobs.string("DKG_TPU_AOT_TOPOLOGY", "chip-less AOT compile topology")
    or "v5e:2x2"
)
RHO_BITS = 128

# v5e:1x1 is rejected by the default 2x2x1 chips_per_host_bounds, so
# the default describes the smallest valid slice (2x2) and compiles for
# ONE of its devices — the executable is single-device either way.
topo = jtop.get_topology_desc(TOPOLOGY, "tpu")
dev = topo.devices[0]
from jax.sharding import SingleDeviceSharding

sharding = SingleDeviceSharding(dev)

cfg = ce.CeremonyConfig(CURVE, N, T)
cs = cfg.cs
fs, bf = cs.scalar, cs.field
u32 = jnp.uint32
nw = fs.limbs * (16 // WINDOW)


def sds(shape):
    return jax.ShapeDtypeStruct(shape, u32, sharding=sharding)


def report(name, lowered):
    try:
        ex = lowered.compile()
        ma = ex.memory_analysis()
        rec = {
            "phase": name,
            "n": N,
            "t": T,
            "curve": CURVE,
            "fb_window": WINDOW,
            "ok": True,
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_hbm_bytes": int(
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
            ),
            "fits_16g": (
                ma.argument_size_in_bytes
                + ma.output_size_in_bytes
                + ma.temp_size_in_bytes
            )
            < (16 << 30),
        }
    except Exception as exc:  # noqa: BLE001 — record the rejection verbatim
        rec = {
            "phase": name,
            "n": N,
            "t": T,
            "curve": CURVE,
            "fb_window": WINDOW,
            "ok": False,
            "error": f"{type(exc).__name__}: {exc}"[:500],
        }
    print(json.dumps(rec), flush=True)
    return rec


table_shape = (nw, 1 << WINDOW, cs.ncoords, bf.limbs)
args_deal = (
    sds((N, T + 1, fs.limbs)),
    sds((N, T + 1, fs.limbs)),
    sds(table_shape),
    sds(table_shape),
)
report(
    "deal",
    jax.jit(lambda ca, cb, gt, ht: ce.deal(cfg, ca, cb, gt, ht)).lower(*args_deal),
)

# the production path on TPU since round 5: dealing is TWO sequential
# programs (commitments, then shares), each dealer-chunked in-trace —
# vet exactly what the engine runs, not the pre-split monolith (a shape
# can pass the monolith compile and still have its real shares program
# rejected)
report(
    "deal_commitments_chunked",
    jax.jit(
        lambda ca, cb, gt, ht: ce.deal_commitments_traced_chunked(
            cfg, ca, cb, gt, ht
        )
    ).lower(*args_deal),
)
report(
    "deal_shares_chunked",
    jax.jit(lambda ca, cb: ce.deal_shares_traced_chunked(cfg, ca, cb)).lower(
        *args_deal[:2]
    ),
)
# the host-loop single-chip path (deal_chunked) compiles one chunk-sized
# program per call; vet that program too
chunk = ce._deal_chunk_default(cfg)
if chunk < N:
    args_chunk = (
        sds((chunk, T + 1, fs.limbs)),
        sds((chunk, T + 1, fs.limbs)),
        sds(table_shape),
        sds(table_shape),
    )
    report(
        f"deal_chunk_{chunk}",
        jax.jit(lambda ca, cb, gt, ht: ce.deal(cfg, ca, cb, gt, ht)).lower(*args_chunk),
    )

pt = (N, T + 1, cs.ncoords, bf.limbs)
args_verify = (
    sds(pt),
    sds((N, N, fs.limbs)),
    sds((N, N, fs.limbs)),
    sds((N, fs.limbs)),
    sds(table_shape),
    sds(table_shape),
)
report(
    "verify_batch",
    jax.jit(
        lambda e, s, r, rho, gt, ht: ce.verify_batch(
            cfg, e, s, r, rho, RHO_BITS, gt, ht
        )
    ).lower(*args_verify),
)
