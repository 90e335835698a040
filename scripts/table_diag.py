#!/usr/bin/env python
"""Pin down the window-16 device table-build stall on the real chip.

The round-4 queue showed BatchedCeremony setup (fixed_base_table_dev at
window=16: a (16, 65536)-lane scalar_mul_small ladder + one Montgomery
batch inversion) never completing within 1800 s on TPU, with BOTH
Pallas on and off — while the same build finishes in seconds on CPU.
This script times each component separately at ramping shapes so the
stalling op is named, not guessed.  Run under an external timeout:

    timeout 1200 python scripts/table_diag.py
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from dkg_tpu.utils import compilecache

compilecache.enable()

from dkg_tpu.fields import device as fd
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh

CURVE = sys.argv[1] if len(sys.argv) > 1 else "secp256k1"
print(f"platform={jax.devices()[0].platform} curve={CURVE} "
      f"PALLAS={os.environ.get('DKG_TPU_PALLAS', '<default>')}", flush=True)

cs = gd.ALL_CURVES[CURVE]
f = cs.field
host_group = gh.ALL_GROUPS[CURVE]


def timed(name, fn):
    t0 = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    print(f"{name:44s} {time.perf_counter() - t0:9.2f} s", flush=True)
    return out


g = gh.ALL_GROUPS[CURVE].generator()
g_dev = gd.from_host(cs, [g])[0]

# 1. FIRST, the evidence this script exists for: the COMPOSED window-16
#    build (the round-4 fix).  Risky ramps come after, so a stall in a
#    known-bad component cannot eat the budget before this lands.
gd._fixed_table_dev_cached.cache_clear()
timed("fixed_base_table_dev window=16 (composed)",
      lambda: gd.fixed_base_table_dev(cs, g, 16))

# 2. batch_inv at ramping lane counts (the Montgomery-trick component)
for lanes in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
    x = jnp.ones((lanes, f.limbs), jnp.uint32).at[:, 0].set(
        jnp.arange(1, lanes + 1, dtype=jnp.uint32)
    )
    rows = 256 if lanes % 256 == 0 else 1
    timed(
        f"batch_inv lanes={lanes} rows={rows}",
        lambda x=x, rows=rows: fd.batch_inv(f, x.reshape(rows, -1, f.limbs), axis=0),
    )

# 3. the narrow-window ladder build (still the w<=8 production path)
gd._fixed_table_dev_cached.cache_clear()
timed("fixed_base_table_dev window=8 (ladder)",
      lambda: gd.fixed_base_table_dev(cs, g, 8))

# 4. LAST: the 1M-lane ladder ramp — the component that stalled the
#    round-4 profile; kept to measure where the old build broke.
for lanes in (1 << 10, 1 << 14, 1 << 17, 1 << 20):
    k = jnp.arange(lanes, dtype=jnp.uint32) & jnp.uint32(0xFFFF)
    p = jnp.broadcast_to(g_dev, (lanes, cs.ncoords, f.limbs))
    timed(f"scalar_mul_small lanes={lanes}", lambda k=k, p=p: gd.scalar_mul_small(cs, k, p, 16))

print("diag done", flush=True)
