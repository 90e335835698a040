"""Component-level timing of the bench workload (deal + verify_batch)
at n=1024 t=341 secp256k1 on the real chip.  Coarse (seconds-scale):
each stage ends in block_until_ready.

Usage:  python scripts/profile_verify.py [N] (from the repo root, on a
machine with the chip).  Feature flags come from the environment exactly as
in production (DKG_TPU_PALLAS / DKG_TPU_MXU), so
one run per flag set isolates a regression:

    python scripts/profile_verify.py 256                     # defaults
    DKG_TPU_PALLAS=0 python scripts/profile_verify.py 256    # no fused kernels
    DKG_TPU_PALLAS=0 DKG_TPU_MXU=0 DKG_TPU_RLC=bits \
        python scripts/profile_verify.py 256                 # round-1 config
    DKG_TPU_RLC=straus|bits  # force the point-RLC schedule independently

Per-stage wall-clocks print AS THEY COMPLETE (flush=True) — if a stage
stalls, the last printed line names the culprit.  Stage list: table
build (g and h), each deal component, the Fiat-Shamir digest, each
verify component.
"""
from __future__ import annotations

import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

from dkg_tpu.utils import compilecache

compilecache.enable()

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.groups import device as gd

N, T = int(sys.argv[1]) if len(sys.argv) > 1 else 1024, None
T = (N - 1) // 3

_sync = jax.block_until_ready

print(
    f"flags: PALLAS={os.environ.get('DKG_TPU_PALLAS', '<default>')} "
    f"MXU={os.environ.get('DKG_TPU_MXU', '<default>')}",
    flush=True,
)

_t0 = time.perf_counter()
c = ce.BatchedCeremony("secp256k1", N, T, b"bench", random.Random(7))
_sync(c.h_table)
print(f"{'setup: tables+coeffs':26s} {time.perf_counter()-_t0:8.3f} s", flush=True)
cfg = c.cfg
cs = cfg.cs
fs = cs.scalar


def timed(name, fn, *args):
    t0 = time.perf_counter()
    out = fn(*args)
    _sync(out)  # cold: compile + first run
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = fn(*args)
    _sync(out)
    print(
        f"{name:26s} {time.perf_counter()-t0:8.3f} s   (cold {cold:7.2f} s)",
        flush=True,
    )
    return out


print(f"n={N} t={T} curve=secp256k1 platform={jax.devices()[0].platform}", flush=True)

# --- deal components -------------------------------------------------------
fb = jax.jit(lambda tab, k: gd.fixed_base_mul(cs, tab, k))
a_pub = timed("deal: fixed_base g (n,t+1)", fb, c.g_table, c.coeffs_a)
b_hid = timed("deal: fixed_base h (n,t+1)", fb, c.h_table, c.coeffs_b)
e_comm = timed("deal: point add", jax.jit(lambda p, q: gd.add(cs, p, q)), a_pub, b_hid)

from dkg_tpu.poly import device as pdev

xs = jnp.arange(1, cfg.n + 1, dtype=jnp.uint32)
xs_limbs = jnp.zeros((cfg.n, fs.limbs), jnp.uint32).at[:, 0].set(xs)
shares = timed(
    "deal: eval_many (n,n)",
    jax.jit(lambda co, x: pdev.eval_many(fs, co, x)),
    c.coeffs_a,
    xs_limbs,
)
hidings = timed(
    "deal: eval_many 2", jax.jit(lambda co, x: pdev.eval_many(fs, co, x)), c.coeffs_b, xs_limbs
)

# --- verify components -----------------------------------------------------
rho_bits = 128
_t0 = time.perf_counter()
rho = jnp.asarray(ce.derive_rho(cfg, a_pub, e_comm, shares, hidings, rho_bits))
print(f"{'fiat-shamir: derive_rho':26s} {time.perf_counter()-_t0:8.3f} s", flush=True)

s_rlc = timed(
    "verify: field_dot s", jax.jit(lambda w, v: ce._field_dot(fs, w, v)), rho, shares
)
r_rlc = timed(
    "verify: field_dot r", jax.jit(lambda w, v: ce._field_dot(fs, w, v)), rho, hidings
)
d_comm = timed(
    "verify: point_rlc (128b)",
    jax.jit(lambda w, p: ce._point_rlc(cs, w, p, rho_bits)),
    rho,
    e_comm,
)
rhs = timed(
    "verify: eval_point_poly",
    jax.jit(lambda d: gd.eval_point_poly(cs, d, xs, cfg.index_bits)),
    d_comm,
)
lhs = timed(
    "verify: 2 fixed_base (n,)",
    jax.jit(
        lambda s_, r_: gd.add(
            cs, gd.fixed_base_mul(cs, c.g_table, s_), gd.fixed_base_mul(cs, c.h_table, r_)
        )
    ),
    s_rlc,
    r_rlc,
)
ok = timed("verify: eq", jax.jit(lambda p, q: gd.eq(cs, p, q)), lhs, rhs)
print("all ok:", bool(jnp.all(ok)), flush=True)
