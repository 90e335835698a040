"""Batched elliptic-curve arithmetic on limb arrays (JAX / XLA, TPU-first).

Points are ``uint32`` arrays of shape ``(..., C, L)`` — C projective
coordinates of L 16-bit limbs — batched over the leading axes.  All
formulas are **complete/unified** so every op is branchless: adding the
identity, adding equal points, and doubling all flow through the same
code path.  That is the TPU-native answer to the reference's per-point
CPU arithmetic (reference: src/groups.rs:55-90 delegating to
curve25519-dalek; MSM seam at src/traits.rs:234-237):

* Edwards (ristretto255): extended coordinates (X,Y,Z,T), a=-1, unified
  add (Hisil-Wong-Carter-Dawson 2008, complete for d non-square) +
  dedicated doubling.
* Short Weierstrass a=0 (secp256k1, BLS12-381 G1): projective (X,Y,Z)
  complete formulas (Renes-Costello-Batina 2015, algorithms 7 & 9).

Hot-op inventory (what the DKG protocol needs, SURVEY §2 table):

* ``scalar_mul``       — batched variable-base (KEM, public shares)
* ``fixed_base_mul``   — batched g/h multiples via host-precomputed
                         window tables (coefficient commitments, KEM c1)
* ``msm``              — batched Straus shared-doubling multi-scalar
                         multiplication (share verification, the §6
                         north-star workload)
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..fields import device as fd
from ..fields import host as fh
from ..fields.spec import FieldSpec
from . import host as gh

WINDOW = 4  # window bits for scalar decomposition (16-entry tables)

# Shared lazy dispatch switch: default ON on a real TPU backend,
# DKG_TPU_PALLAS=1/0 forces either way (see fields/device.py).
fused_kernels_active = fd.fused_kernels_active


def msm_mode() -> str:
    """The MSM kernel :func:`msm` dispatches: ``DKG_TPU_MSM`` where set
    (validated), else Straus with the multi-op kernels and the bucket
    method without them."""
    from ..utils import envknobs

    mode = envknobs.choice(
        "DKG_TPU_MSM",
        ("straus", "pippenger"),
        "MSM kernel: bucket method vs shared-doubling reference",
    )
    return mode or ("straus" if fused_kernels_active() else "pippenger")


def point_kernel_tier() -> dict[str, str]:
    """What this process's selectors choose now, as labels:
    ``tier`` (``fused``: the window step and the Horner ladder are one
    multi-op kernel launch each, on every curve since PR 42, PERF.md
    section 6; ``composed``: XLA operations) and the ``msm`` kernel.
    ``service.engine.WarmRuntime.commitment`` books it as the gauge
    ``point_kernel_tier{curve,tier,msm}``: the trace-time counters are
    zero in a process that loads its programs."""
    return {"tier": "fused" if fused_kernels_active() else "composed", "msm": msm_mode()}


def _jit_static0(fn):
    """jit with the CurveSpec (hashable, frozen) as a static argument."""
    return jax.jit(fn, static_argnums=0)


@dataclasses.dataclass(frozen=True)
class CurveSpec:
    """Device-side curve description.  Hashable (ints/str only) so it can
    be a static jit argument; limb constants are materialised lazily."""

    name: str
    kind: str  # "edwards" | "weierstrass_a0"
    field: FieldSpec
    scalar: FieldSpec
    const: int  # 2d (edwards) or 3b (weierstrass_a0)
    gen_affine: tuple  # (x, y) ints

    @property
    def ncoords(self) -> int:
        return 4 if self.kind == "edwards" else 3


RISTRETTO255 = CurveSpec(
    "ristretto255",
    "edwards",
    gh.RISTRETTO255.base_field,
    gh.RISTRETTO255.scalar_field,
    2 * gh.D % gh.P,
    (gh._BASE_X, gh._BASE_Y),
)

SECP256K1 = CurveSpec(
    "secp256k1",
    "weierstrass_a0",
    gh.SECP256K1.base_field,
    gh.SECP256K1.scalar_field,
    21,
    (gh.SECP256K1.gen_x, gh.SECP256K1.gen_y),
)

BLS12_381_G1 = CurveSpec(
    "bls12_381_g1",
    "weierstrass_a0",
    gh.BLS12_381_G1.base_field,
    gh.BLS12_381_G1.scalar_field,
    12,
    (gh.BLS12_381_G1.gen_x, gh.BLS12_381_G1.gen_y),
)

ALL_CURVES = {c.name: c for c in (RISTRETTO255, SECP256K1, BLS12_381_G1)}


# ---------------------------------------------------------------------------
# host <-> device conversion
# ---------------------------------------------------------------------------


def identity(cs: CurveSpec, batch: tuple = ()) -> jax.Array:
    if cs.kind == "edwards":
        coords = [0, 1, 1, 0]
    else:
        coords = [0, 1, 0]
    pt = np.stack([fh.encode(cs.field, c) for c in coords])
    return jnp.broadcast_to(jnp.asarray(pt), batch + (cs.ncoords, cs.field.limbs))


def generator(cs: CurveSpec, batch: tuple = ()) -> jax.Array:
    return from_host(cs, [_gen_host(cs)] )[0] if batch == () else jnp.broadcast_to(
        from_host(cs, [_gen_host(cs)])[0], batch + (cs.ncoords, cs.field.limbs)
    )


def _gen_host(cs: CurveSpec):
    x, y = cs.gen_affine
    if cs.kind == "edwards":
        return (x, y, 1, x * y % cs.field.modulus)
    return (x, y, 1)


def from_host(cs: CurveSpec, points) -> jax.Array:
    """List/array of host point tuples -> device limb array (n, C, L)."""
    arr = np.asarray(
        [[int(c) for c in p] for p in points], dtype=object
    )  # (n, C) ints
    return jnp.asarray(fh.encode(cs.field, arr))


def to_host(cs: CurveSpec, pts: jax.Array) -> list:
    """Device limb array (n, C, L) -> list of host point tuples."""
    dec = fh.decode(cs.field, np.asarray(pts))  # (n, C) object ints
    return [tuple(int(c) for c in row) for row in dec]


# ---------------------------------------------------------------------------
# point addition / doubling / negation (complete & branchless)
# ---------------------------------------------------------------------------


def add(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    if fused_kernels_active():
        from ..ops import pallas_point

        return pallas_point.pt_add(cs, p, q)
    return _add_xla(cs, p, q)


@_jit_static0
def _add_xla(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    if cs.kind == "edwards":
        return _ed_add(cs, p, q)
    return _ws_add(cs, p, q)


def double(cs: CurveSpec, p: jax.Array) -> jax.Array:
    if fused_kernels_active():
        from ..ops import pallas_point

        return pallas_point.pt_double(cs, p)
    return _double_xla(cs, p)


@_jit_static0
def _double_xla(cs: CurveSpec, p: jax.Array) -> jax.Array:
    if cs.kind == "edwards":
        return _ed_double(cs, p)
    return _ws_double(cs, p)


@_jit_static0
def neg(cs: CurveSpec, p: jax.Array) -> jax.Array:
    f = cs.field
    if cs.kind == "edwards":
        x, y, z, t = _unstack(p, 4)
        return _stack(fd.neg(f, x), y, z, fd.neg(f, t))
    x, y, z = _unstack(p, 3)
    return _stack(x, fd.neg(f, y), z)


def _unstack(p: jax.Array, n: int):
    return tuple(p[..., i, :] for i in range(n))


def _stack(*coords) -> jax.Array:
    return jnp.stack(jnp.broadcast_arrays(*coords), axis=-2)


def _ed_add(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """Unified extended twisted Edwards addition, a=-1 (add-2008-hwcd-3).

    Complete for ristretto255 (d non-square), so it doubles and handles
    the identity with no branches — exactly what a batched lane wants.
    """
    f = cs.field
    x1, y1, z1, t1 = _unstack(p, 4)
    x2, y2, z2, t2 = _unstack(q, 4)
    a = fd.mul(f, fd.sub(f, y1, x1), fd.sub(f, y2, x2))
    b = fd.mul(f, fd.add(f, y1, x1), fd.add(f, y2, x2))
    c = fd.mul(f, fd.mul(f, t1, fd.constant(f, cs.const)), t2)
    d = fd.mul(f, fd.add(f, z1, z1), z2)
    e = fd.sub(f, b, a)
    ff = fd.sub(f, d, c)
    g = fd.add(f, d, c)
    h = fd.add(f, b, a)
    return _stack(
        fd.mul(f, e, ff), fd.mul(f, g, h), fd.mul(f, ff, g), fd.mul(f, e, h)
    )


def _ed_double(cs: CurveSpec, p: jax.Array) -> jax.Array:
    """Dedicated doubling (dbl-2008-hwcd), valid for all inputs."""
    f = cs.field
    x1, y1, z1, _ = _unstack(p, 4)
    a = fd.square(f, x1)
    b = fd.square(f, y1)
    zz = fd.square(f, z1)
    c = fd.add(f, zz, zz)
    d = fd.neg(f, a)  # a = -1
    e = fd.sub(f, fd.sub(f, fd.square(f, fd.add(f, x1, y1)), a), b)
    g = fd.add(f, d, b)
    h = fd.sub(f, d, b)
    ff = fd.sub(f, g, c)
    return _stack(
        fd.mul(f, e, ff), fd.mul(f, g, h), fd.mul(f, ff, g), fd.mul(f, e, h)
    )


def _ws_add(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """Complete projective addition for y^2=x^3+b (RCB15 algorithm 7)."""
    f = cs.field
    b3 = fd.constant(f, cs.const)
    x1, y1, z1 = _unstack(p, 3)
    x2, y2, z2 = _unstack(q, 3)
    t0 = fd.mul(f, x1, x2)
    t1 = fd.mul(f, y1, y2)
    t2 = fd.mul(f, z1, z2)
    t3 = fd.mul(f, fd.add(f, x1, y1), fd.add(f, x2, y2))
    t3 = fd.sub(f, fd.sub(f, t3, t0), t1)
    t4 = fd.mul(f, fd.add(f, y1, z1), fd.add(f, y2, z2))
    t4 = fd.sub(f, fd.sub(f, t4, t1), t2)
    xz = fd.mul(f, fd.add(f, x1, z1), fd.add(f, x2, z2))
    y3 = fd.sub(f, fd.sub(f, xz, t0), t2)
    x3 = fd.add(f, fd.add(f, t0, t0), t0)
    t2 = fd.mul(f, b3, t2)
    z3 = fd.add(f, t1, t2)
    t1 = fd.sub(f, t1, t2)
    y3 = fd.mul(f, b3, y3)
    x_out = fd.sub(f, fd.mul(f, t3, t1), fd.mul(f, t4, y3))
    y_out = fd.add(f, fd.mul(f, t1, z3), fd.mul(f, x3, y3))
    z_out = fd.add(f, fd.mul(f, z3, t4), fd.mul(f, x3, t3))
    return _stack(x_out, y_out, z_out)


def _ws_double(cs: CurveSpec, p: jax.Array) -> jax.Array:
    """Complete doubling for y^2=x^3+b (RCB15 algorithm 9)."""
    f = cs.field
    b3 = fd.constant(f, cs.const)
    x, y, z = _unstack(p, 3)
    t0 = fd.square(f, y)
    z3 = fd.add(f, t0, t0)
    z3 = fd.add(f, z3, z3)
    z3 = fd.add(f, z3, z3)
    t1 = fd.mul(f, y, z)
    t2 = fd.mul(f, b3, fd.square(f, z))
    x3 = fd.mul(f, t2, z3)
    y3 = fd.add(f, t0, t2)
    z3 = fd.mul(f, t1, z3)
    t1 = fd.add(f, t2, t2)
    t2 = fd.add(f, t1, t2)
    t0 = fd.sub(f, t0, t2)
    y3 = fd.add(f, x3, fd.mul(f, t0, y3))
    x3 = fd.mul(f, t0, fd.mul(f, x, y))
    x3 = fd.add(f, x3, x3)
    return _stack(x3, y3, z3)


def _ed_madd(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """Mixed unified Edwards add: q affine (Z2 == 1, T2 = X2*Y2).

    add-2008-hwcd-3 with the D = 2*Z1*Z2 multiply specialised away —
    8 muls instead of 9.  Still unified/complete (the affine identity
    (0, 1, 1, 0) flows through like any point)."""
    f = cs.field
    x1, y1, z1, t1 = _unstack(p, 4)
    x2, y2, _, t2 = _unstack(q, 4)
    a = fd.mul(f, fd.sub(f, y1, x1), fd.sub(f, y2, x2))
    b = fd.mul(f, fd.add(f, y1, x1), fd.add(f, y2, x2))
    c = fd.mul(f, fd.mul(f, t1, fd.constant(f, cs.const)), t2)
    d = fd.add(f, z1, z1)  # 2*Z1*Z2 with Z2 = 1
    e = fd.sub(f, b, a)
    ff = fd.sub(f, d, c)
    g = fd.add(f, d, c)
    h = fd.add(f, b, a)
    return _stack(
        fd.mul(f, e, ff), fd.mul(f, g, h), fd.mul(f, ff, g), fd.mul(f, e, h)
    )


def _ws_madd(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """Mixed addition for y^2 = x^3 + b: q affine (RCB15 algorithm 8).

    11 muls vs algorithm 7's 12 (T2 = Z1*Z2 becomes Z1; the (Y1+Z1)
    (Y2+Z2) and (X1+Z1)(X2+Z2) cross terms collapse to Y2*Z1 + Y1 and
    X2*Z1 + X1).  Complete for every P INCLUDING the identity, but NOT
    for q = identity (Z2 would be 0, not 1) — callers must mask
    zero-digit table entries (see _fixed_base_mul_core)."""
    f = cs.field
    b3 = fd.constant(f, cs.const)
    x1, y1, z1 = _unstack(p, 3)
    x2, y2, _ = _unstack(q, 3)
    t0 = fd.mul(f, x1, x2)
    t1 = fd.mul(f, y1, y2)
    t3 = fd.mul(f, fd.add(f, x1, y1), fd.add(f, x2, y2))
    t3 = fd.sub(f, fd.sub(f, t3, t0), t1)
    t4 = fd.add(f, fd.mul(f, y2, z1), y1)
    y3 = fd.add(f, fd.mul(f, x2, z1), x1)
    x3 = fd.add(f, fd.add(f, t0, t0), t0)
    t2 = fd.mul(f, b3, z1)
    z3 = fd.add(f, t1, t2)
    t1 = fd.sub(f, t1, t2)
    y3 = fd.mul(f, b3, y3)
    x_out = fd.sub(f, fd.mul(f, t3, t1), fd.mul(f, t4, y3))
    y_out = fd.add(f, fd.mul(f, t1, z3), fd.mul(f, x3, y3))
    z_out = fd.add(f, fd.mul(f, z3, t4), fd.mul(f, x3, t3))
    return _stack(x_out, y_out, z_out)


@_jit_static0
def _madd_xla(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    if cs.kind == "edwards":
        return _ed_madd(cs, p, q)
    return _ws_madd(cs, p, q)


def madd(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """p + q with q affine-normalised (Z = 1) — one mul cheaper than the
    general add.  Weierstrass callers must not pass q = identity."""
    if fused_kernels_active():
        from ..ops import pallas_point

        return pallas_point.pt_madd(cs, p, q)
    return _madd_xla(cs, p, q)


@_jit_static0
def eq(cs: CurveSpec, p: jax.Array, q: jax.Array) -> jax.Array:
    """Batched projective equality -> bool array over the batch shape.

    Edwards path is torsion-safe ristretto equality (X1Y2==Y1X2 or
    Y1Y2==X1X2 — RFC 9496 §4.3.3; Z's cancel).  Weierstrass path is
    cross-multiplied affine equality, identity-correct.
    """
    f = cs.field
    if cs.kind == "edwards":
        x1, y1, _, _ = _unstack(p, 4)
        x2, y2, _, _ = _unstack(q, 4)
        lhs = fd.eq(fd.mul(f, x1, y2), fd.mul(f, y1, x2))
        rhs = fd.eq(fd.mul(f, y1, y2), fd.mul(f, x1, x2))
        return lhs | rhs
    x1, y1, z1 = _unstack(p, 3)
    x2, y2, z2 = _unstack(q, 3)
    ex = fd.eq(fd.mul(f, x1, z2), fd.mul(f, x2, z1))
    ey = fd.eq(fd.mul(f, y1, z2), fd.mul(f, y2, z1))
    return ex & ey


def select(pred: jax.Array, p: jax.Array, q: jax.Array) -> jax.Array:
    """Branchless point select; pred shape == batch shape."""
    return jnp.where(pred[..., None, None], p, q)


# ---------------------------------------------------------------------------
# scalar decomposition
# ---------------------------------------------------------------------------


def scalar_windows(cs: CurveSpec, k: jax.Array, window: int = WINDOW) -> jax.Array:
    """(..., L) scalar limbs -> (..., NW) window-bit digits, little-endian.

    ``window`` must divide 16 (the limb width): 4 for per-lane tables
    (variable base), 8 for host-precomputed fixed-base tables, 16 for
    the device-built fixed-base tables (one digit per limb).
    """
    shifts = jnp.arange(0, 16, window, dtype=jnp.uint32)
    digits = (k[..., :, None] >> shifts) & jnp.uint32((1 << window) - 1)
    return digits.reshape(k.shape[:-1] + (k.shape[-1] * (16 // window),))


FIXED_WINDOW = 8  # fixed-base tables: 256-entry windows, half the adds


def _n_windows(cs: CurveSpec, window: int = WINDOW) -> int:
    return cs.scalar.limbs * (16 // window)


# ---------------------------------------------------------------------------
# variable-base scalar multiplication (batched)
# ---------------------------------------------------------------------------


def _build_table(cs: CurveSpec, p: jax.Array) -> jax.Array:
    """Per-lane window table [0P, 1P, ..., 15P]: (..., 16, C, L).

    Built with a scan (one traced add body, not 14 inlined copies) to
    keep the compile surface small — this sits inside every scalar-mul
    / MSM / point-RLC jit.
    """

    def step(prev, _):
        nxt = add(cs, prev, p)
        return nxt, nxt

    _, rest = lax.scan(step, p, None, length=14)  # (14, ..., C, L)
    rest = jnp.moveaxis(rest, 0, -3)
    ident = identity(cs, p.shape[:-2])
    return jnp.concatenate(
        [ident[..., None, :, :], p[..., None, :, :], rest], axis=-3
    )


def _gather_table(table: jax.Array, digit: jax.Array) -> jax.Array:
    """Gather window entries: table (..., 16, C, L) [batch-matched] or
    (16, C, L) [shared], digit (...,) -> (..., C, L)."""
    if table.ndim == 3:  # shared table: plain advanced-index gather
        return table[digit.astype(jnp.int32)]
    idx = digit.astype(jnp.int32)[..., None, None, None]
    return jnp.take_along_axis(table, idx, axis=-3)[..., 0, :, :]


def _canon_batch(n: int) -> int:
    """Pad a flattened batch to the next power of two.

    The ladder kernels compile slowly (hundreds of limb-mul steps in the
    scan body); bucketing eager-call batch shapes to powers of two means
    one compile per size class instead of one per distinct (n_d, n_r,
    ...) combination.  Padding lanes carry k=0 / identity and are
    dropped on return.
    """
    return 1 << (max(n, 1) - 1).bit_length()


def scalar_mul(cs: CurveSpec, k: jax.Array, p: jax.Array) -> jax.Array:
    """Batched k·P: k (..., L) scalar limbs, p (..., C, L) points.

    Eager calls are flattened + power-of-two padded (see _canon_batch);
    traced calls inline into the caller's graph untouched.
    """
    if isinstance(k, jax.core.Tracer) or isinstance(p, jax.core.Tracer):
        return _scalar_mul_core(cs, k, p)
    batch = k.shape[:-1]
    if p.shape[:-2] != batch:
        p = jnp.broadcast_to(p, batch + p.shape[-2:])
    n = 1
    for d in batch:
        n *= int(d)
    m = _canon_batch(n)
    kf = jnp.reshape(k, (n, k.shape[-1]))
    pf = jnp.reshape(p, (n,) + p.shape[-2:])
    if m != n:
        kf = jnp.concatenate([kf, jnp.zeros((m - n,) + kf.shape[1:], kf.dtype)])
        pad_pt = jnp.broadcast_to(identity(cs, (m - n,)), (m - n,) + pf.shape[1:])
        pf = jnp.concatenate([pf, pad_pt.astype(pf.dtype)])
    out = _scalar_mul_core(cs, kf, pf)
    return jnp.reshape(out[:n], batch + out.shape[-2:])


@_jit_static0
def _scalar_mul_core(cs: CurveSpec, k: jax.Array, p: jax.Array) -> jax.Array:
    """Fixed-window MSB-first double-and-add via lax.scan: no
    data-dependent control flow (digit-0 adds the identity through the
    complete formulas).  Replaces the reference's per-point dalek scalar
    mult (reference: src/groups.rs:70-76) with one wide batched op.

    When the fused kernels are active (default on TPU), the scan body's
    4-double+add window collapses into ONE fused Pallas kernel launch
    (ops.pallas_point.pt_window_step) — intermediates never touch HBM.
    """
    table = _build_table(cs, p)
    digits = scalar_windows(cs, k)  # (..., NW)
    digits_rev = jnp.moveaxis(digits, -1, 0)[::-1]  # MSB first
    fused = fused_kernels_active()

    def step(acc, dig):
        entry = _gather_table(table, dig)
        return window_step(cs, acc, entry, WINDOW, fused), None

    init = identity(cs, p.shape[:-2])
    acc, _ = lax.scan(step, init, digits_rev)
    return acc


# ---------------------------------------------------------------------------
# fixed-base multiplication via host-precomputed tables
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=32)
def _fixed_table_np(cs: CurveSpec, base_key: tuple, window: int = FIXED_WINDOW) -> np.ndarray:
    """Host-computed window table for a fixed base: (NW, 2**window, C, L).

    T[w][d] = d · (2**window)^w · B.  Stored affine-normalised (Z=1) so
    gathered entries are cheap to add.  Cached per (curve, base, window).
    8-bit windows halve the device adds vs 4-bit at 2 MB/base of table —
    a clear trade on TPU where the gather is cheap and HBM is plentiful.
    """
    host_group = gh.ALL_GROUPS[cs.name]
    base = base_key_to_point(cs, base_key)
    nw = _n_windows(cs, window)
    entries = 1 << window
    out = np.zeros((nw, entries, cs.ncoords, cs.field.limbs), dtype=np.uint32)
    window_base = base
    for w in range(nw):
        acc = host_group.identity()
        for d in range(entries):
            out[w, d] = _affine_limbs(cs, host_group, acc)
            acc = host_group.add(acc, window_base)
        for _ in range(window):
            window_base = host_group.add(window_base, window_base)
    return out


def base_key(cs: CurveSpec, point) -> tuple:
    """Hashable key for a host point (affine-normalised)."""
    host_group = gh.ALL_GROUPS[cs.name]
    if cs.kind == "edwards":
        x, y, z, _ = point
        zi = pow(z, cs.field.modulus - 2, cs.field.modulus)
        return (x * zi % cs.field.modulus, y * zi % cs.field.modulus)
    aff = host_group.to_affine(point)
    return aff if aff is not None else ("identity",)


def base_key_to_point(cs: CurveSpec, key: tuple):
    if key == ("identity",):
        return gh.ALL_GROUPS[cs.name].identity()
    x, y = key
    if cs.kind == "edwards":
        return (x, y, 1, x * y % cs.field.modulus)
    return (x, y, 1)


def _affine_limbs(cs: CurveSpec, host_group, p) -> np.ndarray:
    """Host point -> affine-normalised (C, L) limb array (identity kept
    projective: Edwards (0,1,1,0) is already affine; Weierstrass (0,1,0))."""
    pm = cs.field.modulus
    if cs.kind == "edwards":
        x, y, z, _ = p
        zi = pow(z, pm - 2, pm)
        xa, ya = x * zi % pm, y * zi % pm
        coords = (xa, ya, 1, xa * ya % pm)
    else:
        aff = host_group.to_affine(p)
        coords = (0, 1, 0) if aff is None else (aff[0], aff[1], 1)
    return fh.encode(cs.field, list(coords))


def default_fixed_window() -> int:
    """Backend-matched fixed-base window width, the one owner of the
    rule: 16 bits on TPU (device-composed tables), ``FIXED_WINDOW``
    elsewhere (host-built).  Read at trace time by
    :func:`fixed_base_table` and ``groups.precompute.base_table``."""
    return 16 if fd._on_tpu() else FIXED_WINDOW


def fixed_base_table(cs: CurveSpec, base) -> jax.Array:
    """Device window table for a fixed base point.

    Backend-matched window width (:func:`default_fixed_window`): on TPU
    the table is DEVICE-BUILT with 16-bit windows — 16 mixed adds per
    256-bit scalar instead of 32, for ~200 MB of HBM per base (a clear
    trade: the commitment phase is add-bound, HBM is plentiful, and the
    build is one batched ladder call amortised over the whole
    ceremony).  Elsewhere the 8-bit host-built table.
    """
    window = default_fixed_window()
    if window == FIXED_WINDOW:
        return jnp.asarray(_fixed_table_np(cs, base_key(cs, base)))
    return fixed_base_table_dev(cs, base, window)


def fixed_base_table_dev(cs: CurveSpec, base, window: int = 16) -> jax.Array:
    """Device-built affine window table: (NW, 2**window, C, L).

    T[w][d] = d * (2**window)^w * B, affine-normalised (Z = 1) like the
    host table, with the same identity convention for entry 0 (Edwards
    (0,1,1,0) — genuinely affine; Weierstrass (0,1,0) — masked by the
    digit-0 select in _fixed_base_mul_core).  Narrow windows (<= 8 bits)
    build as one batched ladder per window base; wide windows COMPOSE
    two half-width host-table entries with one batched add (see
    _compose_table_dev).  Both end in a single Montgomery-trick
    inversion over all entries; cached per (curve, base, window).
    """
    return _fixed_table_dev_cached(cs, base_key(cs, base), window)


@functools.lru_cache(maxsize=8)
def _fixed_table_dev_cached(cs: CurveSpec, key: tuple, window: int) -> jax.Array:
    f = cs.field
    if window > 8:
        half = window // 2
        if window % 2 or half > 8 or 16 % window:
            raise ValueError(f"unsupported fixed-base window width {window}")
        t_half = jnp.asarray(_fixed_table_np(cs, key, half))
        return affine_canon(cs, _compose_table_dev(cs, t_half, window))
    host_group = gh.ALL_GROUPS[cs.name]
    base = base_key_to_point(cs, key)
    nw = _n_windows(cs, window)
    entries = 1 << window
    # window bases (2**window)^w * B: nw public host scalar-mults
    bases = []
    pt = base
    for _ in range(nw):
        bases.append(pt)
        for _ in range(window):
            pt = host_group.add(pt, pt)
    bases_dev = from_host(cs, bases)  # (nw, C, L)
    digits = jnp.broadcast_to(
        jnp.arange(entries, dtype=jnp.uint32)[None, :], (nw, entries)
    )
    pts = scalar_mul_small(
        cs, digits, jnp.broadcast_to(bases_dev[:, None], (nw, entries, cs.ncoords, f.limbs)),
        window,
    )  # (nw, entries, C, L) projective
    return affine_canon(cs, pts)


def _compose_table_dev(cs: CurveSpec, t_half: jax.Array, window: int) -> jax.Array:
    """Wide-window table entries by COMPOSITION, not a device ladder.

    With the cheap host-built half-width table T[v][e] = e·(2**h)^v·B
    (h = window/2, shape (2·nw, 2**h, C, L), passed in so callers can
    source it from the persistent cache — groups/precompute.py), every
    wide entry d = lo + 2**h·hi is ``T[2w][lo] + T[2w+1][hi]`` — ONE
    complete point add per entry.  The previous 16-step 1M-lane ladder
    build stalled the round-4 TPU bench inside a single giant remote
    compile; this build is one small host table + one batched add
    (+ the shared batched inversion), so the device graphs stay
    compile-light.  Identity lanes flow through the complete formulas
    (identity entries are stored projectively).
    """
    f = cs.field
    lo = t_half[0::2][:, None, :, :, :]  # (nw, 1,  2**half, C, L)
    hi = t_half[1::2][:, :, None, :, :]  # (nw, 2**half, 1,  C, L)
    pts = add(cs, lo, hi)  # (nw, 2**half, 2**half, C, L); d = hi·2**half + lo
    nw = _n_windows(cs, window)
    return pts.reshape(nw, 1 << window, cs.ncoords, f.limbs)


def fixed_base_mul(cs: CurveSpec, table: jax.Array, k: jax.Array) -> jax.Array:
    """Batched k·B for fixed B: table (NW, 2**w, C, L), k (..., L).

    The window width w (4/8/16) is encoded in the table's entry count;
    NW = 256/w windows of one gathered MIXED add each, no doublings —
    the workhorse for coefficient commitments g·a + h·b (reference hot
    loop committee.rs:151-159) and KEM first components g·r (reference:
    elgamal.rs:138-142).  Eager calls are flattened + power-of-two
    padded (see _canon_batch).  With the fused kernels active the
    windows run in the point kernels' lane-block form (see
    :func:`_fixed_base_mul_core`); the observation is part of the
    jitted program's key, so a process that flips ``DKG_TPU_PALLAS``
    never answers one form from the other's trace.
    """
    blocks = fused_kernels_active()
    if isinstance(k, jax.core.Tracer) or isinstance(table, jax.core.Tracer):
        return _fixed_base_mul_core(cs, blocks, table, k)
    batch = k.shape[:-1]
    n = 1
    for d in batch:
        n *= int(d)
    m = _canon_batch(n)
    kf = jnp.reshape(k, (n, k.shape[-1]))
    if m != n:
        kf = jnp.concatenate([kf, jnp.zeros((m - n,) + kf.shape[1:], kf.dtype)])
    out = _fixed_base_mul_core(cs, blocks, table, kf)
    return jnp.reshape(out[:n], batch + out.shape[-2:])


@functools.partial(jax.jit, static_argnums=(0, 1))
def _fixed_base_mul_core(cs: CurveSpec, blocks: bool, table: jax.Array, k: jax.Array) -> jax.Array:
    """One gathered mixed add a window, in one of two forms, same group
    element limb for limb:

    * ``blocks`` (the fused kernels are active): the accumulator is
      (nb, C·L, BLOCK) lane blocks (``ops.pallas_point``) from the first
      window to the last and is converted once, at the end.  A window's
      entries are gathered as C·L-word ROWS of its table and go rows ->
      blocks, the one layout change of a point-sized array a step; they
      are never written in the ``(..., C, L)`` form, whose minor dims
      the TPU pads to a (4, 128) tile, 2 KiB a lane (717 MB a step at
      350 k lanes, read back once for the entry and once for its Z).
      Lanes past the batch take digit 0, the identity entry, and stay
      the identity.
    * tensor form elsewhere: ``(..., C, L)`` through ``madd``.

    Table entries are affine-normalised (Z = 1), so each window is a
    mixed add.  Weierstrass identity entries are NOT affine — they are
    stored (0, 1, 0) — so the step keeps the old accumulator where the
    gathered entry's Z = 0 (covers both the digit-0 entry and every
    entry of an identity-base table); the Edwards identity (0, 1, 1, 0)
    is affine and flows through the unified madd.  Each traced body
    books ``fixed_base_traced_total{form, window}``.
    """
    from ..utils import metrics  # utils imports dkg, which imports this module

    # window width is encoded in the table's entry count (16 -> 4-bit,
    # 256 -> 8-bit, 65536 -> 16-bit); all divide the 16-bit limb width.
    window = int(table.shape[1]).bit_length() - 1
    metrics.REGISTRY.inc(
        "fixed_base_traced_total", form="blocks" if blocks else "tensor", window=str(window)
    )
    digits = scalar_windows(cs, k, window)  # (..., NW)
    if blocks:
        return _fixed_base_blocks(cs, table, digits)
    sel = jnp.moveaxis(digits, -1, 0)  # (NW, ...)

    def step(acc, args):
        tab_w, dig = args  # (2**window, C, L), (...)
        entry = _gather_table(tab_w, dig)
        nxt = madd(cs, acc, entry)
        if cs.kind != "edwards":
            nxt = select(~fd.is_zero(entry[..., 2, :]), nxt, acc)
        return nxt, None

    init = identity(cs, k.shape[:-1])
    acc, _ = lax.scan(step, init, (table, sel))
    return acc


def _fixed_base_blocks(cs: CurveSpec, table: jax.Array, digits: jax.Array) -> jax.Array:
    """The window loop of :func:`_fixed_base_mul_core` on lane blocks:
    table (NW, 2**w, C, L), digits (..., NW) -> (..., C, L)."""
    from ..ops import pallas_point as pp

    L, C = cs.field.limbs, cs.ncoords
    batch = digits.shape[:-1]
    n = int(np.prod(batch, dtype=np.int64))
    nb = max(1, -(-n // pp.BLOCK))
    sel = jnp.moveaxis(jnp.reshape(digits, (n, -1)), -1, 0)
    sel = jnp.pad(sel, ((0, 0), (0, nb * pp.BLOCK - n)))  # (NW, lanes), unsigned and < 2**window

    def step(acc_t, args):
        tab_w, dig = args  # (2**window, C, L), (lanes,)
        rows = jnp.reshape(tab_w, (tab_w.shape[0], C * L))
        entry_t = pp.lane_blocks(rows.at[dig].get(mode="promise_in_bounds"))
        nxt = pp.madd_tiles(cs, acc_t, entry_t)
        if cs.kind != "edwards":
            live = jnp.any(entry_t[:, 2 * L : 3 * L, :] != 0, axis=1, keepdims=True)  # (nb, 1, BLOCK)
            nxt = jnp.where(live, nxt, acc_t)
        return nxt, None

    acc_t, _ = lax.scan(step, pp.identity_tiles(cs, nb), (table, sel))
    return pp.from_tiles(cs, acc_t, batch, n)


@functools.partial(jax.jit, static_argnums=(0, 3))
def scalar_mul_small(cs: CurveSpec, k: jax.Array, p: jax.Array, nbits: int) -> jax.Array:
    """k·P for small public integers k < 2**nbits: k (...,) uint32,
    p (..., C, L) -> (..., C, L).

    Branchless binary ladder, ~2·nbits point-ops — used where scalars are
    party indices (<= n, so ~14 bits), not full field elements.  With
    the fused kernels active the whole ladder is ONE Pallas launch.
    """
    if fused_kernels_active():
        from ..ops import pallas_point

        batch = jnp.broadcast_shapes(jnp.shape(k), p.shape[:-2])
        p = jnp.broadcast_to(p, batch + p.shape[-2:])
        return pallas_point.pt_ladder_mul_add(
            cs, p, identity(cs, batch), k, nbits
        )
    bits = (k.astype(jnp.uint32)[..., None] >> jnp.arange(nbits, dtype=jnp.uint32)) & 1
    bits_rev = jnp.moveaxis(bits, -1, 0)[::-1]  # (nbits, ...) MSB first

    def step(acc, bit):
        acc = _double_xla(cs, acc)
        return select(bit != 0, _add_xla(cs, acc, p), acc), None

    init = identity(cs, p.shape[:-2])
    acc, _ = lax.scan(step, init, bits_rev)
    return acc


@functools.partial(jax.jit, static_argnums=(0, 3))
def eval_point_poly(
    cs: CurveSpec, coeffs: jax.Array, x: jax.Array, nbits: int
) -> jax.Array:
    """Horner evaluation of a point-coefficient polynomial at small public
    x: coeffs (..., T, C, L) low-order-first, x (...,) uint32 -> (..., C, L).

    acc = x·acc + C_l per step — the share-verification RHS
    sum_l x^l E_l (reference: committee.rs:292-296) without any 255-bit
    MSM: for x = party index (<= n), each Horner step costs one
    ~nbits-bit ladder instead of a full-width scalar mult.  This is the
    TPU-native restructuring of the reference's per-pair Pippenger MSM
    (SURVEY §2 table row 3).  With the fused kernels active each Horner
    step (the full ladder + add) is ONE Pallas launch.
    """
    cs_rev = jnp.moveaxis(coeffs, -3, 0)[::-1]  # (T, ..., C, L) high first
    batch = jnp.broadcast_shapes(coeffs.shape[:-3], x.shape)
    if fused_kernels_active():
        from ..ops import pallas_point

        def step_fused(acc, c_l):
            return pallas_point.pt_ladder_mul_add(cs, acc, c_l, x, nbits), None

        init = identity(cs, batch)
        acc, _ = lax.scan(step_fused, init, cs_rev)
        return acc

    bits = (x.astype(jnp.uint32)[..., None] >> jnp.arange(nbits, dtype=jnp.uint32)) & 1
    bits_rev = jnp.moveaxis(bits, -1, 0)[::-1]  # (nbits, ...) MSB first

    def step(acc, c_l):
        # acc <- x*acc via branchless ladder
        mul_acc = identity(cs, acc.shape[:-2])

        def ladder(m, bit):
            m = _double_xla(cs, m)
            return select(bit != 0, _add_xla(cs, m, acc), m), None

        mul_acc, _ = lax.scan(ladder, mul_acc, bits_rev)
        return _add_xla(cs, mul_acc, c_l), None

    init = identity(cs, batch)
    acc, _ = lax.scan(step, init, cs_rev)
    return acc


# ---------------------------------------------------------------------------
# multi-scalar multiplication (batched Straus)
# ---------------------------------------------------------------------------


#: fewest lanes one row of the canonicalisation's Montgomery scan may
#: hold, and the most rows it takes.  A scan step is one DEPENDENT field
#: multiply whatever its width, so a row narrower than the device
#: computes at once buys nothing and costs three serial multiplies: a
#: convoy's 768 and 8 lanes take ONE row (a plain lane-wide inversion,
#: no scan), the million-lane table build its 256.  Swept once on a v5e
#: (PERF.md section 6, PR 26): 1024 is the best of 256 / 1024 / 4096 at
#: 16 k lanes and within 1.2-1.6 x of the best at 2 k, 87 k and 350 k
#: (where 4096 wins); one row at every count loses by 5-13 x from 16 k up.
_CANON_ROW_LANES = 1024
_CANON_MAX_ROWS = 256


def _canon_rows(n_lanes: int) -> int:
    """Montgomery-scan length of :func:`affine_canon` for a lane count."""
    return max(1, min(_CANON_MAX_ROWS, n_lanes // _CANON_ROW_LANES))


def affine_canon(cs: CurveSpec, pts: jax.Array) -> jax.Array:
    """Canonical (affine, Z=1) limb representation of a point batch:
    (..., C, L) -> (..., C, L) with X/Z, Y/Z (+ T = XY for Edwards);
    zero-Z lanes map to the canonical identity ((0,1,0) Weierstrass).

    Schedule-independent by construction: any operation order that
    yields the same group element yields the same canonical limbs.
    Transcript digests MUST hash this form — a Fiat-Shamir digest over
    raw projective limbs would make rho depend on which addition
    schedule (platform / feature flags) produced the commitments,
    breaking cross-platform digest agreement for the same logical
    ceremony.

    One batched inversion over all lanes, as wide as the batch and as
    short as the exponent allows: the lanes fold into
    :func:`_canon_rows` Montgomery-trick rows (one row, so no scan at
    all, below ``2 * _CANON_ROW_LANES`` lanes), and the inversion under
    them is the windowed ``fd.pow_const`` chain — on the fused field
    kernel (``ops.pallas_field.mod_pow_const``) where
    ``fd.fused_kernels_active()``, on ``fd.mul`` elsewhere.  One jitted
    XLA module per shape, inversion included (``jit_affine_canon``).

    Each eager call books ``affine_canon_calls_total{path, rows}`` and
    ``affine_canon_lanes_total`` — per dispatch from the host, so a
    process that finds the program compiled counts like one that traced
    it; a call from inside another traced function books nothing.  The
    ``path`` booked is the jitted program's static key, so it names the
    inversion the compiled program holds and not a switch read later.
    """
    path = _canon_path()
    if not isinstance(pts, jax.core.Tracer):
        from ..utils import metrics  # utils imports dkg, which imports this module

        n_lanes = int(np.prod(pts.shape[:-2], dtype=np.int64))
        metrics.REGISTRY.inc(
            "affine_canon_calls_total",
            path=path,
            rows="1" if _canon_rows(n_lanes) == 1 else ">1",
        )
        metrics.REGISTRY.inc("affine_canon_lanes_total", n_lanes)
    return _affine_canon_jit(cs, path, pts)


def _canon_path() -> str:
    """Which inversion a trace of :func:`affine_canon` made now would
    hold: ``fd.pow_const`` resolves it from the environment at trace
    time, so it is part of the jitted program's key (a process that
    changes the switches re-traces instead of running the other
    program) and the label its counter books."""
    if not fd.fused_kernels_active():
        return "xla"
    return "fused" if fd._on_tpu() else "fused_interpret"


def _affine_canon_traced(cs: CurveSpec, path: str, pts: jax.Array) -> jax.Array:
    if path != _canon_path():
        raise RuntimeError(f"affine_canon keyed {path!r}, traces {_canon_path()!r}")
    f = cs.field
    # a convoy's (c, n, t+1, C, L) stack is computed as the (c * n, t+1, C, L)
    # batch it is: a bitcast under the trace, and the program the chip was
    # measured on (PERF.md section 6, PR 43: five axes cost it a tenth)
    lead = pts.shape[:-3]
    if len(lead) > 1:
        pts = pts.reshape((-1,) + pts.shape[-3:])
    z = pts[..., 2, :]
    z_is_zero = fd.is_zero(z)
    z_safe = fd.select(z_is_zero, jnp.broadcast_to(fd.ones(f), z.shape), z)
    flat = z_safe.reshape(-1, f.limbs)
    n_lanes = flat.shape[0]
    rows = _canon_rows(n_lanes)
    pad = (-n_lanes) % rows  # whole rows; the kernel pads a row to its block itself
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.broadcast_to(fd.ones(f), (pad, f.limbs))]
        )
    zi = fd.batch_inv(f, flat.reshape(rows, -1, f.limbs), axis=0)
    zi = zi.reshape(-1, f.limbs)[:n_lanes].reshape(z.shape)
    xy = fd.mul(f, pts[..., :2, :], zi[..., None, :])  # X/Z and Y/Z in one pass
    x_a, y_a = xy[..., 0, :], xy[..., 1, :]
    one = jnp.broadcast_to(fd.ones(f), x_a.shape)
    if cs.kind == "edwards":
        out = jnp.stack([x_a, y_a, one, fd.mul(f, x_a, y_a)], axis=-2)
    else:
        out = jnp.stack([x_a, y_a, one], axis=-2)
    ident = identity(cs)
    out = jnp.where(
        z_is_zero[..., None, None], jnp.broadcast_to(ident, out.shape), out
    )
    return out.reshape(lead + out.shape[-3:]) if len(lead) > 1 else out


# the XLA module keeps the public name: device traces and the
# benchmark's digest_time_share find the program as ``jit_affine_canon``
_affine_canon_traced.__name__ = "affine_canon"
_affine_canon_jit = jax.jit(_affine_canon_traced, static_argnums=(0, 1))


def _batch_zinv_host(zs: list[int], p: int) -> list[int]:
    """Montgomery-trick inversion over host ints: one Fermat ``pow`` +
    3(k-1) 256-bit modmuls for k nonzero lanes; zero lanes -> 0."""
    prefix = [1] * len(zs)
    acc = 1
    for i, z in enumerate(zs):
        prefix[i] = acc
        if z:
            acc = acc * z % p
    inv_acc = pow(acc, p - 2, p)
    out = [0] * len(zs)
    for i in range(len(zs) - 1, -1, -1):
        z = zs[i]
        if z:
            out[i] = inv_acc * prefix[i] % p
            inv_acc = inv_acc * z % p
    return out


def affine_canon_host(cs: CurveSpec, pts) -> np.ndarray:
    """Host big-int twin of :func:`affine_canon`: (..., C, L) limbs ->
    (..., C, L) uint32 canonical affine limbs, bit-identical to the
    device pass on the same points (zero-Z lanes map to the canonical
    identity, Z=1, Edwards T=XY).

    Exists for the transcript-digest host leg: on CPU the jitted device
    canonicalisation pays an XLA Fermat-inversion ladder where one
    Montgomery-trick pass over 256-bit Python ints costs microseconds —
    the same backend economics as :func:`encode_batch`'s host leg, which
    shares :func:`_batch_zinv_host`.
    """
    f = cs.field
    pts_np = np.asarray(pts)
    shape = pts_np.shape
    flat = pts_np.reshape((-1,) + shape[-2:])
    le = np.ascontiguousarray(flat.astype("<u2")).view(np.uint8)
    n_pts = flat.shape[0]
    p = f.modulus
    coords = [
        [int.from_bytes(le[i, c].tobytes(), "little") for i in range(n_pts)]
        for c in range(cs.ncoords)
    ]
    zinv = _batch_zinv_host(coords[2], p)
    ident = np.asarray(identity(cs), np.uint32)
    out = np.empty((n_pts,) + shape[-2:], np.uint32)
    from ..fields.spec import int_to_limbs

    for i in range(n_pts):
        zi = zinv[i]
        if not zi:
            out[i] = ident
            continue
        x = coords[0][i] * zi % p
        y = coords[1][i] * zi % p
        out[i, 0] = int_to_limbs(x, f.limbs)
        out[i, 1] = int_to_limbs(y, f.limbs)
        out[i, 2] = 0
        out[i, 2, 0] = 1
        if cs.kind == "edwards":
            out[i, 3] = int_to_limbs(x * y % p, f.limbs)
    return out.reshape(shape)


def encode_batch(cs: CurveSpec, pts) -> np.ndarray:
    """Canonical compressed encodings for a whole point batch:
    ``(..., C, L)`` -> ``(..., enc_len)`` uint8, each row bit-identical
    to ``HostGroup.encode`` of that element (the DEM/KDF input and the
    wire point format).

    ONE batched inversion and ONE device->host transfer cover the
    entire batch — vs the scalar path's per-point ``to_affine``
    inversion plus per-dealer ``to_host``.  WHERE the inversion runs
    follows the backend: on TPU the device :func:`affine_canon` pass
    (every lane inverted at once on the fused field kernel, so a
    handful of points and a few thousand cost the device about the
    same — but each call is a round trip through the device's queue);
    on CPU the Montgomery trick over host big-ints — XLA:CPU field muls are
    per-op-overhead-bound at DEM batch widths, so the device pass costs
    ~100ms where 256-bit Python modmuls cost ~100ns each (the dealing
    bench regression that motivated the dispatch).  Both legs produce
    identical bytes (tests/test_dem_batch.py exercises both dispatches).
    The ristretto ENCODE's inverse square root (RFC 9496 §4.3.2) has no
    Montgomery-style batching, so Edwards finishes per point on the
    affine host coordinates — still one transfer and one inversion pass.
    """
    f = cs.field
    if fd._on_tpu():
        aff = np.asarray(affine_canon(cs, jnp.asarray(pts)))
        batch = aff.shape[:-2]
        flat = aff.reshape((-1,) + aff.shape[-2:])
        if cs.kind != "edwards":
            nb = f.nbytes
            x_le = np.ascontiguousarray(flat[:, 0, :].astype("<u2")).view(np.uint8)
            out = np.empty((flat.shape[0], 1 + nb), dtype=np.uint8)
            out[:, 0] = 2 + (flat[:, 1, 0] & 1).astype(np.uint8)
            out[:, 1:] = x_le[:, nb - 1 :: -1]
            # affine_canon maps zero-Z lanes to the canonical identity
            # (0,1,0), whose wire form is the all-zero SEC encoding
            out[(flat[:, 2, :] == 0).all(axis=1)] = 0
            return out.reshape(batch + (1 + nb,))
        affine = [
            tuple(
                int.from_bytes(
                    np.ascontiguousarray(flat[i, c].astype("<u2")).tobytes(),
                    "little",
                )
                for c in range(cs.ncoords)
            )
            for i in range(flat.shape[0])
        ]
    else:
        pts_np = np.asarray(pts)  # the one transfer (no-op on host arrays)
        batch = pts_np.shape[:-2]
        flat = pts_np.reshape((-1,) + pts_np.shape[-2:])
        le = np.ascontiguousarray(flat.astype("<u2")).view(np.uint8)
        n_pts = flat.shape[0]
        p = f.modulus
        coords = [
            [int.from_bytes(le[i, c].tobytes(), "little") for i in range(n_pts)]
            for c in range(3)
        ]
        zinv = _batch_zinv_host(coords[2], p)
        if cs.kind != "edwards":
            nb = f.nbytes
            out = np.zeros((n_pts, 1 + nb), dtype=np.uint8)
            for i in range(n_pts):
                zi = zinv[i]
                if not zi:
                    continue  # identity -> all-zero SEC encoding
                y = coords[1][i] * zi % p
                out[i, 0] = 2 + (y & 1)
                out[i, 1:] = np.frombuffer(
                    (coords[0][i] * zi % p).to_bytes(nb, "big"), dtype=np.uint8
                )
            return out.reshape(batch + (1 + nb,))
        affine = []
        for i in range(n_pts):
            zi = zinv[i]
            if zi:
                x = coords[0][i] * zi % p
                y = coords[1][i] * zi % p
            else:  # canonical Edwards identity
                x, y = 0, 1
            affine.append((x, y, 1, x * y % p))
    host = gh.ALL_GROUPS[cs.name]
    out = np.empty((len(affine), 32), dtype=np.uint8)
    for i, pt in enumerate(affine):
        out[i] = np.frombuffer(host.encode(pt), dtype=np.uint8)
    return out.reshape(batch + (32,))


def window_step(cs: CurveSpec, acc: jax.Array, entry: jax.Array, window: int, fused: bool) -> jax.Array:
    """One Straus window step: ``window`` doublings then add ``entry``.

    THE single definition of the fused-vs-XLA dispatch shared by
    :func:`msm`, :func:`_scalar_mul_core` and the ceremony point-RLC —
    with the fused kernels active the whole step is one Pallas launch
    (intermediates never touch HBM); otherwise plain XLA ops.  A caller
    that holds the kernels' lane blocks calls
    ``pallas_point.window_step_tiles`` itself.
    """
    if fused:
        from ..ops import pallas_point as pp

        return pp.pt_window_step(cs, acc, entry, window)
    for _ in range(window):
        acc = _double_xla(cs, acc)
    return _add_xla(cs, acc, entry)


def _tree_reduce(cs: CurveSpec, pts: jax.Array, axis_len: int) -> jax.Array:
    """Pairwise point-add reduction over axis -3 (the m axis)."""
    m = axis_len
    while m > 1:
        if m % 2 == 1:
            pad = identity(cs, pts.shape[:-3] + (1,))
            pts = jnp.concatenate([pts, pad], axis=-3)
            m += 1
        pts = add(cs, pts[..., 0::2, :, :], pts[..., 1::2, :, :])
        m //= 2
    return pts[..., 0, :, :]


def _tree_tiles(cs: CurveSpec, x: jax.Array, m: int, cols: int) -> jax.Array:
    """:func:`_tree_reduce` on lane blocks: ``x`` (nb, C·L, BLOCK) holds
    m·cols lanes ordered (dealer, column) and the identity after them;
    the result's first ``cols`` lanes are the sums over the dealers.

    Each level adds the upper half of the dealers onto the lower (dealer
    j + ceil(m/2) onto j: another pairing than :func:`_tree_reduce`'s
    neighbours, so the same group element in other coordinates).  The
    upper half is a slice of whole blocks where ceil(m/2)·cols is a
    multiple of BLOCK, and of blocks and lanes where it is not; nothing
    leaves the block form.  Lanes past the live ones hold sums nobody
    reads, except before a level with an odd count, whose unpaired
    dealer adds the lanes after the last: those are set to the identity.
    """
    from ..ops import pallas_point as pp

    B = pp.BLOCK
    while m > 1:
        top = (m + 1) // 2
        nout = -(-top * cols // B)
        qb, r = divmod(top * cols, B)
        short = qb + nout + (r > 0) - x.shape[0]
        ext = jnp.concatenate([x, pp.identity_tiles(cs, short)]) if short > 0 else x
        hi = ext[qb : qb + nout]
        if r:
            hi = jnp.concatenate([hi[..., r:], ext[qb + 1 : qb + nout + 1, :, :r]], axis=-1)
        x = pp.add_tiles(cs, x[:nout], hi)
        if top > 1 and top % 2:
            lane = jnp.arange(nout * B).reshape(nout, 1, B)
            x = jnp.where(lane < top * cols, x, pp.identity_tiles(cs))
        m = top
    return x


def msm(cs: CurveSpec, scalars: jax.Array, points: jax.Array) -> jax.Array:
    """Batched MSM: Σ_j k_j·P_j over axis -2 of scalars / -3 of points.

    scalars (..., m, L), points (..., m, C, L) -> (..., C, L).

    Two bit-exact kernels (both end in the same complete formulas and a
    canonical reduction order per window, so they agree limb-for-limb
    after affine_canon):

    * ``straus`` — shared-doubling Straus (:func:`msm_straus`): per-lane
      16-entry tables, tree-reduce per window.  Default when the fused
      multi-op Pallas kernels are active (TPU): the window step is one
      kernel launch and the per-lane tables live in HBM.
    * ``pippenger`` — bucket method (:func:`msm_pippenger`): no per-point
      tables at all; points are scattered into 2**c buckets per window,
      then each window is closed with ~2**(c+1) adds.  Default elsewhere:
      on CPU the per-lane table build + gathers dominate Straus, and the
      bucket width c scales with the batch (see :func:`pippenger_window`).

    ``DKG_TPU_MSM=straus|pippenger`` (validated) forces a kernel.
    This is the share-verification workhorse (reference seam:
    traits.rs:234-237; hot call committee.rs:292-296).
    """
    if msm_mode() == "pippenger":
        return msm_pippenger(cs, scalars, points)
    return msm_straus(cs, scalars, points)


@_jit_static0
def msm_straus(cs: CurveSpec, scalars: jax.Array, points: jax.Array) -> jax.Array:
    """Straus shared-doubling MSM (the reference kernel — see :func:`msm`):
    per 4-bit window, gather each point's digit multiple from its
    per-lane table, tree-reduce the m contributions, then 4 shared
    doublings."""
    m = points.shape[-3]
    tables = _build_table(cs, points)  # (..., m, 16, C, L)
    digits = scalar_windows(cs, scalars)  # (..., m, NW)
    digits_rev = jnp.moveaxis(digits, -1, 0)[::-1]  # (NW, ..., m)
    fused = fused_kernels_active()

    def step(acc, dig):
        contribs = _gather_table(tables, dig)  # (..., m, C, L)
        total = _tree_reduce(cs, contribs, m)
        return window_step(cs, acc, total, WINDOW, fused), None

    init = identity(cs, points.shape[:-3])
    acc, _ = lax.scan(step, init, digits_rev)
    return acc


# Measured c=4 -> c=8 crossover per curve.  Every figure here is a CPU
# timing (XLA:CPU probe, jit-cached steady state; msm at m =
# 64/256/512), none is from the chip: BLS12-381's 24-limb field mul
# makes every bucket-closing add ~2.3x a 16-limb add, but the scatter
# pass grows by the same factor, so its crossover sits HIGHER than the
# 256-bit curves' — w=4 still won at m=256 (704 vs 781 ms) and only
# loses at m=512 (1483 vs 1292 ms).  The chip's default schedule is not
# Pippenger at all: verify's point-RLC is Straus in the kernels'
# lane-block form there (dkg/ceremony.py ``_straus_tiles``, PR 31) and
# never asks for a bucket width; this table steers the CPU default and
# an explicit ``DKG_TPU_RLC=pippenger``.
_PIPPENGER_CROSSOVER: dict[str, int] = {"bls12_381_g1": 512}


def pippenger_window(m: int, curve: str | None = None) -> int:
    """Bucket width (bits) from the MSM batch shape (and curve).

    Cost model (sequential point-op calls, the CPU/XLA currency):
    NW(c) · (m + 2·(2**c - 1) + c + 1) with NW(c) = 256/c windows — the
    scatter pass is m adds per window regardless of c, the bucket
    suffix-sum closes at 2 adds per bucket, so doubling c halves the
    window count once m dwarfs the 2**(c+1) closing cost.  Crossover
    c=4 -> c=8 sits at m ≈ 2·(2**8 - 2**4) ≈ 450 for the 16-limb
    curves; measured per-curve overrides in ``_PIPPENGER_CROSSOVER``.
    Widths must divide the 16-bit limb (scalar_windows).
    """
    return 8 if m >= _PIPPENGER_CROSSOVER.get(curve, 448) else 4


def msm_pippenger(
    cs: CurveSpec, scalars: jax.Array, points: jax.Array, nbits: int | None = None
) -> jax.Array:
    """Bucket-method (Pippenger) MSM: scalars (..., m, L),
    points (..., m, C, L) -> (..., C, L), summed over the m axis.

    ``nbits`` bounds the scalars' bit width (e.g. 128-bit RLC weights);
    windows above it are statically dropped.  Batch axes of scalars and
    points must match (scalars broadcast up).
    """
    if nbits is None:
        nbits = cs.scalar.limbs * 16
    scalars = jnp.broadcast_to(scalars, points.shape[:-2] + scalars.shape[-1:])
    return _msm_pippenger_core(cs, scalars, points, nbits)


def _bucket_scan(
    cs: CurveSpec, points: jax.Array, digits: jax.Array, entries: int
) -> jax.Array:
    """The XLA scatter leg of Pippenger: scan over the m points; each
    step gathers the point's current bucket per window (take_along_axis
    over the bucket axis), adds through the complete formulas, and
    writes it back with a branchless one-hot select.  The per-step
    ``(…, nw, entries)`` one-hot and whole-bucket-tensor select are the
    HBM cost the Pallas kernel leg eliminates.

    points (..., m, C, L), digits (..., m, nw) ->
    buckets (..., nw, entries, C, L).
    """
    batch = points.shape[:-3]
    nw = digits.shape[-1]
    pts_m = jnp.moveaxis(points, -3, 0)  # (m, ..., C, L)
    digs_m = jnp.moveaxis(digits, -2, 0).astype(jnp.int32)  # (m, ..., nw)
    bucket_ids = jnp.arange(entries, dtype=jnp.int32)

    def scatter(buckets, args):
        pt, dig = args  # (..., C, L), (..., nw)
        idx = dig[..., None, None, None]  # (..., nw, 1, 1, 1)
        cur = jnp.take_along_axis(buckets, idx, axis=-3)[..., 0, :, :]
        new = add(cs, cur, pt[..., None, :, :])  # (..., nw, C, L)
        onehot = bucket_ids == dig[..., None]  # (..., nw, entries)
        buckets = jnp.where(onehot[..., None, None], new[..., None, :, :], buckets)
        return buckets, None

    init_b = identity(cs, batch + (nw, entries))
    buckets, _ = lax.scan(scatter, init_b, (pts_m, digs_m))
    return buckets


@functools.partial(jax.jit, static_argnums=(0, 3))
def _msm_pippenger_core(
    cs: CurveSpec, scalars: jax.Array, points: jax.Array, nbits: int
) -> jax.Array:
    """Three passes, all batched over the leading axes and all windows at
    once (the m axis is the only sequential dimension that grows with
    the problem):

    1. scatter — the XLA scan leg (:func:`_bucket_scan`) on every
       backend.  Digit-0 contributions land in bucket 0, which the
       reduction ignores (identity-safe).  (A VMEM-resident Pallas
       twin was wrong on the v5e at PR 22 and was removed at PR 30.)
    2. bucket close — descending suffix-sum scan over the 2**c - 1
       non-zero buckets: run += B_b; tot += run computes
       Σ_b b·B_b in 2 adds per bucket, for every window in parallel.
    3. window combine — MSB-first Horner over the NW window sums via
       :func:`window_step` (c doublings + 1 add per window).
    """
    m = points.shape[-3]
    batch = points.shape[:-3]
    window = pippenger_window(m, cs.name)
    entries = 1 << window
    nw = min(_n_windows(cs, window), -(-nbits // window))
    digits = scalar_windows(cs, scalars, window)[..., :nw]  # (..., m, nw)

    buckets = _bucket_scan(cs, points, digits, entries)

    # descending suffix sums over buckets [entries-1 .. 1]
    nonzero = jnp.moveaxis(buckets[..., 1:, :, :], -3, 0)[::-1]

    def close(carry, bucket):
        run, tot = carry
        run = add(cs, run, bucket)
        tot = add(cs, tot, run)
        return (run, tot), None

    ident_w = identity(cs, batch + (nw,))
    (_, win_sums), _ = lax.scan(close, (ident_w, ident_w), nonzero)

    ws_rev = jnp.moveaxis(win_sums, -3, 0)[::-1]  # (nw, ..., C, L) MSB first
    fused = fused_kernels_active()

    def combine(acc, w_sum):
        return window_step(cs, acc, w_sum, window, fused), None

    acc, _ = lax.scan(combine, identity(cs, batch), ws_rev)
    return acc
