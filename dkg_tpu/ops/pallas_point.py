"""Fused Pallas point-operation kernels (Edwards AND Weierstrass a=0).

The scalar-mult ladder's hot loop is point add/double — each one is
~7-14 Barrett multiplies plus adds/subs.  The XLA path materialises
every intermediate field element in HBM between fused regions; these
kernels keep the WHOLE point operation (and multi-op sequences: the
n-double window step, the full small-scalar ladder) in VMEM:
coordinates ride the sublane axis as C·L limb rows, the batch rides
the 128-wide lane axis, and the multiplies chain through
ops.pallas_field.mod_mul_rows without ever leaving the core.

Curve coverage matches groups/device.py: twisted Edwards a=-1
(add-2008-hwcd-3 unified add, dbl-2008-hwcd doubling — complete for
ristretto255) and short Weierstrass a=0 (Renes-Costello-Batina 2015
algorithms 7 & 9 complete formulas — secp256k1, BLS12-381 G1).  These
mirror the role of dalek's backend in the reference (reference:
src/groups.rs:55-90 delegating point arithmetic to curve25519-dalek;
MSM seam src/traits.rs:234-237).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..groups.device import CurveSpec
from ..utils import metrics
from .pallas_field import (
    BLOCK,
    mod_add_rows,
    mod_mul_rows,
    mod_sub_rows,
    mxu_operands,
    rows_mul_context,
)


def _const_rows(fs, value: int, like):
    from ..fields.spec import int_to_limbs

    return [jnp.full_like(like, np.uint32(v)) for v in int_to_limbs(value % fs.modulus, fs.limbs)]


def _ed_add_rows(cs: CurveSpec, p_rows, q_rows):
    """Unified extended Edwards add on 4 coordinate row-lists each."""
    f = cs.field
    x1, y1, z1, t1 = p_rows
    x2, y2, z2, t2 = q_rows
    a = mod_mul_rows(f, mod_sub_rows(f, y1, x1), mod_sub_rows(f, y2, x2))
    b = mod_mul_rows(f, mod_add_rows(f, y1, x1), mod_add_rows(f, y2, x2))
    d2 = _const_rows(f, cs.const, x1[0])
    c = mod_mul_rows(f, mod_mul_rows(f, t1, d2), t2)
    d = mod_mul_rows(f, mod_add_rows(f, z1, z1), z2)
    e = mod_sub_rows(f, b, a)
    ff = mod_sub_rows(f, d, c)
    g = mod_add_rows(f, d, c)
    h = mod_add_rows(f, b, a)
    return (
        mod_mul_rows(f, e, ff),
        mod_mul_rows(f, g, h),
        mod_mul_rows(f, ff, g),
        mod_mul_rows(f, e, h),
    )


def _ed_double_rows(cs: CurveSpec, p_rows):
    """Dedicated doubling (dbl-2008-hwcd), a = -1."""
    f = cs.field
    x1, y1, z1, _ = p_rows
    a = mod_mul_rows(f, x1, x1)
    b = mod_mul_rows(f, y1, y1)
    zz = mod_mul_rows(f, z1, z1)
    c = mod_add_rows(f, zz, zz)
    zero = [jnp.zeros_like(x1[0]) for _ in range(f.limbs)]
    d = mod_sub_rows(f, zero, a)  # a = -1 => D = -A
    xy = mod_add_rows(f, x1, y1)
    e = mod_sub_rows(f, mod_sub_rows(f, mod_mul_rows(f, xy, xy), a), b)
    g = mod_add_rows(f, d, b)
    h = mod_sub_rows(f, d, b)
    ff = mod_sub_rows(f, g, c)
    return (
        mod_mul_rows(f, e, ff),
        mod_mul_rows(f, g, h),
        mod_mul_rows(f, ff, g),
        mod_mul_rows(f, e, h),
    )


def _ws_add_rows(cs: CurveSpec, p_rows, q_rows):
    """Complete projective add for y^2 = x^3 + b (RCB15 algorithm 7),
    the row-list twin of groups/device.py _ws_add."""
    f = cs.field
    x1, y1, z1 = p_rows
    x2, y2, z2 = q_rows
    b3 = _const_rows(f, cs.const, x1[0])
    t0 = mod_mul_rows(f, x1, x2)
    t1 = mod_mul_rows(f, y1, y2)
    t2 = mod_mul_rows(f, z1, z2)
    t3 = mod_mul_rows(f, mod_add_rows(f, x1, y1), mod_add_rows(f, x2, y2))
    t3 = mod_sub_rows(f, mod_sub_rows(f, t3, t0), t1)
    t4 = mod_mul_rows(f, mod_add_rows(f, y1, z1), mod_add_rows(f, y2, z2))
    t4 = mod_sub_rows(f, mod_sub_rows(f, t4, t1), t2)
    xz = mod_mul_rows(f, mod_add_rows(f, x1, z1), mod_add_rows(f, x2, z2))
    y3 = mod_sub_rows(f, mod_sub_rows(f, xz, t0), t2)
    x3 = mod_add_rows(f, mod_add_rows(f, t0, t0), t0)
    t2 = mod_mul_rows(f, b3, t2)
    z3 = mod_add_rows(f, t1, t2)
    t1 = mod_sub_rows(f, t1, t2)
    y3 = mod_mul_rows(f, b3, y3)
    x_out = mod_sub_rows(f, mod_mul_rows(f, t3, t1), mod_mul_rows(f, t4, y3))
    y_out = mod_add_rows(f, mod_mul_rows(f, t1, z3), mod_mul_rows(f, x3, y3))
    z_out = mod_add_rows(f, mod_mul_rows(f, z3, t4), mod_mul_rows(f, x3, t3))
    return (x_out, y_out, z_out)


def _ws_double_rows(cs: CurveSpec, p_rows):
    """Complete doubling for y^2 = x^3 + b (RCB15 algorithm 9)."""
    f = cs.field
    x, y, z = p_rows
    b3 = _const_rows(f, cs.const, x[0])
    t0 = mod_mul_rows(f, y, y)
    z3 = mod_add_rows(f, t0, t0)
    z3 = mod_add_rows(f, z3, z3)
    z3 = mod_add_rows(f, z3, z3)
    t1 = mod_mul_rows(f, y, z)
    t2 = mod_mul_rows(f, b3, mod_mul_rows(f, z, z))
    x3 = mod_mul_rows(f, t2, z3)
    y3 = mod_add_rows(f, t0, t2)
    z3 = mod_mul_rows(f, t1, z3)
    t1 = mod_add_rows(f, t2, t2)
    t2 = mod_add_rows(f, t1, t2)
    t0 = mod_sub_rows(f, t0, t2)
    y3 = mod_add_rows(f, x3, mod_mul_rows(f, t0, y3))
    x3 = mod_mul_rows(f, t0, mod_mul_rows(f, x, y))
    x3 = mod_add_rows(f, x3, x3)
    return (x3, y3, z3)


def _ed_madd_rows(cs: CurveSpec, p_rows, q_rows):
    """Mixed unified Edwards add: q affine (Z2 == 1) — the 2*Z1*Z2
    multiply collapses to 2*Z1 (see groups/device._ed_madd)."""
    f = cs.field
    x1, y1, z1, t1 = p_rows
    x2, y2, _, t2 = q_rows
    a = mod_mul_rows(f, mod_sub_rows(f, y1, x1), mod_sub_rows(f, y2, x2))
    b = mod_mul_rows(f, mod_add_rows(f, y1, x1), mod_add_rows(f, y2, x2))
    d2 = _const_rows(f, cs.const, x1[0])
    c = mod_mul_rows(f, mod_mul_rows(f, t1, d2), t2)
    d = mod_add_rows(f, z1, z1)
    e = mod_sub_rows(f, b, a)
    ff = mod_sub_rows(f, d, c)
    g = mod_add_rows(f, d, c)
    h = mod_add_rows(f, b, a)
    return (
        mod_mul_rows(f, e, ff),
        mod_mul_rows(f, g, h),
        mod_mul_rows(f, ff, g),
        mod_mul_rows(f, e, h),
    )


def _ws_madd_rows(cs: CurveSpec, p_rows, q_rows):
    """Mixed addition, q affine (RCB15 algorithm 8) — NOT valid for
    q = identity; callers mask zero digits (see groups/device._ws_madd)."""
    f = cs.field
    x1, y1, z1 = p_rows
    x2, y2, _ = q_rows
    b3 = _const_rows(f, cs.const, x1[0])
    t0 = mod_mul_rows(f, x1, x2)
    t1 = mod_mul_rows(f, y1, y2)
    t3 = mod_mul_rows(f, mod_add_rows(f, x1, y1), mod_add_rows(f, x2, y2))
    t3 = mod_sub_rows(f, mod_sub_rows(f, t3, t0), t1)
    t4 = mod_add_rows(f, mod_mul_rows(f, y2, z1), y1)
    y3 = mod_add_rows(f, mod_mul_rows(f, x2, z1), x1)
    x3 = mod_add_rows(f, mod_add_rows(f, t0, t0), t0)
    t2 = mod_mul_rows(f, b3, z1)
    z3 = mod_add_rows(f, t1, t2)
    t1 = mod_sub_rows(f, t1, t2)
    y3 = mod_mul_rows(f, b3, y3)
    x_out = mod_sub_rows(f, mod_mul_rows(f, t3, t1), mod_mul_rows(f, t4, y3))
    y_out = mod_add_rows(f, mod_mul_rows(f, t1, z3), mod_mul_rows(f, x3, y3))
    z_out = mod_add_rows(f, mod_mul_rows(f, z3, t4), mod_mul_rows(f, x3, t3))
    return (x_out, y_out, z_out)


def _madd_rows(cs: CurveSpec, p_rows, q_rows):
    if cs.kind == "edwards":
        return _ed_madd_rows(cs, p_rows, q_rows)
    return _ws_madd_rows(cs, p_rows, q_rows)


def _add_rows(cs: CurveSpec, p_rows, q_rows):
    if cs.kind == "edwards":
        return _ed_add_rows(cs, p_rows, q_rows)
    return _ws_add_rows(cs, p_rows, q_rows)


def _double_rows(cs: CurveSpec, p_rows):
    if cs.kind == "edwards":
        return _ed_double_rows(cs, p_rows)
    return _ws_double_rows(cs, p_rows)


def _identity_rows(cs: CurveSpec, like):
    """Constant identity point as coordinate row-lists."""
    f = cs.field
    zero = [jnp.zeros_like(like) for _ in range(f.limbs)]
    one = [jnp.full_like(like, np.uint32(1))] + [
        jnp.zeros_like(like) for _ in range(f.limbs - 1)
    ]
    if cs.kind == "edwards":  # (0, 1, 1, 0)
        return (zero, one, list(one), list(zero))
    return (zero, one, list(zero))  # (0, 1, 0)


def _select_rows(bit, a_rows, b_rows):
    """Per-lane select between two point row-lists; bit a (1, B) tile."""
    keep = bit != 0
    return tuple(
        [jnp.where(keep, ai, bi) for ai, bi in zip(ac, bc)]
        for ac, bc in zip(a_rows, b_rows)
    )


def _rows_in(ref, L: int, ncoords: int = 4):
    """(C·L, B) ref -> C coordinate row-lists of L tiles each."""
    return tuple(
        [ref[c * L + i : c * L + i + 1, :] for i in range(L)]
        for c in range(ncoords)
    )


def _rows_out(ref, rows, L: int):
    for c in range(len(rows)):
        for i in range(L):
            ref[c * L + i : c * L + i + 1, :] = rows[c][i]


def _block_spec(rows: int):
    return pl.BlockSpec((rows, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM)


def _block_call(cs: CurveSpec, name: str, kernel, operands, rows_in, interpret: bool):
    """One launch of ``kernel`` over ONE lane block: ``operands`` are
    (rows, BLOCK) arrays, the result a (C·L, BLOCK) point block.

    Every kernel below is written for one block inside its own ``jit``
    and ``jax.vmap``ped over a batch's blocks by its wrapper, the form
    ``pallas_field.mod_pow_const`` introduced: the batching rule turns the
    map into the launch's grid from the cached jaxpr, so a process
    traces a kernel's multiply bodies ONCE per (curve, kernel).  With
    the grid written here they were traced again for every batch size
    a program holds — a (1024,341) verify has 23 ``pt_add`` sizes, 5 s
    each in the sandbox and more on a serving host."""
    out_rows = cs.ncoords * cs.field.limbs
    extra, extra_specs = mxu_operands(cs.field, interpret)
    return pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[_block_spec(r) for r in rows_in] + extra_specs,
        out_specs=_block_spec(out_rows),
        out_shape=jax.ShapeDtypeStruct((out_rows, BLOCK), jnp.uint32),
        interpret=interpret,
        name=name,
    )(*operands, *extra)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _add_call(cs: CurveSpec, p_t: jax.Array, q_t: jax.Array, interpret: bool):
    L, C = cs.field.limbs, cs.ncoords

    def kernel(p_ref, q_ref, *rest):
        with rows_mul_context(cs.field, rest[:-1]):
            _rows_out(
                rest[-1], _add_rows(cs, _rows_in(p_ref, L, C), _rows_in(q_ref, L, C)), L
            )

    return _block_call(cs, "pt_add", kernel, (p_t, q_t), (C * L, C * L), interpret)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _madd_call(cs: CurveSpec, p_t: jax.Array, q_t: jax.Array, interpret: bool):
    L, C = cs.field.limbs, cs.ncoords

    def kernel(p_ref, q_ref, *rest):
        with rows_mul_context(cs.field, rest[:-1]):
            _rows_out(
                rest[-1], _madd_rows(cs, _rows_in(p_ref, L, C), _rows_in(q_ref, L, C)), L
            )

    return _block_call(cs, "pt_madd", kernel, (p_t, q_t), (C * L, C * L), interpret)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _double_call(cs: CurveSpec, p_t: jax.Array, n_doubles: int, interpret: bool):
    L, C = cs.field.limbs, cs.ncoords

    def kernel(p_ref, *rest):
        with rows_mul_context(cs.field, rest[:-1]):
            rows = _rows_in(p_ref, L, C)
            for _ in range(n_doubles):
                rows = _double_rows(cs, rows)
            _rows_out(rest[-1], rows, L)

    return _block_call(cs, "pt_double", kernel, (p_t,), (C * L,), interpret)


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _window_call(cs: CurveSpec, acc_t: jax.Array, n_doubles: int, interpret: bool, entry_t: jax.Array):
    """The fused ladder window step: n_doubles doublings then one add,
    all inside one kernel launch — the HBM-traffic killer for
    scalar_mul's scan body (groups/device.py _scalar_mul_core)."""
    L, C = cs.field.limbs, cs.ncoords

    def kernel(acc_ref, entry_ref, *rest):
        with rows_mul_context(cs.field, rest[:-1]):
            rows = _rows_in(acc_ref, L, C)
            for _ in range(n_doubles):
                rows = _double_rows(cs, rows)
            rows = _add_rows(cs, rows, _rows_in(entry_ref, L, C))
            _rows_out(rest[-1], rows, L)

    return _block_call(
        cs, "pt_window_step", kernel, (acc_t, entry_t), (C * L, C * L), interpret
    )


@functools.partial(jax.jit, static_argnums=(0, 3, 4))
def _ladder_call(
    cs: CurveSpec,
    p_t: jax.Array,
    add_t: jax.Array,
    nbits: int,
    interpret: bool,
    bits_t: jax.Array,
):
    """out = x·P + A in ONE launch, x given per-lane as MSB-first bits.

    The whole double-and-select-add ladder (the Horner step of
    eval_point_poly, reference committee.rs:292-296's sum x^l E_l) runs
    VMEM-resident; the loop body is traced once via fori_loop so kernel
    code size stays ~2 point-ops regardless of nbits.
    """
    L, C = cs.field.limbs, cs.ncoords

    def kernel(p_ref, add_ref, bits_ref, *rest):
        p_rows = _rows_in(p_ref, L, C)

        def body(i, m_arr):
            rows = _rows_in(m_arr, L, C)
            rows = _double_rows(cs, rows)
            added = _add_rows(cs, rows, p_rows)
            bit = (
                bits_ref[i : i + 1, :]
                if isinstance(i, int)
                else bits_ref[pl.dslice(i, 1), :]
            )
            rows = _select_rows(bit, added, rows)
            return jnp.concatenate([r for coord in rows for r in coord], axis=0)

        m_arr = jnp.concatenate(
            [r for coord in _identity_rows(cs, p_ref[0:1, :]) for r in coord], axis=0
        )
        with rows_mul_context(cs.field, rest[:-1]):
            if interpret:
                # interpret-mode lowering of fori_loop over this body is
                # pathologically slow to compile; tests use tiny nbits, so
                # unroll instead.
                for i in range(nbits):
                    m_arr = body(i, m_arr)
            else:
                m_arr = jax.lax.fori_loop(0, nbits, body, m_arr)
            rows = _add_rows(cs, _rows_in(m_arr, L, C), _rows_in(add_ref, L, C))
        _rows_out(rest[-1], rows, L)

    return _block_call(
        cs, "pt_ladder_mul_add", kernel, (p_t, add_t, bits_t),
        (C * L, C * L, nbits), interpret,
    )


def lane_blocks(flat: jax.Array) -> jax.Array:
    """(m, rows) with m a BLOCK multiple -> (m // BLOCK, rows, BLOCK):
    lanes on the lane axis, one kernel block each."""
    m, rows = flat.shape
    return jnp.swapaxes(jnp.reshape(flat, (m // BLOCK, BLOCK, rows)), 1, 2)


def _identity_flat(cs: CurveSpec) -> np.ndarray:
    ident = np.zeros((cs.ncoords, cs.field.limbs), np.uint32)
    ident[1, 0] = 1
    if cs.kind == "edwards":
        ident[2, 0] = 1
    return ident.reshape(-1)


def identity_tiles(cs: CurveSpec, nb: int = 1) -> jax.Array:
    """``nb`` lane blocks of the identity."""
    flat = _identity_flat(cs)
    return jnp.broadcast_to(jnp.asarray(flat)[None, :, None], (nb, flat.size, BLOCK))


def to_tiles(cs: CurveSpec, pts: jax.Array) -> tuple[jax.Array, tuple, int]:
    """(..., C, L) -> ((nb, C·L, BLOCK) lane blocks, batch_shape, n).

    The form every point kernel works on: the batch flattened row-major
    onto lanes, BLOCK to a block, the tail padded with the identity (so
    padding lanes stay on-curve).  With :func:`from_tiles` the seam
    between the tensor form and the ``*_tiles`` twins below, for a
    caller that strings kernels together and converts once each way
    (``dkg.ceremony._point_rlc``)."""
    L, C = cs.field.limbs, cs.ncoords
    batch = pts.shape[:-2]
    n = 1
    for d in batch:
        n *= int(d)
    m = max(BLOCK, ((n + BLOCK - 1) // BLOCK) * BLOCK)
    flat = jnp.reshape(pts, (n, C * L))
    if m != n:
        flat = jnp.concatenate(
            [flat, jnp.broadcast_to(jnp.asarray(_identity_flat(cs)), (m - n, C * L))]
        )
    return lane_blocks(flat), batch, n


def from_tiles(cs: CurveSpec, t: jax.Array, batch: tuple, n: int) -> jax.Array:
    """The first ``n`` lanes of (nb, C·L, BLOCK) blocks as ``batch + (C, L)``."""
    L, C = cs.field.limbs, cs.ncoords
    flat = jnp.reshape(jnp.swapaxes(t, 1, 2), (-1, C * L))
    return jnp.reshape(flat[:n], batch + (C, L))


def _interp(interpret: bool | None = None) -> bool:
    from ..fields import device as fd

    return (not fd._on_tpu()) if interpret is None else interpret


def add_tiles(cs: CurveSpec, p_t: jax.Array, q_t: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """:func:`pt_add` on lane blocks: (nb, C·L, BLOCK) in and out."""
    metrics.REGISTRY.inc("pallas_calls_total", kernel="pt_add")
    interp = _interp(interpret)
    return jax.vmap(lambda pb, qb: _add_call(cs, pb, qb, interp))(p_t, q_t)


def madd_tiles(cs: CurveSpec, p_t: jax.Array, q_t: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """:func:`pt_madd` on lane blocks (``groups.device._fixed_base_mul_core``'s
    window step: the accumulator never leaves this form)."""
    metrics.REGISTRY.inc("pallas_calls_total", kernel="pt_madd")
    interp = _interp(interpret)
    return jax.vmap(lambda pb, qb: _madd_call(cs, pb, qb, interp))(p_t, q_t)


def double_tiles(cs: CurveSpec, p_t: jax.Array, n_doubles: int = 1, *, interpret: bool | None = None) -> jax.Array:
    """:func:`pt_double` on lane blocks."""
    metrics.REGISTRY.inc("pallas_calls_total", kernel="pt_double")
    interp = _interp(interpret)
    return jax.vmap(lambda pb: _double_call(cs, pb, n_doubles, interp))(p_t)


def window_step_tiles(
    cs: CurveSpec, acc_t: jax.Array, entry_t: jax.Array, n_doubles: int = 4, *, interpret: bool | None = None
) -> jax.Array:
    """:func:`pt_window_step` on lane blocks."""
    metrics.REGISTRY.inc("pallas_calls_total", kernel="pt_window_step")
    interp = _interp(interpret)
    return jax.vmap(lambda ab, eb: _window_call(cs, ab, n_doubles, interp, eb))(acc_t, entry_t)


def pt_add(cs: CurveSpec, p: jax.Array, q: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Fused-kernel twin of groups.device.add (both curve kinds).

    p, q: (..., C, L) projective/extended points (batches broadcast)."""
    p, q = jnp.broadcast_arrays(jnp.asarray(p, jnp.uint32), jnp.asarray(q, jnp.uint32))
    p_t, batch, n = to_tiles(cs, p)
    out = add_tiles(cs, p_t, to_tiles(cs, q)[0], interpret=interpret)
    return from_tiles(cs, out, batch, n)


def pt_madd(cs: CurveSpec, p: jax.Array, q: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Fused mixed add: q affine-normalised (Z = 1).  Weierstrass
    callers must not pass q = identity (see groups/device.madd)."""
    p, q = jnp.broadcast_arrays(jnp.asarray(p, jnp.uint32), jnp.asarray(q, jnp.uint32))
    p_t, batch, n = to_tiles(cs, p)
    out = madd_tiles(cs, p_t, to_tiles(cs, q)[0], interpret=interpret)
    return from_tiles(cs, out, batch, n)


def pt_double(cs: CurveSpec, p: jax.Array, n_doubles: int = 1, *, interpret: bool | None = None) -> jax.Array:
    """Fused 2^n_doubles·P in one launch."""
    p_t, batch, n = to_tiles(cs, jnp.asarray(p, jnp.uint32))
    return from_tiles(cs, double_tiles(cs, p_t, n_doubles, interpret=interpret), batch, n)


def pt_window_step(
    cs: CurveSpec, acc: jax.Array, entry: jax.Array, n_doubles: int = 4, *, interpret: bool | None = None
) -> jax.Array:
    """acc <- 2^n_doubles · acc + entry, fused in one kernel launch."""
    acc, entry = jnp.broadcast_arrays(
        jnp.asarray(acc, jnp.uint32), jnp.asarray(entry, jnp.uint32)
    )
    acc_t, batch, n = to_tiles(cs, acc)
    out = window_step_tiles(cs, acc_t, to_tiles(cs, entry)[0], n_doubles, interpret=interpret)
    return from_tiles(cs, out, batch, n)


def pt_ladder_mul_add(
    cs: CurveSpec,
    p: jax.Array,
    addend: jax.Array,
    x: jax.Array,
    nbits: int,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """x·P + A for small public per-lane integers x < 2**nbits, fused.

    p, addend: (..., C, L); x: (...,) uint32.  One kernel launch runs
    the whole nbits-step ladder — this is eval_point_poly's Horner step
    (acc <- x·acc + E_l) collapsed from ~2·nbits XLA ops into one.
    """
    metrics.REGISTRY.inc("pallas_calls_total", kernel="pt_ladder_mul_add")
    p, addend = jnp.broadcast_arrays(
        jnp.asarray(p, jnp.uint32), jnp.asarray(addend, jnp.uint32)
    )
    x = jnp.broadcast_to(jnp.asarray(x, jnp.uint32), p.shape[:-2])
    p_t, batch, n = to_tiles(cs, p)
    a_t, _, _ = to_tiles(cs, addend)
    B = p_t.shape[0] * BLOCK
    xf = jnp.reshape(x, (n,))
    if B != n:
        xf = jnp.concatenate([xf, jnp.zeros((B - n,), jnp.uint32)])
    # MSB-first bit rows per lane: bit (nbits-1-i) of x in row i
    shifts = jnp.arange(nbits - 1, -1, -1, dtype=jnp.uint32)
    bits_t = lane_blocks((xf[:, None] >> shifts[None, :]) & jnp.uint32(1))
    interp = _interp(interpret)
    out = jax.vmap(lambda pb, ab, bb: _ladder_call(cs, pb, ab, nbits, interp, bb))(
        p_t, a_t, bits_t
    )
    return from_tiles(cs, out, batch, n)


# Backwards-compatible Edwards aliases (round-1 API).
def ed_add(cs: CurveSpec, p: jax.Array, q: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    return pt_add(cs, p, q, interpret=interpret)


def ed_window_step(
    cs: CurveSpec, acc: jax.Array, entry: jax.Array, n_doubles: int = 4, *, interpret: bool | None = None
) -> jax.Array:
    return pt_window_step(cs, acc, entry, n_doubles, interpret=interpret)
