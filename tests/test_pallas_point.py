"""Fused Pallas point kernels vs the XLA-path group ops.

Coverage strategy (compile-cost driven — in this environment XLA:CPU
takes minutes-to-hours on interpret-mode pallas programs, see
slow_operation_alarm / "algebraic simplifier stuck" warnings):

* **Row-function parity (default tier, plain XLA on CPU).**  The kernel
  bodies are built from pure-jnp "row list" functions
  (ops/pallas_field.mod_*_rows, ops/pallas_point._*_rows); calling them
  directly on (1, B) tiles exercises every formula / limb-order / carry
  path with NO pallas machinery and compiles in seconds.  A 2-limb toy
  field (p = 2^31 - 1) keeps it cheap; parity holds for ARBITRARY
  coordinate tuples because the formulas are polynomial maps.
* **Kernel parity on a real TPU backend** (Mosaic compiles these in
  seconds): the full pallas_call plumbing — BlockSpecs, grid tiling,
  ref slicing, the fori_loop ladder — against the XLA implementations
  ``gd._add_xla``/``_double_xla`` (NOT ``gd.add``/``gd.double``, which
  on TPU dispatch straight back to the kernels under test).
"""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dkg_tpu.fields.spec import FieldSpec
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh
from dkg_tpu.ops import pallas_point as pp

pytestmark = pytest.mark.slow  # compile-heavy: nightly/device tier

RNG = random.Random(0xEDED)

ON_TPU = jax.default_backend() == "tpu"

TOY_FS = FieldSpec("toy_m31", (1 << 31) - 1, 2)
TOY_ED = gd.CurveSpec("toy_ed", "edwards", TOY_FS, TOY_FS, 37, (0, 1))
TOY_WS = gd.CurveSpec("toy_ws", "weierstrass_a0", TOY_FS, TOY_FS, 21, (0, 1))
TOY_CURVES = [TOY_ED, TOY_WS]


def _toy_points_dev(cs, n):
    """Random coordinate tuples (NOT on-curve: parity is algebraic)."""
    from dkg_tpu.fields import host as fh

    arr = np.asarray(
        [
            [RNG.randrange(cs.field.modulus) for _ in range(cs.ncoords)]
            for _ in range(n)
        ],
        dtype=object,
    )
    return jnp.asarray(fh.encode(cs.field, arr))


def _to_rows(cs, pts):
    """(n, C, L) device points -> kernel row-list layout (C lists of L
    (1, n) tiles) — exactly what _rows_in produces from a (C·L, B) ref."""
    L, C = cs.field.limbs, cs.ncoords
    return tuple(
        [pts[:, c, i][None, :] for i in range(L)] for c in range(C)
    )


def _from_rows(cs, rows):
    L, C = cs.field.limbs, cs.ncoords
    return jnp.stack(
        [jnp.concatenate([rows[c][i] for i in range(L)], axis=0).T for c in range(C)],
        axis=-2,
    )


@pytest.mark.parametrize("cs", TOY_CURVES, ids=lambda c: c.kind)
def test_toy_add_rows_matches_xla(cs):
    p = _toy_points_dev(cs, 9)
    q = _toy_points_dev(cs, 9)
    got = _from_rows(cs, pp._add_rows(cs, _to_rows(cs, p), _to_rows(cs, q)))
    want = gd._add_xla(cs, p, q)
    assert jnp.all(got == want)


@pytest.mark.parametrize("cs", TOY_CURVES, ids=lambda c: c.kind)
def test_toy_double_rows_matches_xla(cs):
    p = _toy_points_dev(cs, 9)
    got = _from_rows(cs, pp._double_rows(cs, _to_rows(cs, p)))
    want = gd._double_xla(cs, p)
    assert jnp.all(got == want)


@pytest.mark.parametrize("cs", TOY_CURVES, ids=lambda c: c.kind)
def test_toy_identity_select_rows(cs):
    """_identity_rows encodes the identity; _select_rows picks per-lane."""
    p = _toy_points_dev(cs, 9)
    rows = _to_rows(cs, p)
    ident = pp._identity_rows(cs, rows[0][0])
    got_ident = _from_rows(cs, tuple(list(c) for c in ident))
    want_ident = gd.identity(cs, (9,))
    assert jnp.all(got_ident == want_ident)
    bit = jnp.asarray([[1, 0, 1, 0, 1, 0, 1, 0, 1]], jnp.uint32)
    sel = _from_rows(cs, pp._select_rows(bit, rows, ident))
    want_sel = gd.select(bit[0] != 0, p, want_ident)
    assert jnp.all(sel == want_sel)


def test_toy_field_rows_match_xla():
    """mod_mul/add/sub row functions vs fields.device on the toy field."""
    from dkg_tpu.fields import device as fd
    from dkg_tpu.fields import host as fh
    from dkg_tpu.ops import pallas_field as pf

    fs = TOY_FS
    xs = [RNG.randrange(fs.modulus) for _ in range(64)]
    ys = [RNG.randrange(fs.modulus) for _ in range(64)]
    a = jnp.asarray(fh.encode(fs, xs))
    b = jnp.asarray(fh.encode(fs, ys))
    rows_a = [a.T[i : i + 1, :] for i in range(fs.limbs)]
    rows_b = [b.T[i : i + 1, :] for i in range(fs.limbs)]

    def collect(rows):
        return jnp.concatenate(rows, axis=0).T

    assert jnp.all(collect(pf.mod_mul_rows(fs, rows_a, rows_b)) == fd.mul(fs, a, b))
    assert jnp.all(collect(pf.mod_add_rows(fs, rows_a, rows_b)) == fd.add(fs, a, b))
    assert jnp.all(collect(pf.mod_sub_rows(fs, rows_a, rows_b)) == fd.sub(fs, a, b))


# --------------------------------------------------------------------------
# full-kernel parity on a real TPU backend (Mosaic)
# --------------------------------------------------------------------------

needs_tpu = pytest.mark.skipif(
    not ON_TPU, reason="pallas_call plumbing: Mosaic-only (interpret compile is pathological here)"
)


@needs_tpu
@pytest.mark.parametrize("curve", ["ristretto255", "secp256k1"])
def test_kernel_add_matches_xla_tpu(curve):
    cs = gd.ALL_CURVES[curve]
    host_group = gh.ALL_GROUPS[curve]
    pts = [
        host_group.scalar_mul(host_group.random_scalar(RNG), host_group.generator())
        for _ in range(5)
    ] + [host_group.identity()]
    qts = [
        host_group.scalar_mul(host_group.random_scalar(RNG), host_group.generator())
        for _ in range(5)
    ] + [host_group.identity()]
    p_dev = gd.from_host(cs, pts)
    q_dev = gd.from_host(cs, qts)
    got = pp.pt_add(cs, p_dev, q_dev, interpret=False)
    want = gd._add_xla(cs, p_dev, q_dev)
    for a, b in zip(gd.to_host(cs, np.asarray(got)), gd.to_host(cs, np.asarray(want))):
        assert host_group.eq(a, b)


@needs_tpu
@pytest.mark.parametrize("curve", ["secp256k1", "ristretto255"])
def test_kernel_window_and_ladder_tpu(curve):
    # ristretto255 since PR 42: the multi-op Edwards bodies compile on the
    # v5e in seconds in their block form (the round-4 hang was of a body
    # traced again for every batch size) and every curve runs them
    # (groups.device.point_kernel_tier).
    cs = gd.ALL_CURVES[curve]
    host_group = gh.ALL_GROUPS[curve]
    pts = gd.from_host(
        cs,
        [
            host_group.scalar_mul(host_group.random_scalar(RNG), host_group.generator())
            for _ in range(6)
        ],
    )
    ent = gd.from_host(
        cs,
        [
            host_group.scalar_mul(host_group.random_scalar(RNG), host_group.generator())
            for _ in range(6)
        ],
    )
    got_w = pp.pt_window_step(cs, pts, ent, 4, interpret=False)
    want_w = pts
    for _ in range(4):
        want_w = gd._double_xla(cs, want_w)
    want_w = gd._add_xla(cs, want_w, ent)
    assert bool(jnp.all(gd.eq(cs, got_w, want_w)))

    xs = jnp.asarray([0, 1, 5, 9, 12, 15], jnp.uint32)
    nbits = 4
    got_l = pp.pt_ladder_mul_add(cs, pts, ent, xs, nbits, interpret=False)
    acc = gd.identity(cs, (6,))
    for i in range(nbits - 1, -1, -1):
        acc = gd._double_xla(cs, acc)
        acc = gd.select((xs >> i) & 1 != 0, gd._add_xla(cs, acc, pts), acc)
    want_l = gd._add_xla(cs, acc, ent)
    assert bool(jnp.all(gd.eq(cs, got_l, want_l)))


@pytest.mark.parametrize("cs", TOY_CURVES, ids=lambda c: c.kind)
def test_toy_madd_rows_matches_xla(cs):
    """_madd_rows == _madd_xla == _add_xla when the second operand's Z
    coordinate is 1 (the affine-table contract of fixed_base_mul)."""
    p = _toy_points_dev(cs, 9)
    q = np.asarray(_toy_points_dev(cs, 9)).copy()
    z_one = np.zeros(cs.field.limbs, np.uint32)
    z_one[0] = 1
    q[:, 2, :] = z_one  # force Z2 = 1 (coordinate index 2 on both kinds)
    q = jnp.asarray(q)
    got = _from_rows(cs, pp._madd_rows(cs, _to_rows(cs, p), _to_rows(cs, q)))
    assert jnp.all(got == gd._madd_xla(cs, p, q))
    assert jnp.all(got == gd._add_xla(cs, p, q))
