"""Device group-layer parity tests: batched limb point ops vs host oracle.

CPU-vs-TPU bit-exactness is the SURVEY §4 addition over the reference's
internal-consistency-only test style; every device result is decoded and
compared to the Python-int oracle in projective (torsion-safe) equality.
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from dkg_tpu.fields import host as fh
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh

pytestmark = pytest.mark.slow  # compile-heavy: nightly/device tier

RNG = random.Random(0xDE71CE)

CURVES = [gd.RISTRETTO255, gd.SECP256K1, gd.BLS12_381_G1]
CURVE_IDS = [c.name for c in CURVES]


def hostg(cs):
    return gh.ALL_GROUPS[cs.name]


def rand_points(cs, n):
    g = hostg(cs)
    return [g.scalar_mul(g.random_scalar(RNG), g.generator()) for _ in range(n)]


def assert_eq_host(cs, dev_pts, host_pts):
    g = hostg(cs)
    got = gd.to_host(cs, np.asarray(dev_pts))
    assert len(got) == len(host_pts)
    for a, b in zip(got, host_pts):
        assert g.eq(a, b)


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_add_double_neg_parity(cs):
    g = hostg(cs)
    ps = rand_points(cs, 6) + [g.identity()]
    qs = rand_points(cs, 6) + [g.identity()]
    dp, dq = gd.from_host(cs, ps), gd.from_host(cs, qs)
    assert_eq_host(cs, gd.add(cs, dp, dq), [g.add(a, b) for a, b in zip(ps, qs)])
    assert_eq_host(cs, gd.double(cs, dp), [g.add(a, a) for a in ps])
    assert_eq_host(cs, gd.neg(cs, dp), [g.neg(a) for a in ps])
    # complete-formula edge cases: P+P, P+(-P), P+0, 0+0
    edge_p = [ps[0], ps[1], ps[2], g.identity()]
    edge_q = [ps[0], g.neg(ps[1]), g.identity(), g.identity()]
    de_p, de_q = gd.from_host(cs, edge_p), gd.from_host(cs, edge_q)
    assert_eq_host(
        cs, gd.add(cs, de_p, de_q), [g.add(a, b) for a, b in zip(edge_p, edge_q)]
    )


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_eq_device(cs):
    g = hostg(cs)
    ps = rand_points(cs, 4)
    dp = gd.from_host(cs, ps)
    dq = gd.from_host(cs, [ps[0], ps[1], ps[3], g.identity()])
    got = np.asarray(gd.eq(cs, dp, dq))
    assert got.tolist() == [True, True, False, False]
    # projective scaling invariance: compare against doubled-Z representation
    dbl = gd.add(cs, dp, gd.identity(cs, (4,)))
    assert np.asarray(gd.eq(cs, dp, dbl)).all()


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_scalar_mul_parity(cs):
    g = hostg(cs)
    ks = [0, 1, 2, g.scalar_field.modulus - 1] + [g.random_scalar(RNG) for _ in range(4)]
    ps = rand_points(cs, len(ks))
    dk = jnp.asarray(fh.encode(cs.scalar, ks))
    dp = gd.from_host(cs, ps)
    assert_eq_host(
        cs, gd.scalar_mul(cs, dk, dp), [g.scalar_mul(k, p) for k, p in zip(ks, ps)]
    )


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_fixed_base_mul_parity(cs):
    g = hostg(cs)
    table = gd.fixed_base_table(cs, g.generator())
    ks = [0, 1, g.scalar_field.modulus - 1] + [g.random_scalar(RNG) for _ in range(5)]
    dk = jnp.asarray(fh.encode(cs.scalar, ks))
    assert_eq_host(
        cs,
        gd.fixed_base_mul(cs, table, dk),
        [g.scalar_mul(k, g.generator()) for k in ks],
    )


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_msm_parity(cs):
    g = hostg(cs)
    batch, m = 3, 5
    ks = [[g.random_scalar(RNG) for _ in range(m)] for _ in range(batch)]
    ps = [rand_points(cs, m) for _ in range(batch)]
    dk = jnp.asarray(fh.encode(cs.scalar, ks))  # (batch, m, L)
    dp = jnp.stack([gd.from_host(cs, row) for row in ps])  # (batch, m, C, L)
    got = gd.msm(cs, dk, dp)  # (batch, C, L)
    expect = [g.msm(krow, prow) for krow, prow in zip(ks, ps)]
    assert_eq_host(cs, got, expect)


def test_generator_and_identity_device():
    for cs in CURVES:
        g = hostg(cs)
        assert g.eq(gd.to_host(cs, gd.generator(cs, (1,)))[0], g.generator())
        assert g.eq(gd.to_host(cs, gd.identity(cs, (1,)))[0], g.identity())


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_madd_matches_add_on_affine_operand(cs):
    """madd (mixed add, Z2=1) == add on affine-normalised second
    operands, including P = identity; Edwards also Q = identity."""
    g = hostg(cs)
    pts_p = rand_points(cs, 4) + [g.identity()]
    pts_q = rand_points(cs, 5)
    p_dev = gd.from_host(cs, pts_p)
    # force a non-trivial Z on P by adding a point to itself first
    p_dev = gd._double_xla(cs, p_dev)
    q_aff = jnp.asarray(
        np.stack([gd._affine_limbs(cs, g, q) for q in pts_q])
    )
    got = gd._madd_xla(cs, p_dev, q_aff)
    want = gd._add_xla(cs, p_dev, q_aff)
    assert np.asarray(gd.eq(cs, got, want)).all()
    if cs.kind == "edwards":
        ident_aff = jnp.asarray(
            np.stack([gd._affine_limbs(cs, g, g.identity())] * 5)
        )
        got_i = gd._madd_xla(cs, p_dev, ident_aff)
        assert np.asarray(gd.eq(cs, got_i, p_dev)).all()


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_device_built_table_matches_host_table(cs):
    """fixed_base_table_dev(window=8) is bit-identical to the host-built
    table — same affine normalisation, same identity convention."""
    g = hostg(cs)
    base = g.scalar_mul(g.random_scalar(RNG), g.generator())
    dev = np.asarray(gd.fixed_base_table_dev(cs, base, window=8))
    host = gd._fixed_table_np(cs, gd.base_key(cs, base), 8)
    np.testing.assert_array_equal(dev, host)


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_composed_table_matches_host_table(cs):
    """The wide-window COMPOSITION build (T16[w][d] = T8[2w][lo] +
    T8[2w+1][hi], one batched add) is bit-identical to the host build.
    Exercised at window=8 (composed from two 4-bit half-tables) so the
    production window-16 code path is fully covered at CPU-test scale."""
    g = hostg(cs)
    base = g.scalar_mul(g.random_scalar(RNG), g.generator())
    key = gd.base_key(cs, base)
    dev = np.asarray(gd.affine_canon(cs, gd._compose_table_dev(cs, key, 8)))
    host = gd._fixed_table_np(cs, key, 8)
    np.testing.assert_array_equal(dev, host)


@pytest.mark.skipif(
    __import__("jax").default_backend() != "tpu",
    reason="65536-entry table build is a TPU-scale job (minutes on 1 CPU core)",
)
def test_fixed_base_mul_wide_window_matches_host_oracle():
    """16-bit-window device tables drive fixed_base_mul to the same
    values as the host scalar-mult oracle.  The w=8 device-vs-host table
    parity test covers the identical build pipeline on CPU; this runs
    the production window width on the real chip."""
    cs = gd.SECP256K1
    g = hostg(cs)
    base = g.generator()
    table = gd.fixed_base_table_dev(cs, base, window=16)
    ks = [0, 1, 2, g.scalar_field.modulus - 1, g.random_scalar(RNG)]
    import dkg_tpu.fields.host as fh

    k_dev = jnp.asarray(fh.encode(cs.scalar, ks))
    got = gd.to_host(cs, np.asarray(gd.fixed_base_mul(cs, table, k_dev)))
    for k, pt in zip(ks, got):
        assert g.eq(pt, g.scalar_mul(k, base)), k


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_fixed_base_mul_identity_base(cs):
    """A table built on the identity base yields the identity for every
    scalar (the Z=0 entry mask, not just digit 0, guards the mixed
    add)."""
    g = hostg(cs)
    table = jnp.asarray(gd._fixed_table_np(cs, gd.base_key(cs, g.identity())))
    import dkg_tpu.fields.host as fh

    ks = [0, 1, g.random_scalar(RNG)]
    out = gd.to_host(
        cs, np.asarray(gd.fixed_base_mul(cs, table, jnp.asarray(fh.encode(cs.scalar, ks))))
    )
    for pt in out:
        assert g.eq(pt, g.identity())


@pytest.mark.parametrize("cs", CURVES, ids=CURVE_IDS)
def test_affine_canon_is_representation_independent(cs):
    """affine_canon maps every projective representation of a group
    element to ONE canonical limb array (the transcript-digest
    requirement: rho must not depend on which addition schedule
    produced the commitments), and maps zero-Z lanes to the canonical
    identity."""
    g = hostg(cs)
    pm = cs.field.modulus
    pts, scaled = [], []
    for _ in range(5):
        p = g.scalar_mul_vartime(g.random_scalar(RNG), g.generator())
        z = RNG.randrange(1, pm)
        pts.append(p)
        scaled.append(tuple(c * z % pm for c in p))
    if cs.kind != "edwards":
        pts.append(g.identity())
        scaled.append((0, RNG.randrange(1, pm), 0))  # scaled identity rep
    a = gd.affine_canon(cs, gd.from_host(cs, pts))
    b = gd.affine_canon(cs, gd.from_host(cs, scaled))
    assert (np.asarray(a) == np.asarray(b)).all()
    for orig, canon in zip(pts, gd.to_host(cs, np.asarray(a))):
        assert g.eq(orig, canon)


def test_the_point_kernel_tier_follows_the_fused_kernels(monkeypatch):
    """The tier of the point kernels follows the fused kernels, whatever
    the curve (PR 42 decided Edwards on the v5e: no switch of its own),
    and ``point_kernel_tier`` says it in the labels the engine books."""
    monkeypatch.delenv("DKG_TPU_MSM", raising=False)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    assert gd.point_kernel_tier() == {"tier": "fused", "msm": "straus"}
    monkeypatch.setenv("DKG_TPU_MSM", "pippenger")
    assert gd.point_kernel_tier()["msm"] == "pippenger"
    monkeypatch.delenv("DKG_TPU_MSM")
    monkeypatch.setenv("DKG_TPU_PALLAS", "0")
    assert gd.point_kernel_tier() == {"tier": "composed", "msm": "pippenger"}
    monkeypatch.setenv("DKG_TPU_MSM", "fast")
    with pytest.raises(ValueError, match="DKG_TPU_MSM"):
        gd.point_kernel_tier()


@pytest.mark.parametrize("cs", [gd.RISTRETTO255, gd.SECP256K1], ids=["ristretto255", "secp256k1"])
def test_the_fused_tier_is_the_composition_and_the_host_group(cs, monkeypatch):
    """With the kernels on, the window step is ONE ``pt_window_step`` and
    every Horner step ONE ``pt_ladder_mul_add``, on Edwards as on
    Weierstrass, and both equal the XLA composition limb for limb and
    ``groups/host.py`` as group elements.  The two Pallas entry points
    are answered by their XLA twins (an interpreted multi-op body is
    pathological on the CPU); the bodies themselves are held by
    ``test_pallas_point.py`` and, on the chip,
    ``test_kernel_window_and_ladder_tpu``."""
    from dkg_tpu.ops import pallas_point as pp

    g = hostg(cs)
    calls = []

    def window(c, acc, entry, n_doubles=4, **kw):
        calls.append(("window", n_doubles))
        for _ in range(n_doubles):
            acc = gd._double_xla(c, acc)
        return gd._add_xla(c, acc, entry)

    def ladder(c, p, addend, x, nbits, **kw):
        calls.append(("ladder", nbits))
        acc = gd.identity(c, p.shape[:-2])
        for i in range(nbits - 1, -1, -1):
            acc = gd._double_xla(c, acc)
            acc = gd.select((x >> i) & 1 != 0, gd._add_xla(c, acc, p), acc)
        return gd._add_xla(c, acc, addend)

    monkeypatch.setattr(pp, "pt_window_step", window)
    monkeypatch.setattr(pp, "pt_ladder_mul_add", ladder)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    ks = [g.random_scalar(RNG) for _ in range(3)]
    es = [g.random_scalar(RNG) for _ in range(3)]
    pts = gd.from_host(cs, [g.scalar_mul(k, g.generator()) for k in ks])
    ent = gd.from_host(cs, [g.scalar_mul(e, g.generator()) for e in es])
    q = g.scalar_field.modulus

    got = gd.window_step(cs, pts, ent, 4, gd.fused_kernels_active())
    assert calls == [("window", 4)]
    composed = gd.window_step(cs, pts, ent, 4, False)
    assert (np.asarray(got) == np.asarray(composed)).all()
    for k, e, pt in zip(ks, es, gd.to_host(cs, np.asarray(got))):
        assert g.eq(pt, g.scalar_mul((16 * k + e) % q, g.generator()))

    # Horner over 3 point coefficients at x = 1, 2, 5 (3 bits): a ladder a coefficient
    calls.clear()
    xs = jnp.asarray([1, 2, 5], jnp.uint32)
    coeffs = jnp.stack([pts, ent, pts], axis=-3)  # (3 lanes, T=3, C, L), low order first
    got = gd.eval_point_poly.__wrapped__(cs, coeffs, xs, 3)
    assert calls == [("ladder", 3)]  # the scan's body, traced once
    for x, k, e, pt in zip((1, 2, 5), ks, es, gd.to_host(cs, np.asarray(got))):
        assert g.eq(pt, g.scalar_mul((k + e * x + k * x * x) % q, g.generator()))
