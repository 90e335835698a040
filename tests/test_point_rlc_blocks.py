"""The point-RLC's Straus schedule in the point kernels' lane-block form
(``dkg.ceremony._straus_tiles``, the fused path) against the host oracle
``D_l = sum_j rho_j E_{j,l}`` and the ``bits`` schedule of the unfused path.

Everything the block form adds runs as it does on the chip: the one
conversion in and out, the table kept as blocks, the 16-way select on
the dealer's digit, the tree's block and lane slices, the window step
on blocks, the column chunks, the ``vmap`` of a convoy.  Only the three
launches it strings together (``pallas_point._add_call``,
``_double_call``, ``_window_call``) are answered by the host group law
through a callback: XLA:CPU does not compile their interpret-mode
bodies in any useful time (a (5,3) secp256k1 point-RLC was still in its
first scan after 40 minutes and 19 GB: PR 31; ``test_pallas_point.py``
has the same note).  The bodies did not change; their row functions are
held by ``test_pallas_point.py`` and the kernels on the chip by
``chip_smoke.py``.  The sums differ from the other schedules' in their
projective coordinates (another pairing order), so every comparison is
the host group's ``eq``, column by column.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.fields import device as fd
from dkg_tpu.fields import host as fh
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh
from dkg_tpu.ops import pallas_point as pp
from dkg_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.usefixtures("free_compiled_programs")

NBITS = 8


def _host_kernels(monkeypatch, cs):
    """The three block launches, each lane answered by ``groups/host.py``."""
    g = gh.ALL_GROUPS[cs.name]
    L, C = cs.field.limbs, cs.ncoords

    def lanes(t):
        flat = np.swapaxes(np.asarray(t), -1, -2).reshape(-1, C, L)
        return [tuple(int(c) for c in row) for row in fh.decode(cs.field, flat)]

    def blocks(points, like):
        ints = np.asarray([[int(c) for c in p] for p in points], dtype=object)
        flat = np.asarray(fh.encode(cs.field, ints), np.uint32)
        return np.swapaxes(flat.reshape(like.shape[:-2] + (like.shape[-1], C * L)), -1, -2)

    def call(fn, *ts):
        assert ts[0].shape[-2:] == (C * L, pp.BLOCK), ts[0].shape
        return jax.pure_callback(
            lambda *xs: blocks([fn(*ps) for ps in zip(*map(lanes, xs))], xs[0]),
            jax.ShapeDtypeStruct(ts[0].shape, jnp.uint32),
            *ts,
            vmap_method="broadcast_all",
        )

    def doubled(p, n):
        for _ in range(n):
            p = g.add(p, p)
        return p

    monkeypatch.setattr(pp, "_add_call", lambda c, p, q, interp: call(g.add, p, q))
    monkeypatch.setattr(pp, "_double_call", lambda c, p, n, interp: call(lambda a: doubled(a, n), p))
    monkeypatch.setattr(
        pp, "_window_call", lambda c, a, n, interp, e: call(lambda x, y: g.add(doubled(x, n), y), a, e)
    )
    return g


def _case(cs, g, m, cols, seed):
    """Points with an identity column entry, weights with a zero dealer."""
    rng = random.Random(seed)
    ks = [[rng.randrange(1, 1 << 20) for _ in range(cols)] for _ in range(m)]
    ks[0][0] = 0  # an identity among the points
    for j in range(m):
        ks[j][cols - 1] = 0  # a whole identity column
    base = g.generator()
    pts = [[g.scalar_mul(k, base) for k in row] for row in ks]
    flat = gd.from_host(cs, [p for row in pts for p in row])
    points = flat.reshape(m, cols, cs.ncoords, cs.field.limbs)
    w = [rng.randrange(1, 1 << NBITS) for _ in range(m)]
    w[m // 2] = 0  # a dealer whose every digit is 0
    return ks, points, w


def _oracle(g, ks, w):
    q = g.scalar_field.modulus
    return [
        g.scalar_mul_vartime(sum(wj * row[l] for wj, row in zip(w, ks)) % q, g.generator())
        for l in range(len(ks[0]))
    ]


def _weights(cs, w):
    return jnp.asarray(fh.encode(cs.scalar, w))


@pytest.mark.parametrize(
    "curve,m,cols,chunk",
    [
        ("secp256k1", 16, 6, None),  # the fleet's shape: one block, every level a lane slice
        ("secp256k1", 5, 3, None),  # m odd, under one block
        ("secp256k1", 8, 32, None),  # m a power of two, two blocks: whole-block halves, then lane slices
        ("secp256k1", 7, 21, None),  # m odd, 147 lanes: over one block with a ragged tail
        ("secp256k1", 4, 7, 3),  # DKG_TPU_RLC_CHUNK: two chunks through the map and a ragged last one
        ("ristretto255", 5, 3, None),  # C = 4, the pt_double + pt_add window step
        ("ristretto255", 8, 20, None),  # 160 lanes
        ("bls12_381_g1", 3, 5, None),  # 24 limbs
        ("bls12_381_g1", 4, 40, None),  # 160 lanes
    ],
)
def test_block_form_is_the_same_group_element(monkeypatch, curve, m, cols, chunk):
    cs = gd.ALL_CURVES[curve]
    g = _host_kernels(monkeypatch, cs)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    if chunk is None:
        monkeypatch.delenv("DKG_TPU_RLC_CHUNK", raising=False)
    else:
        monkeypatch.setenv("DKG_TPU_RLC_CHUNK", str(chunk))
    ks, points, w = _case(cs, g, m, cols, seed=m * 1000 + cols)
    before = REGISTRY.snapshot()
    d = gd.to_host(cs, ce._point_rlc(cs, _weights(cs, w), points, NBITS))
    after = REGISTRY.snapshot()
    want = _oracle(g, ks, w)
    assert len(d) == cols
    for l, (got, exp) in enumerate(zip(d, want)):
        assert g.eq(got, exp), (curve, m, cols, l)
    assert g.is_identity(d[cols - 1])

    def booked(snap):
        return sum(
            v for k, v in snap["counters"].items()
            if k.startswith("point_rlc_traced_total") and 'form="blocks"' in k and 'schedule="straus"' in k
        )

    bodies = 1 if chunk is None else 2  # a chunked call traces the map's body and the tail
    assert booked(after) - booked(before) == bodies


def test_block_form_agrees_with_the_bits_schedule_unfused(monkeypatch):
    """The parity leg of ``bench parity_check``: the schedule the CPU
    default can force, with no kernel in it."""
    cs = gd.ALL_CURVES["ristretto255"]
    g = _host_kernels(monkeypatch, cs)
    ks, points, w = _case(cs, g, 4, 2, seed=31)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    blocks = gd.to_host(cs, ce._point_rlc(cs, _weights(cs, w), points, NBITS))
    monkeypatch.setenv("DKG_TPU_PALLAS", "0")
    monkeypatch.setenv("DKG_TPU_RLC", "bits")
    bits = gd.to_host(cs, ce._point_rlc(cs, _weights(cs, w), points, NBITS))
    for l, (a, b) in enumerate(zip(blocks, bits)):
        assert g.eq(a, b), l


def test_block_form_under_vmap_with_a_rho_per_row(monkeypatch):
    """``service.engine._verify_stack``'s twin: a convoy is a ``vmap``
    over ceremonies, each with its own rho and its own commitments."""
    cs = gd.ALL_CURVES["secp256k1"]
    g = _host_kernels(monkeypatch, cs)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    rows = [_case(cs, g, 6, 5, seed=s) for s in (1, 2, 3)]
    points = jnp.stack([p for _, p, _ in rows])
    weights = jnp.stack([_weights(cs, w) for _, _, w in rows])
    d = jax.vmap(lambda w1, p1: ce._point_rlc(cs, w1, p1, NBITS))(weights, points)
    assert d.shape == (3, 5, cs.ncoords, cs.field.limbs)
    for (ks, _, w), d_row in zip(rows, d):
        for got, exp in zip(gd.to_host(cs, d_row), _oracle(g, ks, w)):
            assert g.eq(got, exp)


def test_verify_batch_on_the_block_form_blames_the_tampered_recipient(monkeypatch):
    """Through ``verify_batch``: one share altered on its way to one
    recipient, and that recipient alone reads False.  The fused switch is
    on while ``_point_rlc`` is traced and off around it, so the rest of
    the program is the CPU's XLA path."""
    c = ce.BatchedCeremony("ristretto255", 5, 2, b"pr31-blocks", random.Random(31))
    cfg, cs = c.cfg, c.cfg.cs
    _host_kernels(monkeypatch, cs)
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    a, e, s, r = ce.deal(cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    rho = jnp.asarray(ce.derive_rho(cfg, a, e, s, r, NBITS))
    real, forms = ce._point_rlc, []

    def on_blocks(*args):
        before = REGISTRY.snapshot()["counters"]
        with monkeypatch.context() as mp:
            mp.setenv("DKG_TPU_PALLAS", "1")
            out = real(*args)
        forms.extend(k for k, v in REGISTRY.snapshot()["counters"].items() if v != before.get(k, 0))
        return out

    monkeypatch.setattr(ce, "_point_rlc", on_blocks)
    verify = ce.verify_batch.__wrapped__  # traced here, never served from another test's cache
    assert np.asarray(verify(cfg, e, s, r, rho, NBITS, c.g_table, c.h_table)).all()
    assert any('form="blocks"' in k for k in forms), forms
    one = np.zeros(cs.scalar.limbs, np.uint32)
    one[0] = 1
    bad = s.at[1, 3].set(fd.add(cs.scalar, s[1, 3], jnp.asarray(one)))
    ok = np.asarray(verify(cfg, e, bad, r, rho, NBITS, c.g_table, c.h_table))
    assert ok.tolist() == [True, True, True, False, True]

