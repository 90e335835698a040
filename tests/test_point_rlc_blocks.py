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

import collections
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
from dkg_tpu.groups import precompute as gp
from dkg_tpu.ops import pallas_point as pp
from dkg_tpu.service import engine
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
        ("ristretto255", 5, 3, None),  # C = 4: extended coordinates, 64-row blocks
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


def _lanes_moved(before, after, stack):
    """What ``point_rlc_lanes_traced_total`` of one stack width gained
    between two snapshots, by (part, kind), points before acc."""

    def read(snap, part, kind):
        return snap["counters"].get(
            f'point_rlc_lanes_traced_total{{kind="{kind}",part="{part}",stack="{stack}"}}', 0
        )

    return {
        (part, kind): read(after, part, kind) - read(before, part, kind)
        for part in ("points", "acc")
        for kind in ("live", "block")
    }


def _stack_case(cs, g, k, m, cols, seed):
    """k ceremonies' :func:`_case`, and their points and weights stacked."""
    rows = [_case(cs, g, m, cols, seed=seed + s) for s in range(k)]
    return rows, jnp.stack([_weights(cs, w) for _, _, w in rows]), jnp.stack([p for _, p, _ in rows])


def _bumped(cs, shares, index):
    """``shares`` with 1 added to the share at ``index``."""
    one = np.zeros(cs.scalar.limbs, np.uint32)
    one[0] = 1
    return shares.at[index].set(fd.add(cs.scalar, shares[index], jnp.asarray(one)))


@pytest.mark.parametrize(
    "curve,k,m,cols",
    [("secp256k1", k, m, cols) for k in (1, 2, 8) for m, cols in ((16, 6), (32, 9), (6, 5))]
    + [
        ("secp256k1", 3, 6, 5),  # was test_block_form_under_vmap_with_a_rho_per_row: the convoy as a map
        ("ristretto255", 1, 6, 5),  # C = 4: extended coordinates, 64-row blocks
        ("ristretto255", 2, 16, 6),
        ("ristretto255", 8, 6, 5),
        ("ristretto255", 2, 32, 9),  # 576 lanes: every level of the tree a ragged slice
    ],
)
def test_stacked_block_form_with_a_rho_per_ceremony_and_dealer(monkeypatch, curve, k, m, cols):
    """``service.engine._verify_stack``'s point-RLC: a convoy's k
    ceremonies, each with its own rho and its own commitments, packed
    (dealer, ceremony, column) onto joint lane blocks, against the host
    oracle and against the ``vmap`` of the single form that the stack
    was before (one padded block a ceremony); and the occupancy the
    stacked body books."""
    cs = gd.ALL_CURVES[curve]
    g = _host_kernels(monkeypatch, cs)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    for name in ("DKG_TPU_RLC", "DKG_TPU_RLC_CHUNK"):
        monkeypatch.delenv(name, raising=False)
    rows, weights, points = _stack_case(cs, g, k, m, cols, seed=100 * k)
    before = REGISTRY.snapshot()
    d = ce._point_rlc(cs, weights, points, NBITS)
    after = REGISTRY.snapshot()
    mapped = jax.vmap(lambda w1, p1: ce._point_rlc(cs, w1, p1, NBITS))(weights, points)
    assert d.shape == mapped.shape == (k, cols, cs.ncoords, cs.field.limbs)
    for i, ((ks, _, w), d_row, m_row) in enumerate(zip(rows, d, mapped)):
        want = _oracle(g, ks, w)
        for l, (got, exp, one) in enumerate(zip(gd.to_host(cs, d_row), want, gd.to_host(cs, m_row))):
            assert g.eq(got, exp), (curve, k, m, cols, i, l)
            assert g.eq(one, exp), (curve, k, m, cols, i, l)

    blocks = lambda lanes: -(-lanes // pp.BLOCK) * pp.BLOCK
    booked = list(_lanes_moved(before, after, k).values())
    assert booked == [k * m * cols, blocks(k * m * cols), k * cols, blocks(k * cols)]
    if (k, m, cols) == (8, 16, 6):  # the fleet's width-8 stack, where a block a ceremony was 768 of 1024 and 48 of 1024
        assert booked == [768, 768, 48, 128]
    traced = f'point_rlc_traced_total{{form="blocks",schedule="straus",stack="{k}"}}'
    assert after["counters"].get(traced, 0) - before["counters"].get(traced, 0) == 1


def test_stacked_block_form_chunks_its_columns_counting_the_ceremonies(monkeypatch):
    """The chunk rule of ``_point_rlc`` on a stack: the column axis is
    the third, a chunk holds that many columns of EVERY ceremony, the
    map's body and the ragged tail are two traced bodies."""
    cs = gd.ALL_CURVES["secp256k1"]
    g = _host_kernels(monkeypatch, cs)
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    monkeypatch.setenv("DKG_TPU_RLC_CHUNK", "3")
    k, m, cols = 2, 4, 7
    rows, weights, points = _stack_case(cs, g, k, m, cols, seed=40)
    before = REGISTRY.snapshot()
    d = ce._point_rlc(cs, weights, points, NBITS)
    after = REGISTRY.snapshot()
    assert d.shape == (k, cols, cs.ncoords, cs.field.limbs)
    for (ks, _, w), d_row in zip(rows, d):
        for got, exp in zip(gd.to_host(cs, d_row), _oracle(g, ks, w)):
            assert g.eq(got, exp)
    booked = _lanes_moved(before, after, k)
    assert booked[("points", "live")] == k * m * (3 + 1)  # the body's three columns and the tail's one
    assert booked[("acc", "live")] == k * (3 + 1)


def _rlc_on_blocks(monkeypatch):
    """``_point_rlc`` traced with the fused switch on and the rest of
    the program on the CPU's XLA path; ``verify_batch`` un-jitted, so
    that nothing is served from, or left in, another test's cache.
    Returns the counter keys the RLC moved."""
    real, forms = ce._point_rlc, []

    def on_blocks(*args):
        before = REGISTRY.snapshot()["counters"]
        with monkeypatch.context() as mp:
            mp.setenv("DKG_TPU_PALLAS", "1")
            out = real(*args)
        forms.extend(k for k, v in REGISTRY.snapshot()["counters"].items() if v != before.get(k, 0))
        return out

    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    monkeypatch.setattr(ce, "_point_rlc", on_blocks)
    monkeypatch.setattr(ce, "verify_batch", ce.verify_batch.__wrapped__)
    return forms


def test_verify_batch_on_the_block_form_blames_the_tampered_recipient(monkeypatch):
    """Through ``verify_batch``: one share altered on its way to one
    recipient, and that recipient alone reads False.  The fused switch is
    on while ``_point_rlc`` is traced and off around it, so the rest of
    the program is the CPU's XLA path."""
    c = ce.BatchedCeremony("ristretto255", 5, 2, b"pr31-blocks", random.Random(31))
    cfg, cs = c.cfg, c.cfg.cs
    _host_kernels(monkeypatch, cs)
    a, e, s, r = ce.deal(cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    rho = jnp.asarray(ce.derive_rho(cfg, a, e, s, r, NBITS))
    forms = _rlc_on_blocks(monkeypatch)
    assert np.asarray(ce.verify_batch(cfg, e, s, r, rho, NBITS, c.g_table, c.h_table)).all()
    assert any('form="blocks"' in k for k in forms), forms
    ok = np.asarray(ce.verify_batch(cfg, e, _bumped(cs, s, (1, 3)), r, rho, NBITS, c.g_table, c.h_table))
    assert ok.tolist() == [True, True, True, False, True]


def test_verify_stack_blames_the_tampered_ceremony_and_recipient(monkeypatch):
    """Through ``_verify_stack``: a convoy of three, one share altered
    on its way to recipient 3 of ceremony 2 (of 3, counted from 1), and
    that (ceremony, recipient) alone reads False: the joint packing
    still says which ceremony and which recipient."""
    convoy = [ce.BatchedCeremony("ristretto255", 5, 2, b"pr39-stack", random.Random(390 + i)) for i in range(3)]
    c0 = convoy[0]
    cfg, cs = c0.cfg, c0.cfg.cs
    _host_kernels(monkeypatch, cs)
    dealt = [ce.deal(cfg, c.coeffs_a, c.coeffs_b, c0.g_table, c0.h_table) for c in convoy]
    rho = jnp.stack([jnp.asarray(ce.derive_rho(cfg, *d, NBITS)) for d in dealt])
    assert not np.array_equal(rho[0], rho[1])  # a rho per ceremony
    _, e, s, r = (jnp.stack(x) for x in zip(*dealt))
    forms = _rlc_on_blocks(monkeypatch)
    verify = engine._verify_stack.__wrapped__
    ok = np.asarray(verify(cfg, e, s, r, rho, NBITS, c0.g_table, c0.h_table))
    assert ok.shape == (3, 5) and ok.all()
    assert any('form="blocks"' in k and 'stack="3"' in k for k in forms), forms
    ok = np.asarray(verify(cfg, e, _bumped(cs, s, (1, 1, 2)), r, rho, NBITS, c0.g_table, c0.h_table))
    want = np.ones((3, 5), bool)
    want[1, 2] = False
    assert ok.tolist() == want.tolist()


def _host_deal(g, h, n_real, n, t, rng):
    """One ceremony's round-1 tensors from ``groups/host.py`` and Python
    ints, ``n_real`` dealers padded to the bucket's ``n`` as
    ``engine.pad_coeffs`` pads them: a phantom dealer is the zero
    polynomial twice, so its commitments are the identity and its
    shares 0; every dealer's shares go to all n recipients."""
    q = g.scalar_field.modulus
    ident = g.scalar_mul(0, g.generator())
    e, s, r = [], [], []
    for j in range(n):
        a = [rng.randrange(q) if j < n_real else 0 for _ in range(t + 1)]
        b = [rng.randrange(q) if j < n_real else 0 for _ in range(t + 1)]
        e.append([g.add(g.scalar_mul(x, g.generator()), g.scalar_mul(y, h)) if j < n_real else ident for x, y in zip(a, b)])
        s.append([sum(c * pow(i, l, q) for l, c in enumerate(a)) % q for i in range(1, n + 1)])
        r.append([sum(c * pow(i, l, q) for l, c in enumerate(b)) % q for i in range(1, n + 1)])
    return e, s, r


def test_verify_stack_with_a_padded_member_beside_a_full_one(monkeypatch):
    """Bucket (32,8) as the mix stacks it: a (24,8) ceremony padded to
    32 dealers beside a (32,8) one.  The eight phantom dealers are
    identity commitments with rho lanes of their own, on lanes between
    the real dealers' of both ceremonies (2 x 32 x 9 = 576 lanes, every
    level of the tree a ragged slice): they stay inert and all 2 x 32
    recipients verify; a real dealer's altered share is still caught."""
    cs = gd.ALL_CURVES["secp256k1"]
    g = _host_kernels(monkeypatch, cs)
    cfg = ce.CeremonyConfig("secp256k1", 32, 8)
    rng = random.Random(3924)
    h = g.scalar_mul(rng.randrange(1, g.scalar_field.modulus), g.generator())
    g_table, h_table = gp.generator_table(cs), gp.base_table(cs, h)
    members = [_host_deal(g, h, n_real, 32, 8, rng) for n_real in (24, 32)]
    e = jnp.stack([gd.from_host(cs, [p for row in m[0] for p in row]).reshape(32, 9, cs.ncoords, cs.field.limbs) for m in members])
    s, r = (jnp.asarray(np.stack([fh.encode(cs.scalar, np.asarray(m[i], dtype=object)) for m in members]), jnp.uint32) for i in (1, 2))
    rho = jnp.stack([_weights(cs, [rng.randrange(1, 1 << NBITS) for _ in range(32)]) for _ in members])
    forms = _rlc_on_blocks(monkeypatch)
    verify = engine._verify_stack.__wrapped__
    ok = np.asarray(verify(cfg, e, s, r, rho, NBITS, g_table, h_table))
    assert ok.shape == (2, 32) and ok.all()
    assert any('form="blocks"' in k and 'stack="2"' in k for k in forms), forms
    bad = _bumped(cs, s, (0, 23, 30))  # the last real dealer's share, to a phantom recipient
    ok = np.asarray(verify(cfg, e, bad, r, rho, NBITS, g_table, h_table))
    want = np.ones((2, 32), bool)
    want[0, 30] = False
    assert ok.tolist() == want.tolist()


def _primitives(jaxpr, out=None):
    """Every equation of a jaxpr and of the jaxprs it calls (scan and
    ``jit`` bodies; not the kernels' own), counted by primitive."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        out[eqn.primitive.name] += 1
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _primitives(sub, out)
    return out


#: ``verify_batch`` WITHOUT the ceremony axis, traced with the fused
#: switch on, as counted on the parent of PR 39 (39aef4d, where the
#: stack was a ``vmap`` around it): the equations by primitive, the
#: kernel launches and the schedule bodies it books.  The same tree's
#: StableHLO for a described v5e was compared with the parent's text at
#: (16,5), at a chunked (32,8), at 24 limbs and at (1024,341): equal but
#: for the kernels' serialized bodies, which carry source lines
#: (CHANGES.md, PR 39).  A PR that changes the width-1 program on
#: purpose counts again and says so.
_WIDTH_1 = {
    (16, 5, None): (
        {"add": 83, "and": 69, "broadcast_in_dim": 160, "concatenate": 26, "convert_element_type": 61,
         "dot_general": 18, "eq": 17, "gather": 8, "iota": 1, "jit": 80, "lt": 6, "mul": 30, "ne": 10, "or": 6,
         "pad": 29, "pallas_call": 10, "reduce_and": 2, "reduce_or": 2, "reshape": 38, "rev": 2, "scan": 29,
         "select_n": 31, "shift_left": 12, "shift_right_logical": 74, "slice": 138, "squeeze": 65, "sub": 16,
         "transpose": 68},
        {"pt_add": 6, "pt_window_step": 1, "pt_ladder_mul_add": 1, "pt_madd": 1},
        1,
    ),
    (32, 8, 4): (  # DKG_TPU_RLC_CHUNK=4: two chunks through the map and a tail of one column
        {"add": 84, "and": 70, "broadcast_in_dim": 202, "concatenate": 39, "convert_element_type": 62,
         "dot_general": 18, "dynamic_slice": 2, "eq": 32, "gather": 8, "iota": 2, "jit": 104, "lt": 7, "mul": 31,
         "ne": 10, "or": 6, "pad": 30, "pallas_call": 18, "reduce_and": 2, "reduce_or": 2, "reshape": 46, "rev": 3,
         "scan": 32, "select_n": 47, "shift_left": 12, "shift_right_logical": 75, "slice": 172, "squeeze": 95,
         "sub": 16, "transpose": 71},
        {"pt_add": 13, "pt_window_step": 2, "pt_ladder_mul_add": 1, "pt_madd": 1},
        2,
    ),
}


@pytest.mark.parametrize("n,t,chunk", list(_WIDTH_1))
def test_the_width_1_program_is_the_one_it_was(monkeypatch, n, t, chunk):
    """The stack's lane order is a function of the input's rank: with
    the ceremony axis absent ``verify_batch`` (both large cells, the
    steady cell's width-1 convoys, the heavy bucket,
    ``BatchedCeremony.run``, ``parallel/mesh.py``) traces to the
    program it traced to before the stack existed.  Traced, never
    compiled: the kernels stay ``pallas_call`` equations."""
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    monkeypatch.delenv("DKG_TPU_RLC", raising=False)
    if chunk is None:
        monkeypatch.delenv("DKG_TPU_RLC_CHUNK", raising=False)
    else:
        monkeypatch.setenv("DKG_TPU_RLC_CHUNK", str(chunk))
    cfg = ce.CeremonyConfig("secp256k1", n, t)
    cs = cfg.cs
    L, C, S = cs.field.limbs, cs.ncoords, cs.scalar.limbs
    u = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)
    table = u(S * 2, 256, C, L)
    before = REGISTRY.snapshot()["counters"]
    jaxpr = jax.make_jaxpr(
        lambda e, s, r, rho, g, h: ce.verify_batch.__wrapped__(cfg, e, s, r, rho, 128, g, h)
    )(u(n, t + 1, C, L), u(n, n, S), u(n, n, S), u(n, S), table, table)
    after = REGISTRY.snapshot()["counters"]
    moved = {k: v - before.get(k, 0) for k, v in after.items() if v != before.get(k, 0)}
    primitives, launches, bodies = _WIDTH_1[(n, t, chunk)]
    assert dict(_primitives(jaxpr.jaxpr)) == primitives
    assert {k: v for k, v in moved.items() if k.startswith("pallas_calls_total")} == {
        f'pallas_calls_total{{kernel="{name}"}}': count for name, count in launches.items()
    }
    assert moved['point_rlc_traced_total{form="blocks",schedule="straus",stack="1"}'] == bodies
    assert not any('stack="' in k and 'stack="1"' not in k for k in moved), moved
