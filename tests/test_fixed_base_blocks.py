"""The fixed-base multiply in the point kernels' lane-block form
(``groups.device._fixed_base_mul_core`` with the fused kernels active)
against its tensor form, limb for limb, and against the host oracle
``k·B`` (``groups/host.py``).

Everything the block form adds runs as it does on the chip: the digits
padded to whole blocks, the row gather of a window's entries, rows ->
blocks, the accumulator kept as blocks, the identity mask read from the
entry's Z rows, the one conversion out, the ``vmap`` of a convoy.  Only
the launch it strings together (``pallas_point._madd_call``) is answered
by ``gd._madd_xla`` on the block's lanes: XLA:CPU does not compile the
kernel's interpret-mode body in any useful time
(``test_point_rlc_blocks.py`` has the same note), and the kernel's row
functions are that formula limb for limb (``test_pallas_point.py``
``test_toy_madd_rows_matches_xla``), so the two forms must agree in
every limb, not only as group elements.  Like the kernel, the formula is
NOT valid for a Weierstrass identity entry: a lane that read one comes
out of the launch wrong, and only the mask keeps it right.  The kernel
itself is held by ``tests/test_tpu_compile.py`` (the chip's compiler,
here) and by ``chip_smoke.py`` on the chip.
"""

from __future__ import annotations

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.fields import host as fh
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh
from dkg_tpu.ops import pallas_point as pp
from dkg_tpu.utils.metrics import REGISTRY

pytestmark = pytest.mark.usefixtures("free_compiled_programs")


def _xla_kernel(monkeypatch, cs):
    """``_madd_call`` on one (C·L, BLOCK) block, answered by ``gd._madd_xla``."""
    L, C = cs.field.limbs, cs.ncoords

    def lanes(t):
        assert t.shape == (C * L, pp.BLOCK), t.shape
        return jnp.reshape(t.T, (pp.BLOCK, C, L))

    def call(c, p_t, q_t, interp):
        return jnp.reshape(gd._madd_xla(c, lanes(p_t), lanes(q_t)), (pp.BLOCK, C * L)).T

    monkeypatch.setattr(pp, "_madd_call", call)
    return gh.ALL_GROUPS[cs.name]


def _table(cs, g, window, base=None):
    key = gd.base_key(cs, g.generator() if base is None else base)
    return jnp.asarray(gd._fixed_table_np(cs, key, window))


def _scalars(g, window, n, seed):
    """0, 1, q-1, then a scalar with window i all zero for every i, then
    random ones; a single lane takes q-1."""
    rng = random.Random(seed)
    q = g.scalar_field.modulus
    if n == 1:
        return [q - 1]
    nw = -(-q.bit_length() // 16) * (16 // window)
    mask = (1 << window) - 1
    holes = [g.random_scalar(rng) & ~(mask << (window * i)) for i in range(nw)]
    ks = [0, 1, q - 1] + holes + [g.random_scalar(rng) for _ in range(n)]
    return ks[:n]


def _limbs(cs, ks, shape):
    return jnp.asarray(fh.encode(cs.scalar, ks)).reshape(shape + (cs.scalar.limbs,))


def _fused(monkeypatch, on: bool):
    monkeypatch.setenv("DKG_TPU_PALLAS", "1" if on else "0")


def _booked(form, window):
    key = f'fixed_base_traced_total{{form="{form}",window="{window}"}}'
    return REGISTRY.snapshot()["counters"].get(key, 0)


@pytest.mark.parametrize("shape", [(1,), (96,), (128,), (130,), (5, 3)], ids=str)
@pytest.mark.parametrize(
    "curve,window",
    [("secp256k1", 4), ("secp256k1", 8), ("ristretto255", 8), ("bls12_381_g1", 8)],
)
def test_block_form_is_the_tensor_form_and_the_oracle(monkeypatch, curve, window, shape):
    """Lane counts under, at and over one block and a (m, t+1) batch:
    the padding lanes take digit 0 and are dropped on the way out."""
    cs = gd.ALL_CURVES[curve]
    g = _xla_kernel(monkeypatch, cs)
    n = int(np.prod(shape))
    ks = _scalars(g, window, n, seed=window * 1000 + n)
    table, k = _table(cs, g, window), _limbs(cs, ks, shape)
    _fused(monkeypatch, True)
    blocks = np.asarray(jax.jit(lambda k_: gd.fixed_base_mul(cs, table, k_))(k))
    _fused(monkeypatch, False)
    tensor = np.asarray(jax.jit(lambda k_: gd.fixed_base_mul(cs, table, k_))(k))
    assert blocks.shape == shape + (cs.ncoords, cs.field.limbs)
    np.testing.assert_array_equal(blocks, tensor)
    got = gd.to_host(cs, blocks.reshape(n, cs.ncoords, cs.field.limbs))
    for kk, pt in zip(ks, got):
        assert g.eq(pt, g.scalar_mul_vartime(kk, g.generator())), (curve, window, shape, kk)


@pytest.mark.parametrize("fused,form", [(True, "blocks"), (False, "tensor")])
def test_each_traced_body_books_its_form_and_window(monkeypatch, fused, form):
    """``fixed_base_traced_total{form, window}``: one a traced body, and
    a trace-time count, so a shape no other case of this file traces; a
    second call of the same shape is answered from the trace and books
    nothing."""
    cs = gd.ALL_CURVES["secp256k1"]
    g = _xla_kernel(monkeypatch, cs)
    table, k = _table(cs, g, 4), _limbs(cs, _scalars(g, 4, 7, seed=7), (7,))
    _fused(monkeypatch, fused)
    other_form = "tensor" if fused else "blocks"
    before, other = _booked(form, 4), _booked(other_form, 4)
    for _ in range(2):
        jax.jit(lambda k_: gd.fixed_base_mul(cs, table, k_))(k)
    assert _booked(form, 4) == before + 1
    assert _booked(other_form, 4) == other


@pytest.mark.parametrize("curve", ["secp256k1", "ristretto255", "bls12_381_g1"])
def test_identity_base_table_gives_the_identity(monkeypatch, curve):
    """Every entry of an identity base's table is the identity: on the
    Weierstrass curves the mask, read from the entry's Z rows in block
    form, must hold every lane in every window, not digit 0 alone."""
    cs = gd.ALL_CURVES[curve]
    g = _xla_kernel(monkeypatch, cs)
    table = _table(cs, g, 8, base=g.identity())
    ks = [0, 1, g.scalar_field.modulus - 1, g.random_scalar(random.Random(35))]
    _fused(monkeypatch, True)
    out = gd.fixed_base_mul(cs, table, _limbs(cs, ks, (4,)))  # eager: flattened, padded to 4
    for pt in gd.to_host(cs, np.asarray(out)):
        assert g.is_identity(pt)


@pytest.mark.parametrize("curve", ["secp256k1", "ristretto255"])
def test_block_form_under_vmap_as_deal_stack_calls_it(monkeypatch, curve):
    """``service.engine._deal_stack`` is a ``vmap`` of ``deal`` over a
    convoy: every ceremony's (n, t+1) lanes pad to blocks of their own."""
    cs = gd.ALL_CURVES[curve]
    g = _xla_kernel(monkeypatch, cs)
    table = _table(cs, g, 8)
    stack = [_scalars(g, 8, 12, seed=s) for s in (1, 2, 3)]
    k = jnp.stack([_limbs(cs, ks, (4, 3)) for ks in stack])
    _fused(monkeypatch, True)
    blocks = np.asarray(jax.vmap(lambda k1: gd.fixed_base_mul(cs, table, k1))(k))
    _fused(monkeypatch, False)
    tensor = np.asarray(jax.vmap(lambda k1: gd.fixed_base_mul(cs, table, k1))(k))
    assert blocks.shape == (3, 4, 3, cs.ncoords, cs.field.limbs)
    np.testing.assert_array_equal(blocks, tensor)
    for ks, row in zip(stack, blocks):
        for kk, pt in zip(ks, gd.to_host(cs, row.reshape(12, cs.ncoords, cs.field.limbs))):
            assert g.eq(pt, g.scalar_mul_vartime(kk, g.generator()))


@pytest.mark.parametrize("curve", ["ristretto255", "secp256k1"])
def test_deal_on_the_block_form_is_deal_on_the_tensor_form(monkeypatch, curve):
    """Through ``deal``: the fused switch is on while the two fixed-base
    multiplies are traced and off around them, so the rest of the
    program is the CPU's XLA path, and all four outputs agree in every
    limb with the program traced with the switch off."""
    c = ce.BatchedCeremony(curve, 5, 2, b"pr35-blocks", random.Random(35))
    cfg = c.cfg
    _xla_kernel(monkeypatch, cfg.cs)
    _fused(monkeypatch, False)
    args = (c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    want = jax.jit(lambda *a: ce.deal.__wrapped__(cfg, *a))(*args)
    real, core, forms = gd.fixed_base_mul, gd._fixed_base_mul_core, []

    def on_blocks(*a):
        with monkeypatch.context() as mp:
            mp.setenv("DKG_TPU_PALLAS", "1")
            return real(*a)

    def spy(cs_, blocks, *a):  # the counter is a trace-time count: an earlier case may hold the trace
        forms.append(blocks)
        return core(cs_, blocks, *a)

    monkeypatch.setattr(gd, "fixed_base_mul", on_blocks)
    monkeypatch.setattr(gd, "_fixed_base_mul_core", spy)
    got = jax.jit(lambda *a: ce.deal.__wrapped__(cfg, *a))(*args)
    assert forms == [True, True], forms
    for name, x, y in zip("aesr", got, want):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=name)
    g = gh.ALL_GROUPS[curve]
    a0 = gd.to_host(cfg.cs, np.asarray(got[0])[:, 0])
    for j, pt in enumerate(a0):
        secret = int(fh.decode(cfg.cs.scalar, np.asarray(c.coeffs_a)[j, 0]))
        assert g.eq(pt, g.scalar_mul_vartime(secret, g.generator()))
