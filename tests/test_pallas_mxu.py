"""MXU-native Pallas kernels (ops/pallas_mxu.py) vs their XLA twins.

Coverage strategy mirrors test_pallas_point.py (compile-cost driven —
interpret-mode pallas compiles on XLA:CPU scale with the limb-multiply
count, so real-field multi-multiply kernels take minutes while 2-limb
toy programs compile in well under a second):

* **Default tier** (seconds on XLA:CPU): the :func:`mxu_mul_rows` row
  core at plain XLA trace level on EVERY registered field — the exact
  math the kernel runs, no pallas machinery — plus dispatch-rule unit
  tests and the full ``mxu_mod_mul`` pallas_call on the toy field.
* **Slow tier**: interpret-mode pallas_call parity on the real fields
  (``mxu_mod_mul``: edge lanes, ragged broadcast batches).
  ``DKG_TPU_MUL=gemm`` forced through toy field/point kernels covers
  the ``rows_mul_context`` seam the fused point kernels chain the MXU
  core through (``auto`` keeps Barrett under interpret precisely
  because of the compile pathology above).
* **TPU tier** (Mosaic compiles these in seconds): per-field
  ``mxu_mod_mul`` on the hardware path.
"""

import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dkg_tpu.fields import device as fd
from dkg_tpu.fields import host as fh
from dkg_tpu.fields.spec import ALL_FIELDS, FieldSpec
from dkg_tpu.groups import device as gd
from dkg_tpu.ops import pallas_field as pf
from dkg_tpu.ops import pallas_mxu as pm
from dkg_tpu.ops import pallas_point as pp
from dkg_tpu.utils import metrics

RNG = random.Random(0x3C0)

ON_TPU = jax.default_backend() == "tpu"

RUN_WIDE = os.environ.get("DKG_TPU_SLOW_TESTS") == "1" or ON_TPU

TOY_FS = FieldSpec("toy_m31", (1 << 31) - 1, 2)
TOY_ED = gd.CurveSpec("toy_ed", "edwards", TOY_FS, TOY_FS, 37, (0, 1))
TOY_WS = gd.CurveSpec("toy_ws", "weierstrass_a0", TOY_FS, TOY_FS, 21, (0, 1))
TOY_CURVES = [TOY_ED, TOY_WS]

needs_tpu = pytest.mark.skipif(
    not ON_TPU,
    reason="pallas_call plumbing: Mosaic-only (interpret compile is pathological here)",
)


def _edge_cases(fs, k):
    p = fs.modulus
    xs = [RNG.randrange(p) for _ in range(k)] + [0, 1, 2, p - 2, p - 1]
    ys = [RNG.randrange(p) for _ in range(k)] + [p - 1, p - 1, 0, p - 2, 1]
    return xs, ys


def _toy_points_dev(cs, n):
    """Random coordinate tuples (NOT on-curve: parity is algebraic)."""
    arr = np.asarray(
        [
            [RNG.randrange(cs.field.modulus) for _ in range(cs.ncoords)]
            for _ in range(n)
        ],
        dtype=object,
    )
    return jnp.asarray(fh.encode(cs.field, arr))


# --------------------------------------------------------------------------
# default tier: row core at XLA level, dispatch rules, toy-field kernel
# --------------------------------------------------------------------------


def test_mxu_mul_rows_matches_mul_all_fields():
    """The fused multiply-reduce row core vs fields.device.mul, plain
    XLA on every registered field (every field admits fs.mulred) —
    the same formula the pallas kernel runs, compiled without any
    pallas machinery."""
    for name, fs in list(ALL_FIELDS.items()) + [("toy", TOY_FS)]:
        xs, ys = _edge_cases(fs, 5)
        a = jnp.asarray(fh.encode(fs, xs))
        b = jnp.asarray(fh.encode(fs, ys))
        rows_a = [a.T[i : i + 1, :] for i in range(fs.limbs)]
        rows_b = [b.T[i : i + 1, :] for i in range(fs.limbs)]
        got = jnp.concatenate(pm.mxu_mul_rows(fs, rows_a, rows_b), axis=0).T
        assert jnp.all(got == fd.mul(fs, a, b)), name


def test_mxu_mul_rows_matches_barrett_rows_toy():
    """Both in-kernel multiply cores are bit-exact against each other
    (the dispatch contract of pallas_field.mod_mul_rows)."""
    fs = TOY_FS
    xs, ys = _edge_cases(fs, 16)
    a = jnp.asarray(fh.encode(fs, xs))
    b = jnp.asarray(fh.encode(fs, ys))
    rows_a = [a.T[i : i + 1, :] for i in range(fs.limbs)]
    rows_b = [b.T[i : i + 1, :] for i in range(fs.limbs)]
    got = pm.mxu_mul_rows(fs, rows_a, rows_b)
    want = pf._barrett_mul_rows(fs, rows_a, rows_b)
    for g, w in zip(got, want):
        assert jnp.all(g == w)


def test_rows_mul_dispatch_rules(monkeypatch):
    """auto prefers the MXU core except under interpret (compile
    pathology); gemm forces it everywhere; classic forces Barrett;
    gemm on a non-admitting field raises at trace time."""
    fs = next(iter(ALL_FIELDS.values()))
    monkeypatch.delenv("DKG_TPU_MUL", raising=False)
    assert pf.rows_mul_dispatch(fs, interpret=False) == "mxu"
    assert pf.rows_mul_dispatch(fs, interpret=True) == "barrett"
    monkeypatch.setenv("DKG_TPU_MUL", "classic")
    assert pf.rows_mul_dispatch(fs, interpret=False) == "barrett"
    monkeypatch.setenv("DKG_TPU_MUL", "gemm")
    assert pf.rows_mul_dispatch(fs, interpret=True) == "mxu"

    class _NoMulred:
        name = "no_mulred"
        mulred = None

    monkeypatch.delenv("DKG_TPU_MUL", raising=False)
    assert pf.rows_mul_dispatch(_NoMulred(), interpret=False) == "barrett"
    monkeypatch.setenv("DKG_TPU_MUL", "gemm")
    with pytest.raises(ValueError, match="no_mulred"):
        pf.rows_mul_dispatch(_NoMulred(), interpret=False)


def test_mxu_operands_empty_under_barrett(monkeypatch):
    """Kernels that resolve to the Barrett core get NO extra operands
    (the const matrices ride along only when the MXU core will load
    them) — and rows_mul_context with no refs is a no-op."""
    fs = next(iter(ALL_FIELDS.values()))
    monkeypatch.delenv("DKG_TPU_MUL", raising=False)
    extra, extra_specs = pf.mxu_operands(fs, interpret=True)
    assert extra == [] and extra_specs == []
    extra, extra_specs = pf.mxu_operands(fs, interpret=False)
    assert len(extra) == 2 and len(extra_specs) == 2
    fm_np, q2_np = pm.mxu_const_arrays(fs)
    assert extra[0].shape == fm_np.shape and extra[1].shape == q2_np.shape


def test_mxu_mod_mul_toy_kernel_interpret():
    """Full pallas_call on the 2-limb toy field: edge lanes, a ragged
    non-BLOCK batch with a broadcast operand, and the dispatch
    counter."""
    fs = TOY_FS
    before = metrics.REGISTRY.snapshot()["counters"].get(
        'pallas_calls_total{kernel="mxu_mod_mul"}', 0
    )
    xs, ys = _edge_cases(fs, 11)  # 16 lanes -> padded to one BLOCK tile
    a = jnp.asarray(fh.encode(fs, xs))
    b = jnp.asarray(fh.encode(fs, ys))
    got = pm.mxu_mod_mul(fs, a, b, interpret=True)
    assert jnp.all(got == fd.mul(fs, a, b))
    # ragged 2-D batch, second operand broadcast across a new axis
    a2 = jnp.reshape(a[:14], (7, 2, fs.limbs))
    b2 = b[:2]
    got2 = pm.mxu_mod_mul(fs, a2, b2, interpret=True)
    assert got2.shape == (7, 2, fs.limbs)
    assert jnp.all(got2 == fd.mul(fs, a2, b2))
    after = metrics.REGISTRY.snapshot()["counters"].get(
        'pallas_calls_total{kernel="mxu_mod_mul"}', 0
    )
    assert after == before + 2


def test_int32_hop_casts_exact_below_2_24():
    """Mosaic has no uint32<->float32 cast, so the kernels hop through
    int32; exact over the whole proved range (every value < 2**24)."""
    edge = jnp.asarray([0, 1, 0xFFFF, (1 << 22) - 1, (1 << 24) - 1], jnp.uint32)
    f = pm._u2f(edge)
    assert f.dtype == jnp.float32 and pm._f2u(f).dtype == jnp.uint32
    assert jnp.all(pm._f2u(f) == edge)
    assert jnp.all(f == edge.astype(jnp.float32))
    oh = pm._onehot(edge == jnp.uint32(1))
    assert oh.dtype == jnp.float32 and oh.tolist() == [0, 1, 0, 0, 0]


# --------------------------------------------------------------------------
# slow tier: interpret-mode kernel parity (real fields / toy curves)
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_mxu_mod_mul_kernel_all_fields():
    """Interpret-mode pallas_call on every registered field (the BLS
    base field's 24-limb program is the CPU-compile heavyweight, gated
    like test_pallas_field.py's wide tier): edge lanes and a ragged
    broadcast batch per field, against the int-level ground truth."""
    for name, fs in ALL_FIELDS.items():
        if not RUN_WIDE and fs.limbs > 16:
            continue
        xs, ys = _edge_cases(fs, 6)
        a = jnp.asarray(fh.encode(fs, xs))
        b = jnp.asarray(fh.encode(fs, ys))
        got = fh.decode(fs, np.asarray(pm.mxu_mod_mul(fs, a, b, interpret=True)))
        for g, x, y in zip(got, xs, ys):
            assert int(g) == x * y % fs.modulus, name
        got2 = pm.mxu_mod_mul(fs, a[:7], b[:1], interpret=True)
        assert jnp.all(got2 == fd.mul(fs, a[:7], b[:1])), name


@pytest.mark.slow
def test_mod_mul_kernel_gemm_forced_toy(monkeypatch):
    """DKG_TPU_MUL=gemm routes the MXU core through the generic field
    kernel via mxu_operands + rows_mul_context (the seam every fused
    point kernel chains).  __wrapped__ bypasses the jit cache, which
    does not key on the env knob."""
    monkeypatch.setenv("DKG_TPU_MUL", "gemm")
    fs = TOY_FS
    xs, ys = _edge_cases(fs, 123)  # one full BLOCK tile
    a = jnp.asarray(fh.encode(fs, xs))
    b = jnp.asarray(fh.encode(fs, ys))
    got_t = pf._mod_mul_tiles.__wrapped__(fs, a.T, b.T, True)
    assert jnp.all(got_t.T == fd.mul(fs, a, b))


@pytest.mark.slow
@pytest.mark.parametrize("cs", TOY_CURVES, ids=lambda c: c.kind)
def test_point_kernel_gemm_forced_toy(cs, monkeypatch):
    """A full point-add kernel with the MXU multiply core forced —
    end-to-end through _rows_in / _add_rows / mod_mul_rows dispatch —
    vs the XLA adder on arbitrary coordinate tuples."""
    monkeypatch.setenv("DKG_TPU_MUL", "gemm")
    L, C = cs.field.limbs, cs.ncoords
    p = _toy_points_dev(cs, 128)
    q = _toy_points_dev(cs, 128)
    p_t = jnp.reshape(p, (128, C * L)).T
    q_t = jnp.reshape(q, (128, C * L)).T
    out_t = pp._add_call.__wrapped__(cs, p_t, q_t, True)
    got = jnp.reshape(out_t.T, (128, C, L))
    assert jnp.all(got == gd._add_xla(cs, p, q))


# --------------------------------------------------------------------------
# TPU tier: Mosaic kernel parity on real curves/fields
# --------------------------------------------------------------------------


@needs_tpu
def test_kernel_mxu_mod_mul_all_fields_tpu():
    for name, fs in ALL_FIELDS.items():
        xs, ys = _edge_cases(fs, 6)
        a = jnp.asarray(fh.encode(fs, xs))
        b = jnp.asarray(fh.encode(fs, ys))
        got = fh.decode(fs, np.asarray(pm.mxu_mod_mul(fs, a, b, interpret=False)))
        for g, x, y in zip(got, xs, ys):
            assert int(g) == x * y % fs.modulus, name
