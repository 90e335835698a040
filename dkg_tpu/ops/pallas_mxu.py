"""MXU-native Pallas kernel: the fused multiply-reduce.

It moves the hottest inner loop off the VPU schoolbook tier
(ops/pallas_field.py) and onto the matmul unit, the way the AI-ASIC ZKP
literature maps big-int arithmetic onto accelerator GEMMs — limb
products and reduction folds become small bounded-partial-sum f32
matmuls that are *exact* because every partial column sum stays below
float32's 2**24 integer range:

* :func:`mxu_mul_rows` / :func:`mxu_mod_mul` — the fused
  limb-mul + linear-reduce + lazy-carry modular multiply.  The
  schoolbook columns feed the ``fs.mulred`` byte-residue fold matrix
  directly (one ``jnp.dot`` on the MXU), the scan-free column folds
  squeeze the spill, and ONE carry normalize over L+1 limbs finishes —
  where the classic tier runs mul_wide's 2L-limb carry chain plus a
  separate reducer.  The quotient table is gathered with a two-level
  one-hot matmul (no dynamic gather inside the kernel).  Bit-exact
  against ``fields.device.mul``; the XLA twin of the same formulation
  is ``fields.device._mul_gemm`` (the CPU leg's win).

Layout contract matches ops/pallas_field.py: limbs on the sublane axis,
batch on the lane axis; all field/curve constants are baked Python-int
immediates, so each (field, shape) pair gets its own specialised
program.  Every numeric bound the kernels rely on is proved with exact
Python ints at field registration (spec._build_mulred); fields that
fail admission must use the Barrett row core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fields.spec import FieldSpec
from ..utils import metrics
from .pallas_field import BLOCK, _cond_sub, _mul_columns, _normalize

#: lane width of the second-level quotient-table one-hot (one VPU row)
_QL = 128


def _mask16(x):
    return x & jnp.uint32(0xFFFF)


# Mosaic has no direct uint32 <-> float32 cast; every value crossing
# these is proved < 2**24 (spec._build_mulred), so the int32 hop is
# exact.  One-hots are built with a select, not a bool cast.
def _u2f(x):
    return x.astype(jnp.int32).astype(jnp.float32)


def _f2u(x):
    return x.astype(jnp.int32).astype(jnp.uint32)


def _onehot(cond):
    return jnp.where(cond, jnp.float32(1), jnp.float32(0))


@functools.lru_cache(maxsize=None)
def mxu_const_arrays(fs: FieldSpec) -> tuple[np.ndarray, np.ndarray]:
    """The two constant matrices the MXU core multiplies against, as
    host float32 arrays — Pallas kernels must take them as OPERANDS
    (captured array constants are rejected), so every kernel that
    chains :func:`mxu_mul_rows` appends these two inputs (see
    pallas_field.mxu_operands / rows_mul_context):

    * ``foldm_t`` (2L, 3L+1): transposed ``fs.mulred.foldm`` byte-
      residue fold matrix;
    * ``q2`` (``_QL``, qh): the quotient table reshaped for the
      two-level one-hot gather, Q[lo, hi] = qtable[hi*_QL+lo]
      (zero-padded).  Values < 2**16, so both matmul levels are exact
      in f32 (a single one per one-hot column).
    """
    mr = fs.mulred
    qlen = len(mr.qtable)
    qh = -(-qlen // _QL)
    qpad = np.zeros(qh * _QL, np.uint32)
    qpad[:qlen] = mr.qtable
    return mr.foldm.T.astype(np.float32), qpad.reshape(qh, _QL).T.astype(np.float32)


def mxu_mul_rows(fs: FieldSpec, rows_a, rows_b, foldm_t=None, q2=None):
    """Fused multiply-reduce on unrolled limb-row lists: L tiles in, L out.

    The MXU twin of ops.pallas_field's Barrett ``mod_mul_rows`` — same
    row-list contract, so the fused point kernels chain it without
    leaving VMEM.  Requires ``fs.mulred`` (every registered field
    admits it; spec._build_mulred proves the bounds).  Mirrors
    fields.device._mul_gemm limb for limb:

    1. unnormalized schoolbook columns (< 2**22 — the admission cap);
    2. the high half's three byte planes plus the P_{L-1} spill fold in
       ONE f32 matmul against the baked (2L, 3L+1) residue matrix;
    3. scan-free column folds, one lazy L+1-limb carry, a quotient from
       the two-level one-hot table matmul, and one conditional subtract.

    ``foldm_t``/``q2`` are the :func:`mxu_const_arrays` matrices; inside
    a Pallas kernel they MUST be loaded from kernel operands (captured
    array constants are rejected) — the defaults only work at XLA trace
    level.
    """
    mr = fs.mulred
    if mr is None:
        raise ValueError(f"{fs.name} does not admit the fused MXU mul")
    if foldm_t is None or q2 is None:
        fm_np, q2_np = mxu_const_arrays(fs)
        foldm_t = jnp.asarray(fm_np) if foldm_t is None else foldm_t
        q2 = jnp.asarray(q2_np) if q2 is None else q2
    L = fs.limbs
    cols = _mul_columns(rows_a, rows_b)  # 2L unnormalized column tiles
    plo, phi = cols[:L], cols[L:]
    digit_rows = (
        [r & jnp.uint32(0xFF) for r in phi]
        + [(r >> 8) & jnp.uint32(0xFF) for r in phi]
        + [r >> 16 for r in phi]
        + [plo[L - 1] >> 16]
    )
    digits = _u2f(jnp.concatenate(digit_rows, axis=0))  # (3L+1, W)
    cols8 = jnp.dot(foldm_t, digits, preferred_element_type=jnp.float32)
    cols8 = _f2u(cols8)  # (2L, W), entries < 2**24
    new_cols = []
    for j in range(L):
        keep = plo[j] if j < L - 1 else _mask16(plo[L - 1])
        new_cols.append(
            keep + cols8[2 * j : 2 * j + 1, :] + (cols8[2 * j + 1 : 2 * j + 2, :] << 8)
        )
    c_l = [int(v) for v in mr.c_limbs]
    for _ in range(mr.n_split):
        los = [_mask16(cc) for cc in new_cols]
        his = [cc >> 16 for cc in new_cols]
        top = his[L - 1]
        new_cols = [
            los[j]
            + (his[j - 1] if j else jnp.zeros_like(top))
            + top * jnp.uint32(c_l[j])
            for j in range(L)
        ]
    v = _normalize(new_cols + [jnp.zeros_like(new_cols[0])])  # L+1 tiles, lazy carry
    u = (v[L - 1] >> mr.shift_e) | (v[L] << (16 - mr.shift_e))  # <= u_max < 2**13
    qh = q2.shape[1]
    w = u.shape[-1]
    ui = u.astype(jnp.int32)
    oh_hi = _onehot(jax.lax.broadcasted_iota(jnp.int32, (qh, w), 0) == (ui >> 7))
    tmp = jnp.dot(q2, oh_hi, preferred_element_type=jnp.float32)
    oh_lo = _onehot(jax.lax.broadcasted_iota(jnp.int32, (_QL, w), 0) == (ui & 127))
    q = _f2u(jnp.sum(tmp * oh_lo, axis=0, keepdims=True))  # (1, W)
    npl = [int(x) for x in mr.np_limbs]
    w_cols = [v[j] + q * jnp.uint32(npl[j]) for j in range(L + 1)]
    out = _cond_sub(_normalize(w_cols), [int(x) for x in fs.p_limbs_ext])
    return out[:L]


def _make_mxu_kernel(fs: FieldSpec):
    L = fs.limbs

    def kernel(a_ref, b_ref, fm_ref, q2_ref, out_ref):
        rows_a = [a_ref[i : i + 1, :] for i in range(L)]
        rows_b = [b_ref[i : i + 1, :] for i in range(L)]
        r = mxu_mul_rows(fs, rows_a, rows_b, foldm_t=fm_ref[...], q2=q2_ref[...])
        for i in range(L):
            out_ref[i : i + 1, :] = r[i]

    return kernel


def _const_spec(arr: np.ndarray):
    """A grid-invariant whole-array VMEM block for a constant operand."""
    return pl.BlockSpec(arr.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _mxu_mul_tiles(fs: FieldSpec, a_t: jax.Array, b_t: jax.Array, interpret: bool):
    """(L, B) x (L, B) -> (L, B), B a multiple of BLOCK."""
    L, B = a_t.shape
    fm_np, q2_np = mxu_const_arrays(fs)
    return pl.pallas_call(
        _make_mxu_kernel(fs),
        grid=(B // BLOCK,),
        in_specs=[
            pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
            _const_spec(fm_np),
            _const_spec(q2_np),
        ],
        out_specs=pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32),
        interpret=interpret,
    )(a_t, b_t, jnp.asarray(fm_np), jnp.asarray(q2_np))


def _want_interpret() -> bool:
    """Mosaic only exists on real TPU backends; interpret elsewhere."""
    from ..fields import device as fd

    return not fd._on_tpu()


def mxu_mod_mul(
    fs: FieldSpec, a: jax.Array, b: jax.Array, *, interpret: bool | None = None
) -> jax.Array:
    """Batched (a * b) mod p in ONE fused MXU kernel launch.

    a, b: (..., L) uint32 limb arrays (the framework-wide layout);
    drop-in parity with ``fields.device.mul``.
    """
    metrics.REGISTRY.inc("pallas_calls_total", kernel="mxu_mod_mul")
    a = jnp.asarray(a, jnp.uint32)
    b = jnp.asarray(b, jnp.uint32)
    a, b = jnp.broadcast_arrays(a, b)
    batch = a.shape[:-1]
    n = 1
    for d in batch:
        n *= int(d)
    m = max(BLOCK, ((n + BLOCK - 1) // BLOCK) * BLOCK)
    af = jnp.reshape(a, (n, fs.limbs))
    bf = jnp.reshape(b, (n, fs.limbs))
    if m != n:
        pad = [(0, m - n), (0, 0)]
        af = jnp.pad(af, pad)
        bf = jnp.pad(bf, pad)
    interp = _want_interpret() if interpret is None else interpret
    out_t = _mxu_mul_tiles(fs, af.T, bf.T, interp)
    return jnp.reshape(out_t.T[:n], batch + (fs.limbs,))

