"""Batched modular arithmetic on 16-bit limb arrays (JAX / XLA, TPU-first).

Representation: a field element is ``L`` little-endian 16-bit limbs held in
a ``uint32`` array of shape ``(..., L)``; every operation is batched over
the leading axes.  This is the device-side replacement for the scalar
field/group arithmetic the reference gets from ``curve25519-dalek``
(reference: src/traits.rs:142-238, src/groups.rs:11-90) — but batched: the
DKG protocol's hot loops are per-party/per-coefficient scalar ops
(reference: src/dkg/committee.rs:151-186, :292-296), which here become one
wide array op over all parties at once.

TPU constraints honoured:

* no 64-bit integer ops — all products are 16x16->32 in ``uint32`` lanes;
* no data-dependent control flow — carries/borrows via ``lax.scan`` over
  the (static-length) limb axis, conditionals via branchless selects;
* reduction picks the cheapest admissible lowering per field — pseudo-
  Mersenne fold, linear byte-matrix fold, or classic Barrett — all with
  compile-time constants (see spec.py) and bit-identical canonical output.

Overflow discipline (the invariants that make this correct):

* normalized limbs are < 2**16, stored in uint32;
* schoolbook product columns accumulate <= 2*L terms of < 2**16 each
  (after hi/lo split), so columns are < 2**21 for L<=24 — safely inside
  uint32 for the carry scan;
* Barrett remainder fits in L+1 limbs because r < 3p < b**(L+1).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .spec import POW_WINDOW, FieldSpec, window_digits

# Plain int, not jnp.uint32: a module-level device constant would
# initialise the jax backend at import time, defeating hostmesh's
# platform forcing.  uint32-array ops with a Python int stay uint32.
MASK16 = 0xFFFF

_backend_cache: str | None = None


def _on_tpu() -> bool:
    """Lazy backend probe (never at import time — see hostmesh ordering).

    DKG_TPU_ASSUME_BACKEND overrides the probe: AOT-topology compiles
    (scripts/aot_lab.py, scripts/memproof_tpu.py) run in a CPU process
    but target the TPU compiler, and every backend-sensitive dispatch
    (fused kernels, MXU matmul, table width, RLC schedule) resolves at
    TRACE time — without the override they would compile a program the
    chip never runs.
    """
    from ..utils import envknobs

    env = envknobs.choice(
        "DKG_TPU_ASSUME_BACKEND", ("tpu", "cpu"),
        "backend the trace-time dispatches assume (AOT compiles)",
    )
    if env is not None:
        return env == "tpu"
    global _backend_cache
    if _backend_cache is None:
        # a backend that fails to come up raises here: silently taking
        # the CPU formulations would hide a dead chip
        _backend_cache = jax.default_backend()
    return _backend_cache == "tpu"


def fused_kernels_active() -> bool:
    """Whether the hot ops route to the fused Pallas kernels
    (ops/pallas_field.py, ops/pallas_point.py).  Default ON on a real
    TPU backend (Mosaic), OFF elsewhere (interpret mode inside the
    ladder scans would be pathologically slow on CPU);
    DKG_TPU_PALLAS=1/0 forces either way.  Resolved lazily at trace
    time so importing this module never initialises a JAX backend (see
    parallel/hostmesh.py ordering)."""
    from ..utils import envknobs

    env = envknobs.choice(
        "DKG_TPU_PALLAS", ("0", "1"), "fused Pallas kernel dispatch"
    )
    if env is not None:
        return env == "1"
    return _on_tpu()


def _u32(x) -> jax.Array:
    return jnp.asarray(x, dtype=jnp.uint32)


# ---------------------------------------------------------------------------
# carry / borrow primitives
# ---------------------------------------------------------------------------


def _shift_up(x: jax.Array) -> jax.Array:
    """Shift limbs one position up (towards higher significance),
    dropping the top limb; the last-dim length is preserved."""
    pad = [(0, 0)] * (x.ndim - 1) + [(1, 0)]
    return jnp.pad(x, pad)[..., :-1]


def normalize(cols: jax.Array, out_len: int) -> jax.Array:
    """Carry-propagate accumulator columns into ``out_len`` 16-bit limbs.

    ``cols`` may hold values up to ``2**32 - 2**16`` per column (the scan
    adds an incoming carry of < 2**16, which must not wrap uint32); the
    result is taken mod ``2**(16*out_len)`` (truncation is intentional —
    callers use it for "mod b**k" semantics).
    """
    cols = _u32(cols)
    k = cols.shape[-1]
    if k < out_len:
        pad = [(0, 0)] * (cols.ndim - 1) + [(0, out_len - k)]
        cols = jnp.pad(cols, pad)
    cols = cols[..., :out_len]
    xs = jnp.moveaxis(cols, -1, 0)

    def step(carry, col):
        s = col + carry
        return s >> 16, s & MASK16

    _, limbs = lax.scan(step, jnp.zeros(cols.shape[:-1], jnp.uint32), xs)
    return jnp.moveaxis(limbs, 0, -1)


def sub_with_borrow(a: jax.Array, b: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(a - b) mod 2**(16K) plus the final borrow flag (1 iff a < b).

    Both inputs must be normalized limb arrays of equal last-dim K.
    """
    a, b = jnp.broadcast_arrays(_u32(a), _u32(b))
    xs = (jnp.moveaxis(a, -1, 0), jnp.moveaxis(b, -1, 0))

    def step(borrow, ab):
        ai, bi = ab
        s = ai - bi - borrow  # uint32 wraparound encodes the sign
        return s >> 31, s & MASK16

    borrow, limbs = lax.scan(step, jnp.zeros(a.shape[:-1], jnp.uint32), xs)
    return jnp.moveaxis(limbs, 0, -1), borrow


def cond_sub(x: jax.Array, m) -> jax.Array:
    """Branchless ``x - m if x >= m else x`` on equal-length limb arrays."""
    m = _u32(m)
    d, borrow = sub_with_borrow(x, jnp.broadcast_to(m, x.shape))
    return jnp.where((borrow != 0)[..., None], x, d)


# ---------------------------------------------------------------------------
# wide multiply
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _antidiag_onehot(la: int, lb: int, shift: int) -> np.ndarray:
    """Constant one-hot tensor C[i,j,c] = 1 iff i+j+shift == c: collapses
    the schoolbook product grid into columns with one tensordot.

    float32, not uint32: XLA:CPU has no fast integer GEMM, so a uint32
    tensordot lowers to a scalar loop (~6x slower measured at the
    verify-round batch shape).  The contraction is still exact — every
    operand is an integer < 2**16 and every partial column sum is an
    integer < 2**22 (2L <= 48 terms of < 2**16), inside float32's 2**24
    exact-integer range, so the result round-trips to uint32 bit-exactly
    regardless of summation order."""
    out = np.zeros((la, lb, la + lb), np.float32)
    for i in range(la):
        for j in range(lb):
            out[i, j, i + j + shift] = 1.0
    return out


def _mul_columns(a: jax.Array, b: jax.Array) -> jax.Array:
    """Unnormalized schoolbook product columns: (..., La+Lb) uint32.

    Two backend-matched lowerings of the same column accumulation (bit-
    exact results either way):

    * TPU: product-scanning over a's limbs — each step is one
      (..., Lb)-wide multiply, a hi/lo 16-bit split, and two statically
      shifted adds into the (..., La+Lb) column accumulator.  Fully
      elementwise over the batch, so XLA fuses the chain and no
      (batch, La, Lb) product grid ever reaches HBM (7x faster than the
      tensordot form on v5e at large batches).
    * elsewhere: outer product + one antidiagonal one-hot tensordot,
      lowered as a float32 GEMM (exact — see _antidiag_onehot): XLA:CPU
      has no fast integer matmul, and the f32 form measures ~6x faster
      at the verify-round batch shape while staying bit-identical.

    Column sums stay < 2**22 for L<=24 (2L terms of < 2**16), safely
    inside uint32 (and float32's exact-integer range).
    """
    a, b = _u32(a), _u32(b)
    la, lb = a.shape[-1], b.shape[-1]
    nc = la + lb
    if _on_tpu():
        cols = None
        for i in range(la):
            p = a[..., i : i + 1] * b  # 16x16 -> 32, exact in uint32
            bpad = [(0, 0)] * (p.ndim - 1)
            row = jnp.pad(p & MASK16, bpad + [(i, nc - lb - i)]) + jnp.pad(
                p >> 16, bpad + [(i + 1, nc - lb - i - 1)]
            )
            cols = row if cols is None else cols + row
        return cols
    prod = a[..., :, None] * b[..., None, :]
    lo = (prod & MASK16).astype(jnp.float32)
    hi = (prod >> 16).astype(jnp.float32)
    cols = jnp.tensordot(lo, _antidiag_onehot(la, lb, 0), [[-2, -1], [0, 1]])
    cols = cols + jnp.tensordot(hi, _antidiag_onehot(la, lb, 1), [[-2, -1], [0, 1]])
    return cols.astype(jnp.uint32)


def mul_wide(a: jax.Array, b: jax.Array) -> jax.Array:
    """Full product of limb arrays: (..., La) x (..., Lb) -> (..., La+Lb).

    One carry normalize over the :func:`_mul_columns` accumulator —
    the workhorse under every classic field multiply (the fused GEMM
    twin :func:`_mul_gemm` skips this normalize entirely).
    """
    a, b = _u32(a), _u32(b)
    return normalize(_mul_columns(a, b), a.shape[-1] + b.shape[-1])


# ---------------------------------------------------------------------------
# Barrett reduction and the modular ops
# ---------------------------------------------------------------------------


def barrett_reduce(fs: FieldSpec, x: jax.Array) -> jax.Array:
    """Reduce a normalized 2L-limb value < b**(2L) to L limbs mod p.

    Classic Barrett (HAC Alg. 14.42) with base b = 2**16: the quotient
    estimate is off by at most 2, fixed by two branchless conditional
    subtractions.
    """
    L = fs.limbs
    mu = _u32(fs.barrett_mu)  # (L+1,)
    p_ext = _u32(fs.p_limbs_ext)  # (L+1,)
    q1 = x[..., L - 1 :]  # floor(x / b**(L-1)), L+1 limbs
    q2 = mul_wide(q1, mu)
    q3 = q2[..., L + 1 :]  # floor(q1*mu / b**(L+1)), L+1 limbs
    r1 = x[..., : L + 1]  # x mod b**(L+1)
    r2 = mul_wide(q3, p_ext)[..., : L + 1]  # q3*p mod b**(L+1)
    r, _ = sub_with_borrow(r1, r2)  # wraparound == +b**(L+1): r in [0, 3p)
    r = cond_sub(r, p_ext)
    r = cond_sub(r, p_ext)
    return r[..., :L]


def fold_reduce(fs: FieldSpec, x: jax.Array) -> jax.Array:
    """Pseudo-Mersenne reduction of a 2L-limb value to L limbs mod p.

    Requires ``fs.fold_limbs`` (c = b**L mod p, lc <= 4 limbs; spec.py
    guards admission).  Uses hi*b**L == hi*c (mod p) twice:

    * fold 1: y1 = lo + hi*c       < b**L + b**(L+lc)   (L+lc+1 limbs)
    * fold 2: y2 = lo' + hi'*c     < b**L + b**(2lc+1)  (L+1 limbs)
    * y2 < 3p (spec guard), so two conditional subtractions finish.

    Each fold is one L x lc mul_wide — far cheaper than Barrett's two
    (L+1) x (L+1) multiplies — and the result is the same canonical
    representative in [0, p), so swapping reducers is bit-exact.
    """
    L = fs.limbs
    c = _u32(fs.fold_limbs)
    lc = c.shape[-1]

    def fold(lo, hi, out_len):
        prod = mul_wide(hi, c)
        w = max(prod.shape[-1], lo.shape[-1])

        def pad_to(v):
            return jnp.pad(v, [(0, 0)] * (v.ndim - 1) + [(0, w - v.shape[-1])])

        # both operands are normalized limbs (< 2**16): columns < 2**17
        return normalize(pad_to(prod) + pad_to(lo), out_len)

    y1 = fold(x[..., :L], x[..., L:], L + lc + 1)
    y2 = fold(y1[..., :L], y1[..., L:], L + 1)
    p_ext = _u32(fs.p_limbs_ext)
    y2 = cond_sub(y2, p_ext)
    y2 = cond_sub(y2, p_ext)
    return y2[..., :L]


def linear_reduce(fs: FieldSpec, x: jax.Array) -> jax.Array:
    """Linear-fold reduction of a 2L-limb value to L limbs mod p.

    Exploits linearity of "mod p" over limb values (``fs.linred`` holds
    the constants, with every bound proved at admission time):

    1. The high L limbs, read as 2L bytes d_k, fold in ONE small float32
       contraction: hi * b**L = sum_k d_k * D_k (mod p) with
       D_k = 2**(8k+16L) mod p baked into a (2L, 2L) byte matrix —
       column sums < 2**22, so the f32 GEMM is exact.
    2. ``n_split`` scan-free column folds squeeze the remaining excess:
       split columns into lo/hi, shift hi up a limb, and multiply the
       top spill back in through c = b**L mod p.  Pure elementwise work.
    3. One carry normalize; the quotient then comes from a <= 2**13-entry
       table indexed by the value's top ~12 bits (estimate short by at
       most 1), is multiplied back in as q * (b**(L+1) - p) mod
       b**(L+1), and a single conditional subtraction lands in [0, p).

    Three carry passes and one tiny GEMM, versus Barrett's two
    (L+1)-limb multiplies and five carry passes; the canonical output is
    bit-identical, so swapping reducers never changes results.
    """
    lr = fs.linred
    if lr is None:
        raise ValueError(f"{fs.name} does not admit linear_reduce")
    L = fs.limbs
    x = _u32(x)
    if x.shape[-1] != 2 * L:
        raise ValueError("linear_reduce expects a full 2L-limb product")
    lo, hi = x[..., :L], x[..., L:]
    # step 1: byte-matrix fold of the high half
    d8 = jnp.stack([hi & 0xFF, hi >> 8], axis=-1).reshape(*hi.shape[:-1], 2 * L)
    cols8 = jnp.tensordot(d8.astype(jnp.float32), lr.fold8, [[-1], [0]])
    cols8 = cols8.astype(jnp.uint32).reshape(*hi.shape[:-1], L, 2)
    cols = lo + cols8[..., 0] + (cols8[..., 1] << 8)
    # step 2: scan-free column folds of the spill through c = b**L mod p
    c = _u32(lr.c_limbs)
    for _ in range(lr.n_split):
        hi16 = cols >> 16
        cols = (cols & MASK16) + _shift_up(hi16) + hi16[..., L - 1 :] * c
    # step 3: normalize, table quotient, one conditional subtraction
    v = normalize(cols, L + 1)
    u = (v[..., L - 1] >> lr.shift_e) | (v[..., L] << (16 - lr.shift_e))
    q = jnp.take(_u32(lr.qtable), u, axis=0)
    w = normalize(v + q[..., None] * _u32(lr.np_limbs), L + 1)
    return cond_sub(w, _u32(fs.p_limbs_ext))[..., :L]


def reduce_wide(fs: FieldSpec, x: jax.Array) -> jax.Array:
    """Reduce a normalized 2L-limb value to L limbs mod p, picking the
    cheapest admissible reducer: pseudo-Mersenne fold, then the linear
    fold, then Barrett.  All three produce the canonical representative,
    so the choice never changes results — only the op count."""
    if fs.fold_limbs is not None:
        return fold_reduce(fs, x)
    if fs.linred is not None:
        return linear_reduce(fs, x)
    return barrett_reduce(fs, x)


def zeros(fs: FieldSpec, batch: tuple = ()) -> jax.Array:
    return jnp.zeros(batch + (fs.limbs,), jnp.uint32)


def ones(fs: FieldSpec, batch: tuple = ()) -> jax.Array:
    return jnp.broadcast_to(
        jnp.concatenate([jnp.ones(1, jnp.uint32), jnp.zeros(fs.limbs - 1, jnp.uint32)]),
        batch + (fs.limbs,),
    )


def constant(fs: FieldSpec, value: int) -> jax.Array:
    """Embed a Python int as a compile-time limb constant."""
    from .spec import int_to_limbs

    return _u32(int_to_limbs(value % fs.modulus, fs.limbs))


def add(fs: FieldSpec, a: jax.Array, b: jax.Array) -> jax.Array:
    s = normalize(_u32(a) + _u32(b), fs.limbs + 1)  # limb sums < 2**17
    return cond_sub(s, _u32(fs.p_limbs_ext))[..., : fs.limbs]


def sub(fs: FieldSpec, a: jax.Array, b: jax.Array) -> jax.Array:
    # (a + p) - b avoids signed intermediates; result in [0, 2p) then one
    # conditional subtract.
    ap = normalize(_u32(a) + _u32(fs.p_limbs), fs.limbs + 1)
    b_ext = jnp.pad(_u32(b), [(0, 0)] * (jnp.ndim(b) - 1) + [(0, 1)])
    d, _ = sub_with_borrow(*jnp.broadcast_arrays(ap, b_ext))
    return cond_sub(d, _u32(fs.p_limbs_ext))[..., : fs.limbs]


def neg(fs: FieldSpec, a: jax.Array) -> jax.Array:
    return sub(fs, jnp.broadcast_to(zeros(fs), a.shape), a)


def _mul_gemm(fs: FieldSpec, a: jax.Array, b: jax.Array) -> jax.Array:
    """Fused multiply-reduce: schoolbook columns straight into the
    linear fold, with ONE lazy carry normalize at the very end.

    The classic leg runs mul_wide (2L-limb carry scan) then a reducer
    (more carry passes); here the reduction is applied to the
    UNNORMALIZED product columns (each < 2**22 — the mulred admission
    bound), so the 2L-limb normalize between them disappears:

    1. product columns via :func:`_mul_columns` (exact f32 GEMM on the
       XLA:CPU leg, product-scanning on TPU);
    2. the high-half columns split into three bytes each (byte 2 and
       the P_{L-1} spill are < 2**6), folded in ONE exact f32 GEMM
       against the baked (3L+1, 2L) matrix of 2**(16c+8t) mod p
       residues — ``fs.mulred.foldm``;
    3. ``n_split`` scan-free column folds squeeze the spill through
       c = b**L mod p, then the same normalize/quotient-table/cond_sub
       tail as :func:`linear_reduce` — the lazy carry happens here,
       once, over L+1 limbs instead of 2L.

    Every bound (digit caps, f32 exactness, column caps, table index
    range) is proved with exact ints in spec._build_mulred; fields
    without ``fs.mulred`` must use the classic leg.  Output is the
    canonical representative — bit-identical to the classic leg.
    """
    mr = fs.mulred
    if mr is None:
        raise ValueError(f"{fs.name} does not admit the fused GEMM mul")
    L = fs.limbs
    cols = _mul_columns(_u32(a), _u32(b))  # (..., 2L) unnormalized
    plo, phi = cols[..., :L], cols[..., L:]
    digits = jnp.concatenate(
        [phi & 0xFF, (phi >> 8) & 0xFF, phi >> 16, plo[..., L - 1 :] >> 16],
        axis=-1,
    ).astype(jnp.float32)  # (..., 3L+1) in the MulReduceSpec digit order
    cols8 = jnp.tensordot(digits, jnp.asarray(mr.foldm), [[-1], [0]])
    cols8 = cols8.astype(jnp.uint32).reshape(*phi.shape[:-1], L, 2)
    keep = jnp.concatenate([plo[..., : L - 1], plo[..., L - 1 :] & MASK16], axis=-1)
    cols = keep + cols8[..., 0] + (cols8[..., 1] << 8)
    c = _u32(mr.c_limbs)
    for _ in range(mr.n_split):
        hi16 = cols >> 16
        cols = (cols & MASK16) + _shift_up(hi16) + hi16[..., L - 1 :] * c
    v = normalize(cols, L + 1)
    u = (v[..., L - 1] >> mr.shift_e) | (v[..., L] << (16 - mr.shift_e))
    q = jnp.take(_u32(mr.qtable), u, axis=0)
    w = normalize(v + q[..., None] * _u32(mr.np_limbs), L + 1)
    return cond_sub(w, _u32(fs.p_limbs_ext))[..., :L]


def mul_dispatch_mode(fs: FieldSpec) -> str:
    """The ``fd.mul`` formulation active for this field: ``"gemm"``
    (the fused multiply-reduce, :func:`_mul_gemm`) or ``"classic"``
    (mul_wide + reduce_wide).  Both are bit-exact; the choice is pure
    op count.  ``DKG_TPU_MUL=gemm|classic`` forces one (raising at
    trace time when the field does not admit the GEMM form); auto
    takes the fused form wherever admissible on the XLA:CPU leg —
    measured faster on the 16-limb fields (up to 1.15x; the 2L-step
    carry scan it deletes is sequential cost) and neutral on BLS12-381
    base at every batch shape probed — and keeps the
    product-scanning classic form on TPU, where the elementwise chain
    fuses and the Pallas MXU kernel (ops/pallas_mxu.py) is the fused
    tier instead.  Resolved lazily at trace time (hostmesh ordering).
    """
    from ..utils import envknobs

    env = envknobs.choice(
        "DKG_TPU_MUL",
        ("auto", "gemm", "classic"),
        "fd.mul formulation: fused GEMM multiply-reduce vs classic",
    )
    if env == "gemm":
        if fs.mulred is None:
            raise ValueError(f"{fs.name} does not admit the fused GEMM mul")
        return "gemm"
    if env == "classic":
        return "classic"
    if fs.mulred is not None and not _on_tpu():
        return "gemm"
    return "classic"


def mul(fs: FieldSpec, a: jax.Array, b: jax.Array) -> jax.Array:
    if mul_dispatch_mode(fs) == "gemm":
        return _mul_gemm(fs, a, b)
    return reduce_wide(fs, mul_wide(a, b))


def square(fs: FieldSpec, a: jax.Array) -> jax.Array:
    return mul(fs, a, a)


def pow_const(fs: FieldSpec, x: jax.Array, e: int) -> jax.Array:
    """x**e mod p for a compile-time exponent: a fixed-window
    (``POW_WINDOW`` = 4 bits) left-to-right chain.

    x**0 .. x**15 are built once (14 multiplies), then every further
    digit of ``e`` costs four squarings and one table multiply — about
    333 dependent multiplies for a 256-bit exponent.  The digits live in
    a tiny constant array and the digit step is traced once (one
    ``lax.scan``), so compile time stays flat for any exponent.  Generic
    over ``e``: the Fermat inverse x**(p-2) of every base field and
    ristretto's (p-5)/8 root share it.

    Where the fused kernels are active the same chain runs in one
    Pallas launch, lanes on the lane axis and every multiply in VMEM
    (``ops.pallas_field.mod_pow_const``); here it runs on ``mul``.
    """
    if e < 0:
        raise ValueError("negative exponent")
    if e == 0:
        return jnp.broadcast_to(ones(fs), x.shape)
    if fused_kernels_active():
        from ..ops import pallas_field

        return pallas_field.mod_pow_const(fs, x, e)
    digits = window_digits(e)
    powers = [jnp.broadcast_to(ones(fs), x.shape), x]
    for _ in range(2, max(digits) + 1):
        powers.append(mul(fs, powers[-1], x))
    acc = powers[digits[0]]
    if len(digits) == 1:
        return acc
    table = jnp.stack(powers)

    def step(acc, digit):
        acc = lax.fori_loop(0, POW_WINDOW, lambda _, a: mul(fs, a, a), acc)
        return mul(fs, acc, lax.dynamic_index_in_dim(table, digit, keepdims=False)), None

    acc, _ = lax.scan(step, acc, jnp.asarray(digits[1:], dtype=jnp.int32))
    return acc


def inv(fs: FieldSpec, x: jax.Array) -> jax.Array:
    """Fermat inverse x**(p-2); maps 0 -> 0 (callers guard zero)."""
    return pow_const(fs, x, fs.modulus - 2)


def batch_inv(fs: FieldSpec, x: jax.Array, axis: int = 0) -> jax.Array:
    """Montgomery-trick batched inversion along ``axis``.

    One Fermat inversion + 3(k-1) multiplies for k elements; used by
    Lagrange reconstruction (reference: src/polynomial.rs:162-184) when
    denominators are device-resident.  Zero inputs produce garbage in the
    affected lane only (protocol code never inverts zero).

    The two scans are ``k`` DEPENDENT steps each, so the trick pays only
    while a step is wide enough to fill the device: the caller picks
    ``k`` from its lane count (groups.device.affine_canon does), and
    ``k == 1`` is the plain lane-wide inversion with no scan at all.
    """
    x = jnp.moveaxis(x, axis, 0)
    if x.shape[0] == 1:
        return jnp.moveaxis(inv(fs, x), 0, axis)

    def fwd(carry, xi):
        nxt = mul(fs, carry, xi)
        return nxt, carry  # prefix EXCLUSIVE product

    total, prefix = lax.scan(fwd, jnp.broadcast_to(ones(fs), x.shape[1:]), x)
    inv_total = inv(fs, total)

    def bwd(carry, args):
        xi, pre = args
        out = mul(fs, carry, pre)  # = 1/xi
        carry = mul(fs, carry, xi)  # strip xi from the running inverse
        return carry, out

    _, invs = lax.scan(bwd, inv_total, (x, prefix), reverse=True)
    return jnp.moveaxis(invs, 0, axis)


def eq(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.all(a == b, axis=-1)


def is_zero(a: jax.Array) -> jax.Array:
    return jnp.all(a == 0, axis=-1)


def select(pred: jax.Array, a: jax.Array, b: jax.Array) -> jax.Array:
    """Branchless limb-array select; pred shape == batch shape."""
    return jnp.where(pred[..., None], a, b)
