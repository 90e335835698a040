"""Pallas TPU kernel: batched modular multiply (Barrett) on 16-bit limbs.

The single hottest primitive in the framework: every ladder step of
every scalar multiplication (groups/device.py) bottoms out in
``fields.device.mul`` — a schoolbook limb product plus Barrett
reduction.  The XLA path materialises the (L, L) product grid and an
antidiagonal contraction per multiply; this kernel instead keeps one
(L, BLOCK) tile of each operand resident in VMEM and walks the
schoolbook columns with fully unrolled VPU multiply-accumulates, with
the batch axis riding the 128-wide lane dimension.

Layout contract: limbs on the sublane axis, batch on the lane axis —
the transpose of the (batch, L) layout used elsewhere; the ``mod_mul``
wrapper handles the (cheap, fused) transposes and pads the batch to the
block size.

All constants (p, the Barrett mu, their extended forms) are baked into
the kernel as Python-int immediates, so each field gets its own
specialised program — mirroring how the reference's dalek backend bakes
the curve25519 prime into field ops at compile time (reference:
src/groups.rs:11-53 delegating to curve25519-dalek's fixed-prime field).

Correctness invariants are the same as fields/device.py: limbs < 2**16
in uint32 lanes, column accumulators <= 2*L terms of < 2**16 products'
halves, Barrett remainder < 3p fixed by two conditional subtractions.
"""

from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..fields.spec import POW_WINDOW, FieldSpec, window_digits
from ..utils import metrics

BLOCK = 128  # lane width: one VPU register row of batch elements


def _mul_columns(rows_a, rows_b):
    """Schoolbook product columns of two unrolled limb-row lists.

    rows_* are Python lists of (1, BLOCK) uint32 tiles with values
    < 2**16.  Returns 2L unnormalised column tiles: col[c] =
    sum_{i+j=c} lo(a_i b_j) + sum_{i+j=c-1} hi(a_i b_j) < 2**21·2.
    """
    la, lb = len(rows_a), len(rows_b)
    cols = [None] * (la + lb)
    for i in range(la):
        for j in range(lb):
            prod = rows_a[i] * rows_b[j]  # 16x16 -> 32, exact in uint32
            lo = prod & jnp.uint32(0xFFFF)
            hi = prod >> 16
            c = i + j
            cols[c] = lo if cols[c] is None else cols[c] + lo
            cols[c + 1] = hi if cols[c + 1] is None else cols[c + 1] + hi
    return [jnp.zeros_like(rows_a[0]) if c is None else c for c in cols]


def _normalize(cols):
    """Carry-propagate column tiles into 16-bit limb tiles (same length)."""
    out = []
    carry = jnp.zeros_like(cols[0])
    for c in cols:
        s = c + carry
        out.append(s & jnp.uint32(0xFFFF))
        carry = s >> 16
    return out


def _sub_with_borrow(rows_x, rows_y):
    """Limbwise x - y with borrow chain; returns (rows, borrow_tile)."""
    out = []
    borrow = jnp.zeros_like(rows_x[0])
    for xi, yi in zip(rows_x, rows_y):
        s = xi - yi - borrow  # uint32 wraparound encodes the sign
        out.append(s & jnp.uint32(0xFFFF))
        borrow = s >> 31
    return out, borrow


def _cond_sub(rows_x, const_limbs):
    """Branchless x - m if x >= m else x, m a Python-int limb list."""
    rows_m = [jnp.full_like(rows_x[0], np.uint32(m)) for m in const_limbs]
    diff, borrow = _sub_with_borrow(rows_x, rows_m)
    keep = borrow != 0
    return [jnp.where(keep, xi, di) for xi, di in zip(rows_x, diff)]


def rows_mul_dispatch(fs: FieldSpec, interpret: bool = False) -> str:
    """Which multiply core the fused kernels chain: ``"mxu"`` (the
    fused multiply-reduce of ops/pallas_mxu.py, schoolbook columns
    folded through one exact f32 matmul) or ``"barrett"`` (the VPU
    schoolbook + Barrett core below).  Keyed on the same DKG_TPU_MUL
    knob as the XLA-leg dispatch (fields.device.mul_dispatch_mode), but
    in-kernel ``auto`` prefers the MXU core wherever the field admits
    ``fs.mulred`` — inside a kernel the operands are already
    VMEM-resident rows, so the matmul fold wins on exactly the backend
    (Mosaic) where the XLA auto rule keeps classic.  Exception:
    ``auto`` under INTERPRET mode keeps Barrett — the one-hot gather
    matmuls make the interpret lowering of multi-multiply kernels
    pathologically slow to XLA-compile on CPU (minutes for one point
    add); DKG_TPU_MUL=gemm still forces the MXU core there, which is
    how the slow-tier parity tests cover it.  Both cores are bit-exact;
    resolved at kernel trace time."""
    from ..utils import envknobs

    env = envknobs.choice(
        "DKG_TPU_MUL",
        ("auto", "gemm", "classic"),
        "fd.mul formulation: fused GEMM multiply-reduce vs classic",
    )
    if env == "classic":
        return "barrett"
    if env == "gemm":
        if fs.mulred is None:
            raise ValueError(f"{fs.name} does not admit the fused MXU mul")
        return "mxu"
    if fs.mulred is None or interpret:
        return "barrett"
    return "mxu"


#: trace-time stack of (fs, foldm_t, q2) loaded from kernel operands —
#: kernel tracing is synchronous, so a plain list is safe
_MXU_CONSTS: list = []


@contextlib.contextmanager
def rows_mul_context(fs: FieldSpec, const_refs):
    """Trace-time context: inside the block, ``mod_mul_rows`` for
    ``fs`` routes through the MXU fused core of ops/pallas_mxu.py.

    ``const_refs`` are the two kernel operand refs appended by
    :func:`mxu_operands` (empty when the Barrett core is selected —
    then this is a no-op).  Pallas kernels cannot capture array
    constants, so the fold matrices must flow in as operands and down
    to every chained multiply; this context threads them through the
    point-op row helpers without widening every signature.
    """
    if not const_refs:
        yield
        return
    fm_ref, q2_ref = const_refs
    _MXU_CONSTS.append((fs, fm_ref[...], q2_ref[...]))
    try:
        yield
    finally:
        _MXU_CONSTS.pop()


def mxu_operands(fs: FieldSpec, interpret: bool = False):
    """(arrays, BlockSpecs) a kernel builder appends to its operands to
    enable the MXU multiply core for ``fs`` — both empty when
    :func:`rows_mul_dispatch` selects the Barrett core, so call sites
    can splat them unconditionally."""
    if rows_mul_dispatch(fs, interpret) != "mxu":
        return [], []
    from .pallas_mxu import mxu_const_arrays

    fm_np, q2_np = mxu_const_arrays(fs)
    specs = [
        pl.BlockSpec(fm_np.shape, lambda i: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec(q2_np.shape, lambda i: (0, 0), memory_space=pltpu.VMEM),
    ]
    return [jnp.asarray(fm_np), jnp.asarray(q2_np)], specs


def mod_mul_rows(fs: FieldSpec, rows_a, rows_b):
    """Modular multiply on unrolled limb-row lists: L tiles in, L out.

    The reusable core of the kernel — the fused point-op kernels
    (ops/pallas_point.py) chain many of these without leaving VMEM.
    Routes through the MXU fused multiply-reduce core when the
    enclosing kernel provided the fold matrices via
    :func:`rows_mul_context`; the Barrett VPU core otherwise.
    """
    for cfs, foldm_t, q2 in reversed(_MXU_CONSTS):
        if cfs is fs:
            return _rows_core(fs, "mxu")(list(rows_a), list(rows_b), foldm_t, q2)
    return _rows_core(fs, "barrett")(list(rows_a), list(rows_b))


@functools.lru_cache(maxsize=None)
def _rows_core(fs: FieldSpec, core: str):
    """One multiply core of ``fs`` as a jitted function of its limb rows.

    A point kernel chains 10-60 multiplies and each one is a thousand
    small operations to trace: written inline, tracing the kernels'
    bodies was most of a program's build (a (1024,341) verify: 114
    multiplies, 60 of the 70 s its trace took in the sandbox, several
    times that on a serving host).  Behind a ``jit`` a process traces
    the core once per field and row width, and every further multiply
    is a call of the cached jaxpr, which Mosaic's lowering inlines: the
    kernel it builds is the same."""
    if core == "mxu":
        from .pallas_mxu import mxu_mul_rows

        return jax.jit(
            lambda rows_a, rows_b, foldm_t, q2: mxu_mul_rows(
                fs, rows_a, rows_b, foldm_t=foldm_t, q2=q2
            )
        )
    return jax.jit(lambda rows_a, rows_b: _barrett_mul_rows(fs, rows_a, rows_b))


def _barrett_mul_rows(fs: FieldSpec, rows_a, rows_b):
    """The VPU Barrett multiply core (HAC 14.42), base 2**16 — mirrors
    fields/device.py.  The fallback for fields without ``fs.mulred``
    and the DKG_TPU_MUL=classic leg."""
    L = fs.limbs
    mu = [int(v) for v in fs.barrett_mu]  # (L+1,) Python ints
    p_ext = [int(v) for v in fs.p_limbs_ext]  # (L+1,)
    x = _normalize(_mul_columns(rows_a, rows_b))  # 2L limb tiles
    q1 = x[L - 1 :]  # L+1 tiles
    mu_rows = [jnp.full_like(x[0], np.uint32(m)) for m in mu]
    q2 = _normalize(_mul_columns(q1, mu_rows))
    q3 = q2[L + 1 :]  # L+1 tiles
    pe_rows = [jnp.full_like(x[0], np.uint32(m)) for m in p_ext]
    r2 = _normalize(_mul_columns(q3, pe_rows))[: L + 1]
    r1 = x[: L + 1]
    r, _ = _sub_with_borrow(r1, r2)  # mod b**(L+1): r in [0, 3p)
    r = _cond_sub(r, p_ext)
    r = _cond_sub(r, p_ext)
    return r[:L]


def mod_add_rows(fs: FieldSpec, rows_a, rows_b):
    """Modular add on limb-row lists (L tiles in, L out)."""
    p_ext = [int(v) for v in fs.p_limbs_ext]
    # limb sums < 2**17; one extra carry limb needed before cond_sub
    carry = jnp.zeros_like(rows_a[0])
    out = []
    for a, b in zip(rows_a, rows_b):
        t = a + b + carry
        out.append(t & jnp.uint32(0xFFFF))
        carry = t >> 16
    out.append(carry)  # L+1 tiles
    out = _cond_sub(out, p_ext)
    return out[: fs.limbs]


def mod_sub_rows(fs: FieldSpec, rows_a, rows_b):
    """Modular subtract on limb-row lists: (a + p) - b, then reduce."""
    p_limbs = [int(v) for v in fs.p_limbs]
    p_ext = [int(v) for v in fs.p_limbs_ext]
    carry = jnp.zeros_like(rows_a[0])
    ap = []
    for a, p in zip(rows_a, p_limbs):
        t = a + jnp.uint32(p) + carry
        ap.append(t & jnp.uint32(0xFFFF))
        carry = t >> 16
    ap.append(carry)  # L+1 tiles, = a + p < 2p < b**(L+1)
    b_ext = list(rows_b) + [jnp.zeros_like(rows_b[0])]
    d, _ = _sub_with_borrow(ap, b_ext)  # in [0, 2p)
    d = _cond_sub(d, p_ext)
    return d[: fs.limbs]


def _make_kernel(fs: FieldSpec):
    L = fs.limbs

    def kernel(a_ref, b_ref, *rest):
        out_ref = rest[-1]
        rows_a = [a_ref[i : i + 1, :] for i in range(L)]
        rows_b = [b_ref[i : i + 1, :] for i in range(L)]
        with rows_mul_context(fs, rest[:-1]):
            r = mod_mul_rows(fs, rows_a, rows_b)
        for i in range(L):
            out_ref[i : i + 1, :] = r[i]

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 3))
def _mod_mul_tiles(fs: FieldSpec, a_t: jax.Array, b_t: jax.Array, interpret: bool):
    """(L, B) x (L, B) -> (L, B), B a multiple of BLOCK."""
    L, B = a_t.shape
    extra, extra_specs = mxu_operands(fs, interpret)
    return pl.pallas_call(
        _make_kernel(fs),
        grid=(B // BLOCK,),
        in_specs=[
            pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
        ]
        + extra_specs,
        out_specs=pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32),
        interpret=interpret,
    )(a_t, b_t, *extra)


def _make_madd_kernel(fs: FieldSpec):
    L = fs.limbs

    def kernel(a_ref, b_ref, c_ref, *rest):
        out_ref = rest[-1]
        rows_a = [a_ref[i : i + 1, :] for i in range(L)]
        rows_b = [b_ref[i : i + 1, :] for i in range(L)]
        rows_c = [c_ref[i : i + 1, :] for i in range(L)]
        with rows_mul_context(fs, rest[:-1]):
            r = mod_add_rows(fs, mod_mul_rows(fs, rows_a, rows_b), rows_c)
        for i in range(L):
            out_ref[i : i + 1, :] = r[i]

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 4))
def _mod_madd_tiles(fs: FieldSpec, a_t, b_t, c_t, interpret: bool):
    """(L, B) x3 -> (L, B): (a*b + c) mod p, one fused launch."""
    L, B = a_t.shape
    spec = pl.BlockSpec((L, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM)
    extra, extra_specs = mxu_operands(fs, interpret)
    return pl.pallas_call(
        _make_madd_kernel(fs),
        grid=(B // BLOCK,),
        in_specs=[spec, spec, spec] + extra_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((L, B), jnp.uint32),
        interpret=interpret,
    )(a_t, b_t, c_t, *extra)


def _make_pow_kernel(fs: FieldSpec, digits: tuple[int, ...]):
    """x**e for the compile-time exponent whose window digits are
    ``digits`` (fields.spec.window_digits): the fixed-window chain of
    fields.device.pow_const, VMEM-resident.

    Two multiply bodies are traced whatever the exponent: the table
    build (x**k = x**(k-1) * x into a VMEM scratch of 2**POW_WINDOW
    entries) and the digit step's (four squarings and the table
    multiply share it); every loop is a ``fori_loop`` carrying one
    (L, BLOCK) array (interpret mode too: its lowering compiles in
    seconds, unrolled it would be 330 bodies).  The
    digit of a step is a scalar read from SMEM and selects the table
    entry by a sublane offset — every lane raises to the same exponent."""
    L = fs.limbs

    def rows_of(arr):
        return [arr[i : i + 1, :] for i in range(L)]

    def mul(a, b_rows):
        return jnp.concatenate(mod_mul_rows(fs, rows_of(a), b_rows), axis=0)

    def kernel(digits_ref, x_ref, *rest):
        out_ref, tab_ref = rest[-2:]

        def entry(d):
            return pl.ds(pl.multiple_of(d * L, 8), L)

        x_arr = x_ref[...]
        x_rows = rows_of(x_arr)
        one = jnp.ones_like(x_rows[0])
        tab_ref[0:L, :] = jnp.concatenate([one] + [jnp.zeros_like(one)] * (L - 1), axis=0)
        tab_ref[L : 2 * L, :] = x_arr
        with rows_mul_context(fs, rest[:-2]):

            def build(k, prev):  # prev = x**(k-1)
                nxt = mul(prev, x_rows)
                tab_ref[entry(k), :] = nxt
                return nxt

            def step(j, acc):
                # POW_WINDOW squarings, then the digit's table entry: one
                # traced body, its second operand selected by the trip
                ent = tab_ref[entry(digits_ref[j]), :]
                return jax.lax.fori_loop(
                    0,
                    POW_WINDOW + 1,
                    lambda i, a: mul(a, rows_of(jnp.where(i < POW_WINDOW, a, ent))),
                    acc,
                )

            jax.lax.fori_loop(2, max(digits) + 1, build, x_arr)
            first = digits[0] * L
            out_ref[...] = jax.lax.fori_loop(
                1, len(digits), step, tab_ref[first : first + L, :]
            )

    return kernel


@functools.partial(jax.jit, static_argnums=(0, 2, 3))
def _mod_pow_block(fs: FieldSpec, x_blk: jax.Array, digits: tuple, interpret: bool):
    """(L, BLOCK) -> (L, BLOCK): every lane of ONE block to the power
    the digits spell.  One block whatever the batch, so that a process
    traces the kernel's multiply bodies once: the wrapper maps
    this call over the blocks (``jax.vmap`` turns the map into the
    launch's grid without re-tracing the kernel), where a grid written
    here would re-trace them for every new lane count — seconds each
    on a serving host, in every process, for every convoy width."""
    spec = pl.BlockSpec((fs.limbs, BLOCK), lambda i: (0, i), memory_space=pltpu.VMEM)
    extra, extra_specs = mxu_operands(fs, interpret)
    return pl.pallas_call(
        _make_pow_kernel(fs, digits),
        grid=(1,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), spec] + extra_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x_blk.shape, jnp.uint32),
        scratch_shapes=[pltpu.VMEM(((1 << POW_WINDOW) * fs.limbs, BLOCK), jnp.uint32)],
        interpret=interpret,
        name="mod_pow_const",
    )(jnp.asarray(digits, jnp.int32), x_blk, *extra)


def _want_interpret() -> bool:
    """Mosaic only exists on real TPU backends; interpret elsewhere."""
    from ..fields import device as fd

    return not fd._on_tpu()


def mod_mul(fs: FieldSpec, a: jax.Array, b: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """Batched (a * b) mod p via the Pallas kernel.

    a, b: (..., L) uint32 limb arrays (the framework-wide layout); the
    batch is flattened, padded to a BLOCK multiple, and mapped onto the
    lane axis.  Drop-in parity with ``fields.device.mul``.
    """
    metrics.REGISTRY.inc("pallas_calls_total", kernel="mod_mul")
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
    out_t = _mod_mul_tiles(fs, af.T, bf.T, interp)
    return jnp.reshape(out_t.T[:n], batch + (fs.limbs,))


def mod_madd(
    fs: FieldSpec,
    a: jax.Array,
    b: jax.Array,
    c: jax.Array,
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Batched (a * b + c) mod p in ONE fused kernel launch.

    The Horner-step primitive (acc <- acc·x + coeff) behind
    poly.device.eval_many — the reference's per-recipient evaluation
    loop (reference: src/dkg/committee.rs:163-186 ->
    src/polynomial.rs:68-74) collapsed to one launch per coefficient.
    """
    metrics.REGISTRY.inc("pallas_calls_total", kernel="mod_madd")
    a, b, c = jnp.broadcast_arrays(
        jnp.asarray(a, jnp.uint32), jnp.asarray(b, jnp.uint32), jnp.asarray(c, jnp.uint32)
    )
    batch = a.shape[:-1]
    n = 1
    for d in batch:
        n *= int(d)
    m = max(BLOCK, ((n + BLOCK - 1) // BLOCK) * BLOCK)
    flat = [jnp.reshape(x, (n, fs.limbs)) for x in (a, b, c)]
    if m != n:
        flat = [jnp.pad(x, [(0, m - n), (0, 0)]) for x in flat]
    interp = _want_interpret() if interpret is None else interpret
    out_t = _mod_madd_tiles(fs, flat[0].T, flat[1].T, flat[2].T, interp)
    return jnp.reshape(out_t.T[:n], batch + (fs.limbs,))


def mod_pow_const(
    fs: FieldSpec, x: jax.Array, e: int, *, interpret: bool | None = None
) -> jax.Array:
    """Batched x**e mod p for a compile-time exponent e > 0 in ONE
    kernel launch: the fused twin of ``fields.device.pow_const`` (which
    dispatches here where the fused kernels are active) — the Fermat
    inversion under ``groups.device.affine_canon``.

    x: (..., L) uint32 limb arrays; the batch is flattened, padded to a
    BLOCK multiple and cut into (L, BLOCK) tiles, lanes on the lane
    axis, one grid step a tile; the whole windowed chain (about 333
    dependent multiplies for a 256-bit e) runs without leaving VMEM.
    """
    metrics.REGISTRY.inc("pallas_calls_total", kernel="mod_pow_const")
    x = jnp.asarray(x, jnp.uint32)
    batch = x.shape[:-1]
    n = 1
    for d in batch:
        n *= int(d)
    m = max(BLOCK, ((n + BLOCK - 1) // BLOCK) * BLOCK)
    xf = jnp.reshape(x, (n, fs.limbs))
    if m != n:
        xf = jnp.pad(xf, [(0, m - n), (0, 0)])
    interp = _want_interpret() if interpret is None else interpret
    digits = window_digits(e)
    blocks = jnp.swapaxes(jnp.reshape(xf, (m // BLOCK, BLOCK, fs.limbs)), 1, 2)
    out = jax.vmap(lambda blk: _mod_pow_block(fs, blk, digits, interp))(blocks)
    out = jnp.reshape(jnp.swapaxes(out, 1, 2), (m, fs.limbs))
    return jnp.reshape(out[:n], batch + (fs.limbs,))
