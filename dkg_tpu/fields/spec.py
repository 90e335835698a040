"""Field specifications for the dkg_tpu limb arithmetic stack.

Every scalar/base field used by the framework is described by a
:class:`FieldSpec`: the modulus, the number of 16-bit limbs used for the
device representation, and precomputed Barrett-reduction constants.

Design notes (TPU-first):

* TPUs have no native 64-bit integer multiply; products must be built from
  16x16->32-bit multiplies that fit in ``uint32`` lanes.  We therefore
  represent an N-bit field element as ``L`` little-endian 16-bit limbs
  stored in a ``uint32`` array of shape ``(..., L)``.
* Reduction is Barrett (not Montgomery) because Barrett exposes the work as
  three large limb-convolutions — wide, batched, branch-free element-wise
  ops that XLA vectorizes well — instead of a carried sequential CIOS loop.
* All constants here are plain Python ints / numpy arrays computed once at
  import; inside ``jit`` they become compile-time constants.

Reference parity: this is the TPU-native replacement for the curve/field
arithmetic the reference delegates to ``curve25519-dalek``
(reference: src/traits.rs:142-238, src/groups.rs:11-90).  The reference is
generic over a ``Scalar``/``PrimeGroupElement`` trait pair; here the same
seam is a ``FieldSpec`` (+ group modules) so new curves plug in by
registering their moduli.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

LIMB_BITS = 16
LIMB_MASK = (1 << LIMB_BITS) - 1


def int_to_limbs(x: int, n_limbs: int) -> np.ndarray:
    """Little-endian 16-bit limb decomposition of a non-negative int."""
    if x < 0:
        raise ValueError("int_to_limbs expects non-negative input")
    out = np.zeros(n_limbs, dtype=np.uint32)
    for i in range(n_limbs):
        out[i] = x & LIMB_MASK
        x >>= LIMB_BITS
    if x != 0:
        raise ValueError(f"value does not fit in {n_limbs} limbs")
    return out


def limbs_to_int(limbs) -> int:
    """Inverse of :func:`int_to_limbs` (accepts any 1-D integer array)."""
    acc = 0
    for i, limb in enumerate(np.asarray(limbs, dtype=np.uint64).tolist()):
        acc += int(limb) << (LIMB_BITS * i)
    return acc


#: window width of the compile-time-exponent chain (fields.device.pow_const
#: and its fused twin ops.pallas_field.mod_pow_const): 2**4 table entries
POW_WINDOW = 4


def window_digits(e: int) -> tuple[int, ...]:
    """Base-``2**POW_WINDOW`` digits of a positive exponent, most
    significant first — the schedule of the fixed-window chain."""
    if e <= 0:
        raise ValueError("window_digits expects a positive exponent")
    digits = []
    while e:
        digits.append(e & ((1 << POW_WINDOW) - 1))
        e >>= POW_WINDOW
    return tuple(reversed(digits))


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """A prime field with its device-representation parameters."""

    name: str
    modulus: int
    limbs: int  # number of 16-bit limbs; modulus < 2**(16*limbs)

    def __post_init__(self):
        if self.modulus >= 1 << (LIMB_BITS * self.limbs):
            raise ValueError("modulus does not fit in the limb budget")
        # Barrett requires the top limb of p to be non-zero
        # (p >= b**(L-1), b = 2**16) so the quotient estimate is tight.
        if self.modulus < 1 << (LIMB_BITS * (self.limbs - 1)):
            raise ValueError("modulus too small for limb count (Barrett)")

    @property
    def bits(self) -> int:
        return self.modulus.bit_length()

    @property
    def nbytes(self) -> int:
        """Canonical little-endian encoding length (reference: 32 bytes)."""
        return (self.bits + 7) // 8

    @functools.cached_property
    def p_limbs(self) -> np.ndarray:
        return int_to_limbs(self.modulus, self.limbs)

    @functools.cached_property
    def p_limbs_ext(self) -> np.ndarray:
        """p padded to L+1 limbs (Barrett remainders live mod b**(L+1))."""
        return int_to_limbs(self.modulus, self.limbs + 1)

    @functools.cached_property
    def barrett_mu(self) -> np.ndarray:
        """floor(b**(2L) / p) as L+1 limbs."""
        mu = (1 << (2 * LIMB_BITS * self.limbs)) // self.modulus
        return int_to_limbs(mu, self.limbs + 1)

    @functools.cached_property
    def linred(self) -> "LinearReduceSpec | None":
        """Constants for the linear-fold reduction (fields.device.
        linear_reduce), or ``None`` when the field fails admission.

        Reduction mod p is linear over limb values, so the high half of a
        2L-limb product folds in one shot: split it into 2L 8-bit digits
        d_k and precompute D_k = 2**(8k + 16L) mod p — then
        hi * b**L == sum_k d_k * D_k (mod p), a single (2L x 2L) byte-
        matrix contraction whose column sums stay inside float32's exact
        range (<= 2L * 255**2 < 2**22).  The remaining excess over b**L
        is squeezed out by a few *scan-free* column folds (top spill *
        c, c = b**L mod p), and the final quotient comes from a tiny
        precomputed table indexed by the top ~12 bits, leaving exactly
        one conditional subtraction.  All bounds below are proved with
        exact Python ints at admission time; inadmissible fields fall
        back to Barrett.
        """
        return _build_linred(self)

    @functools.cached_property
    def mulred(self) -> "MulReduceSpec | None":
        """Constants for the fused multiply-reduce (fields.device._mul_gemm
        and ops.pallas_mxu), or ``None`` when the field fails admission.

        Where ``linred`` folds an already-normalized 2L-limb product,
        this folds the *unnormalized* schoolbook product columns
        directly — the 2L-limb carry scan between mul_wide and the
        reducer disappears.  Each high column P_c (c >= L, < 2**22) is
        split into three bytes with residues 2**(16c + 8t) mod p, plus
        the one spill digit P_{L-1} >> 16 with residue 2**(16L) mod p:
        3L+1 digits, one exact f32 GEMM, then the same scan-free column
        folds and quotient table as ``linred``.  All bounds are proved
        with exact Python ints at admission time.
        """
        return _build_mulred(self)

    @functools.cached_property
    def fold_limbs(self) -> np.ndarray | None:
        """Pseudo-Mersenne fold constant ``c = b**L mod p`` as limbs, or
        ``None`` when the field is not fold-friendly.

        When ``c`` is tiny (fits in <= 4 limbs, i.e. p = k*2**(16L) - c
        for the curve base fields: 2**32 + 977 for secp256k1, 38 for
        2**255 - 19), a 2L-limb product folds to L limbs with one
        L x lc multiply instead of Barrett's two (L+1)-limb multiplies.
        The guards mirror fields.device.fold_reduce's bound analysis:
        after two folds the value is < b**L + b**(2*lc+1), which two
        conditional subtractions bring below p iff that bound is <= 3p.
        """
        c = (1 << (LIMB_BITS * self.limbs)) % self.modulus
        lc = max(1, (c.bit_length() + LIMB_BITS - 1) // LIMB_BITS)
        if lc > 4 or 2 * lc + 1 > self.limbs:
            return None
        bound = (1 << (LIMB_BITS * self.limbs)) + (1 << (LIMB_BITS * (2 * lc + 1)))
        if bound > 3 * self.modulus:
            return None
        return int_to_limbs(c, lc)

    def rand_int(self, rng) -> int:
        """Uniform field element from a host CSPRNG-style generator.

        ``rng`` must expose ``randbits(k)`` (``random.SystemRandom`` or
        ``random.Random`` for tests).  Rejection sampling keeps it uniform.
        """
        while True:
            x = rng.getrandbits(self.bits)
            if x < self.modulus:
                return x


@dataclasses.dataclass(frozen=True)
class LinearReduceSpec:
    """Precomputed constants for ``fields.device.linear_reduce``.

    Every array is a compile-time constant; every bound was verified with
    exact integer arithmetic in :func:`_build_linred`.
    """

    fold8: np.ndarray  # (2L, 2L) float32: fold8[k, m] = byte m of D_k
    c_limbs: np.ndarray  # (L,) uint32: c = b**L mod p
    n_split: int  # scan-free column-fold iterations
    shift_e: int  # quotient index = value >> (16*(L-1) + shift_e)
    qtable: np.ndarray  # (u_max+1,) uint32: floor(u * 2**s / p)
    np_limbs: np.ndarray  # (L+1,) uint32: b**(L+1) - p  (adds as "-p")


@dataclasses.dataclass(frozen=True)
class MulReduceSpec:
    """Precomputed constants for the fused multiply-reduce
    (``fields.device._mul_gemm`` and the ``ops.pallas_mxu`` kernel).

    Digit order (the device code must build digits in exactly this
    order): for the unnormalized product columns P_c,

    * digits [0, L)      — byte 0 of P_c, c = L .. 2L-1
    * digits [L, 2L)     — byte 1 of P_c, c = L .. 2L-1
    * digits [2L, 3L)    — byte 2 of P_c (< 2**6), c = L .. 2L-1
    * digit  3L          — P_{L-1} >> 16 (< 2**6), residue b**L mod p

    Every array is a compile-time constant; every bound was verified
    with exact integer arithmetic in :func:`_build_mulred`.
    """

    foldm: np.ndarray  # (3L+1, 2L) float32: foldm[i, m] = byte m of R_i
    c_limbs: np.ndarray  # (L,) uint32: c = b**L mod p
    n_split: int  # scan-free column-fold iterations
    shift_e: int  # quotient index = value >> (16*(L-1) + shift_e)
    qtable: np.ndarray  # (u_max+1,) uint32: floor(u * 2**s / p)
    np_limbs: np.ndarray  # (L+1,) uint32: b**(L+1) - p  (adds as "-p")


def _fold_tail(fs: FieldSpec, colb: list) -> tuple | None:
    """Shared tail of the linear-fold admission proofs: replay the
    scan-free column folds and derive the quotient table over exact
    per-column integer bounds ``colb``.

    Returns ``(n_split, shift_e, qtable, np_limbs, c)`` or ``None``
    when any invariant fails (inadmissible rather than silently wrong).
    """
    L, p, b = fs.limbs, fs.modulus, 1 << LIMB_BITS
    col_cap = (1 << 32) - (1 << LIMB_BITS)  # normalize()'s input contract
    if max(colb) > col_cap:
        return None

    # scan-free column folds — top spill times c = b**L mod p.
    c = (1 << (LIMB_BITS * L)) % p
    c_l = [int(v) for v in int_to_limbs(c, L)]
    vb = sum(cb << (LIMB_BITS * j) for j, cb in enumerate(colb))
    n_split, best = 0, (vb, list(colb))
    for it in range(1, 65):
        lob = [min(cb, b - 1) for cb in colb]
        hib = [cb >> LIMB_BITS for cb in colb]
        topb = hib[L - 1]
        colb = [
            lob[j] + (hib[j - 1] if j else 0) + topb * c_l[j] for j in range(L)
        ]
        if max(colb) > col_cap:
            return None
        vb = sum(cb << (LIMB_BITS * j) for j, cb in enumerate(colb))
        if vb >= best[0]:
            break
        n_split, best = it, (vb, list(colb))
    vb = best[0]
    if vb >= 1 << (LIMB_BITS * (L + 1)):  # must normalize into L+1 limbs
        return None

    # quotient-estimate table over the top ~12 bits.  With the index
    # u = floor(v / 2**s) and 2**s <= p, the true quotient is qtable[u]
    # or qtable[u] + 1 — one conditional subtraction fixes it.
    u_full_bits = (vb >> (LIMB_BITS * (L - 1))).bit_length()
    shift_e = max(0, u_full_bits - 12)
    s = LIMB_BITS * (L - 1) + shift_e
    if (1 << s) > p:
        return None
    u_max = vb >> s
    if u_max >= 1 << 13:
        return None
    qtable = np.array([(u << s) // p for u in range(u_max + 1)], np.uint32)
    q_max = vb // p
    if (b - 1) + q_max * (b - 1) > col_cap:  # final-fold column bound
        return None
    np_limbs = int_to_limbs((1 << (LIMB_BITS * (L + 1))) - p, L + 1)
    return n_split, shift_e, qtable, np_limbs, c


def _build_linred(fs: FieldSpec) -> LinearReduceSpec | None:
    """Derive and *prove* the linear-fold reduction constants.

    The device algorithm (fields.device.linear_reduce) is replayed here
    over per-column integer upper bounds; any violated invariant makes
    the field inadmissible (returns None) rather than silently wrong.
    """
    L, p, b = fs.limbs, fs.modulus, 1 << LIMB_BITS

    # Step 1: byte-matrix fold of the high L limbs.
    d_consts = [(1 << (8 * k + LIMB_BITS * L)) % p for k in range(2 * L)]
    fold8 = np.zeros((2 * L, 2 * L), np.float32)
    for k, dk in enumerate(d_consts):
        for m in range(2 * L):
            fold8[k, m] = (dk >> (8 * m)) & 0xFF
    f8i = fold8.astype(np.int64)
    # exact-float32 guard on the contraction's column sums
    if int((255 * f8i.sum(axis=0)).max()) >= 1 << 24:
        return None
    s16 = [
        int(255 * f8i[:, 2 * j].sum() + 256 * 255 * f8i[:, 2 * j + 1].sum())
        for j in range(L)
    ]
    colb = [(b - 1) + s for s in s16]  # + low limb of the input
    tail = _fold_tail(fs, colb)
    if tail is None:
        return None
    n_split, shift_e, qtable, np_limbs, c = tail
    return LinearReduceSpec(
        fold8=fold8,
        c_limbs=int_to_limbs(c, L),
        n_split=n_split,
        shift_e=shift_e,
        qtable=qtable,
        np_limbs=np_limbs,
    )


def _build_mulred(fs: FieldSpec) -> MulReduceSpec | None:
    """Derive and *prove* the fused multiply-reduce constants.

    The device algorithm (fields.device._mul_gemm / ops.pallas_mxu) is
    replayed over exact per-column integer upper bounds.  The input is
    the UNNORMALIZED schoolbook product column vector of two canonical
    elements: column P_c accumulates at most ``n_lo(c) + n_lo(c-1)``
    terms of < 2**16 (lo/hi halves of the 16x16 partial products), so
    P_c < 2**22 for L <= 24 — exactly the bound that makes the one-hot
    f32 product contraction exact.  Skipping the 2L-limb carry
    normalize means the fold digits are the three bytes of each high
    column (plus P_{L-1}'s 16-bit spill), against residues
    2**(16c + 8t) mod p, instead of linred's two bytes per limb.
    """
    L, p, b = fs.limbs, fs.modulus, 1 << LIMB_BITS

    # exact column caps of the unnormalized schoolbook product
    def n_lo(c: int) -> int:
        if c < 0 or c > 2 * L - 2:
            return 0
        return L - abs(c - (L - 1))

    pcap = [(n_lo(c) + n_lo(c - 1)) * (b - 1) for c in range(2 * L)]
    if max(pcap) >= 1 << 24:  # f32-exactness of the product contraction
        return None

    # digit caps and residues, in the MulReduceSpec digit order
    d_caps: list[int] = []
    residues: list[int] = []
    for t in range(3):
        for c in range(L, 2 * L):
            d_caps.append(min(0xFF, pcap[c] >> (8 * t)))
            residues.append((1 << (LIMB_BITS * c + 8 * t)) % p)
    d_caps.append(pcap[L - 1] >> LIMB_BITS)
    residues.append((1 << (LIMB_BITS * L)) % p)

    foldm = np.zeros((3 * L + 1, 2 * L), np.float32)
    for i, r in enumerate(residues):
        for m in range(2 * L):
            foldm[i, m] = (r >> (8 * m)) & 0xFF
    fmi = foldm.astype(np.int64)
    caps = np.array(d_caps, np.int64)
    # exact-float32 guard on the fold contraction's column sums
    if int((caps[:, None] * fmi).sum(axis=0).max()) >= 1 << 24:
        return None
    s16 = [
        int((caps * fmi[:, 2 * j]).sum() + 256 * (caps * fmi[:, 2 * j + 1]).sum())
        for j in range(L)
    ]
    # kept low part: full columns P_j for j < L-1, P_{L-1} mod 2**16
    keep = [pcap[j] for j in range(L - 1)] + [b - 1]
    colb = [k + s for k, s in zip(keep, s16)]
    tail = _fold_tail(fs, colb)
    if tail is None:
        return None
    n_split, shift_e, qtable, np_limbs, c = tail
    return MulReduceSpec(
        foldm=foldm,
        c_limbs=int_to_limbs(c, L),
        n_split=n_split,
        shift_e=shift_e,
        qtable=qtable,
        np_limbs=np_limbs,
    )


# --------------------------------------------------------------------------
# Registry of the concrete fields the framework ships with.
#
# Curve25519 / Ristretto (the reference's only backend, src/groups.rs):
#   base field p = 2^255 - 19, scalar field l = 2^252 + 27742...493.
# secp256k1 (BASELINE.json north-star curve).
# BLS12-381 G1 (BASELINE.json config #5, threshold-BLS).
# --------------------------------------------------------------------------

P25519 = FieldSpec("ed25519_base", (1 << 255) - 19, 16)
L25519 = FieldSpec(
    "ed25519_scalar",
    (1 << 252) + 27742317777372353535851937790883648493,
    16,
)

SECP256K1_P = FieldSpec(
    "secp256k1_base",
    (1 << 256) - (1 << 32) - 977,
    16,
)
SECP256K1_N = FieldSpec(
    "secp256k1_scalar",
    0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141,
    16,
)

BLS12_381_P = FieldSpec(
    "bls12_381_base",
    0x1A0111EA397FE69A4B1BA7B6434BACD764774B84F38512BF6730D2A0F6B0F6241EABFFFEB153FFFFB9FEFFFFFFFFAAAB,
    24,
)
BLS12_381_R = FieldSpec(
    "bls12_381_scalar",
    0x73EDA753299D7D483339D80809A1D80553BDA402FFFE5BFEFFFFFFFF00000001,
    16,
)

ALL_FIELDS = {
    fs.name: fs
    for fs in (P25519, L25519, SECP256K1_P, SECP256K1_N, BLS12_381_P, BLS12_381_R)
}
