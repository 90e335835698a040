"""Host-side (Python-int) reference field arithmetic.

This is the bit-exact oracle the device path is tested against, and the
implementation used for cold-path host work (point (de)compression,
hash-to-group, Fiat-Shamir transcripts) where byte-twiddling is a poor TPU
fit.  It mirrors the role `curve25519-dalek`'s scalar/field code plays for
the reference (src/groups.rs:11-53).

All functions take a :class:`~dkg_tpu.fields.spec.FieldSpec` and plain
Python ints; batching helpers convert between ints and limb arrays.
"""

from __future__ import annotations

import math
import random

import numpy as np

from .spec import FieldSpec, int_to_limbs, limbs_to_int


def add(fs: FieldSpec, a: int, b: int) -> int:
    return (a + b) % fs.modulus


def sub(fs: FieldSpec, a: int, b: int) -> int:
    return (a - b) % fs.modulus


def mul(fs: FieldSpec, a: int, b: int) -> int:
    return (a * b) % fs.modulus


def neg(fs: FieldSpec, a: int) -> int:
    return (-a) % fs.modulus


def inv(fs: FieldSpec, a: int) -> int:
    if a % fs.modulus == 0:
        raise ZeroDivisionError("inverse of zero")
    return pow(a, fs.modulus - 2, fs.modulus)


def powmod(fs: FieldSpec, a: int, e: int) -> int:
    return pow(a, e, fs.modulus)


def to_bytes(fs: FieldSpec, a: int) -> bytes:
    """Canonical little-endian encoding (reference: traits.rs:162-164)."""
    return int(a % fs.modulus).to_bytes(fs.nbytes, "little")


def from_bytes(fs: FieldSpec, data: bytes) -> int | None:
    """Strict canonical decode; None on wrong length or value >= modulus.

    Length is enforced so every element has exactly one accepted encoding
    (wire-format non-malleability, as in the reference's fixed 32-byte
    scalar/point encodings, traits.rs:162-164).
    """
    if len(data) != fs.nbytes:
        return None
    x = int.from_bytes(data, "little")
    if x >= fs.modulus:
        return None
    return x


def from_bytes_mod_order_wide(fs: FieldSpec, data: bytes) -> int:
    """Reduce an oversized little-endian byte string mod p.

    Used for hash-to-scalar (reference: traits.rs hash_to_scalar via
    Blake2b, src/groups.rs:19-23): 64 uniform bytes reduced mod the group
    order give a near-uniform scalar.
    """
    return int.from_bytes(data, "little") % fs.modulus


# ---------------------------------------------------------------------------
# int <-> limb-array conversion (batched)
# ---------------------------------------------------------------------------


def encode(fs: FieldSpec, values) -> np.ndarray:
    """ints (scalar or nested list) -> uint32 limb array (..., L)."""
    arr = np.asarray(values, dtype=object)
    out = np.zeros(arr.shape + (fs.limbs,), dtype=np.uint32)
    for idx in np.ndindex(arr.shape):
        out[idx] = int_to_limbs(int(arr[idx]) % fs.modulus, fs.limbs)
    if arr.shape == ():
        return out.reshape(fs.limbs)
    return out


#: attempts one bulk read asks its generator for at most (32 MB of
#: stream at 256 bits): bounds the big int and keeps ``getrandbits``'s
#: argument inside a C int at any committee size
_DRAW_ATTEMPTS_PER_READ = 1 << 20


def draw_limbs(fs: FieldSpec, rng, shape) -> np.ndarray:
    """``prod(shape)`` uniform field elements as a uint32 limb array
    ``(*shape, L)`` — THE definition of a coefficient draw: bit for bit
    ``encode(fs, [fs.rand_int(rng) for _ in range(prod(shape))])
    .reshape(*shape, L)``, with ``rng`` left in the state that loop
    leaves it in.

    ``random.Random.getrandbits(k)`` for ``k > 32`` concatenates
    successive 32-bit Mersenne outputs, low word first, and shifts only
    the LAST word right by ``32 * words - k``.  So one read of
    ``32 * words * m`` bits is the words of ``m`` successive
    ``getrandbits(fs.bits)`` attempts, but for each attempt's top word,
    which is shifted here.  Attempts ``>= modulus`` are dropped as
    ``rand_int`` drops them, and each round reads exactly as many
    attempts as scalars are still missing (never more), so the attempts
    are the sequential loop's attempts in order and the stream is never
    overshot.

    Which generators take the bulk read is observed from their type:
    ``random.Random`` (its stream is defined, above) and
    ``random.SystemRandom`` (``os.urandom`` bits: any slicing of them is
    uniform, no stream to preserve).  Anything else (a subclass, a stub
    with its own ``getrandbits``) takes the ``rand_int`` loop.  Books
    ``coeff_draw_scalars_total{path}`` (scalars delivered) and, on the
    bulk path, ``coeff_draw_rejected_total`` (attempts thrown away),
    once a call.
    """
    from ..utils import metrics  # utils imports dkg, which imports this module

    need = math.prod(shape)
    if type(rng) not in (random.Random, random.SystemRandom):
        out = encode(fs, [fs.rand_int(rng) for _ in range(need)])
        metrics.REGISTRY.inc("coeff_draw_scalars_total", need, path="sequential")
        return out.reshape(*shape, fs.limbs)

    words = (fs.bits + 31) // 32
    shift = 32 * words - fs.bits
    mod_words = np.frombuffer(fs.modulus.to_bytes(4 * words, "little"), "<u4")
    out = np.empty((need, fs.limbs), np.uint32)
    filled = rejected = 0
    while filled < need:
        m = min(need - filled, _DRAW_ATTEMPTS_PER_READ)
        buf = rng.getrandbits(32 * words * m).to_bytes(4 * words * m, "little")
        rows = np.frombuffer(buf, "<u4").reshape(m, words)
        if shift:
            rows = rows.copy()
            rows[:, -1] >>= shift
        # lexicographic rows < modulus, decided by the top word alone
        # wherever it differs from the modulus's (all but 2**-32 of
        # secp256k1's attempts); the ties compare word by word below it
        top = rows[:, -1]
        keep = top < mod_words[-1]
        tie = np.flatnonzero(top == mod_words[-1])
        if tie.size:
            below = np.zeros(tie.size, bool)
            equal = np.ones(tie.size, bool)
            for w in range(words - 2, -1, -1):
                col = rows[tie, w]
                below |= equal & (col < mod_words[w])
                equal &= col == mod_words[w]
            keep[tie] = below
        kept = int(np.count_nonzero(keep))
        if kept < m:
            rows = rows[keep]
            rejected += m - kept
        # little-endian uint32 words -> 16-bit limbs in uint32; a
        # FieldSpec's modulus fills its top limb, so 2 * words >= L and
        # the halves past L are zero in every row kept
        out[filled : filled + kept] = rows.view("<u2")[:, : fs.limbs]
        filled += kept
    metrics.REGISTRY.inc("coeff_draw_scalars_total", need, path="bulk")
    metrics.REGISTRY.inc("coeff_draw_rejected_total", rejected)
    return out.reshape(*shape, fs.limbs)


def decode(fs: FieldSpec, limbs) -> np.ndarray:
    """uint32 limb array (..., L) -> object array of Python ints."""
    limbs = np.asarray(limbs)
    batch = limbs.shape[:-1]
    out = np.empty(batch, dtype=object)
    for idx in np.ndindex(batch):
        out[idx] = limbs_to_int(limbs[idx])
    return out


def decode_int(fs: FieldSpec, limbs) -> int:
    """Single limb vector -> int."""
    return limbs_to_int(np.asarray(limbs))
