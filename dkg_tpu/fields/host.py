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
import os
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


#: scalars in one draw from which the block path takes it.  Under it the
#: generator's state costs more to carry into numpy and back (624 words
#: each way, 0.6 ms a call on the v5e's host) than the draw saves: there
#: the two paths cross between 16,384 and 22,016 scalars (the sweep:
#: PERF.md section 6, PR 45)
BLOCK_MIN_SCALARS = 1 << 14

#: attempts one chunk of the block path reads: 2 MB of words at 256
#: bits, so a chunk's words, its comparison and its halves stay in cache
_BLOCK_ROWS = 1 << 16


def _block_reader(rng, words: int):
    """``(read, done)`` of the block path: ``read(m)`` gives the words of
    the generator's next ``m`` attempts as an ``(m, words)`` uint32
    array, without a Python int in between, and ``done()``, where there
    is a stream, leaves ``rng`` where those reads left it.

    ``random.Random``: its Mersenne state (624 words and a position)
    carried into a ``numpy.random.MT19937``, whose ``integers(0, 2**32,
    dtype=uint32)`` are exactly the successive 32-bit outputs that
    ``getrandbits`` concatenates (``tests/test_fields.py`` pins numpy to
    that); ``done`` carries the state back.  ``random.SystemRandom``:
    ``os.urandom``, no state and no ``done``."""
    if type(rng) is random.SystemRandom:
        return lambda m: np.frombuffer(os.urandom(4 * words * m), "<u4").reshape(m, words), None
    version, internal, gauss_next = rng.getstate()
    bit_gen = np.random.MT19937()
    bit_gen.state = {
        "bit_generator": "MT19937",
        "state": {"key": np.array(internal[:-1], np.uint32), "pos": internal[-1]},
    }
    gen = np.random.Generator(bit_gen)

    def done():
        state = bit_gen.state["state"]
        rng.setstate((version, (*state["key"].tolist(), int(state["pos"])), gauss_next))

    return lambda m: gen.integers(0, 1 << 32, size=(m, words), dtype=np.uint32), done


def _store_rows(out: np.ndarray, at: int, halves: np.ndarray) -> None:
    """``halves`` (k, L) to the positions ``at .. at + k`` of ``out``'s
    leading two axes in row-major order, for an ``out`` (R, C, L) that
    may be a strided view (a padded tensor's real lanes): the rest of a
    row begun, whole rows, the head of the next."""
    cols, limbs = out.shape[1:]
    k = halves.shape[0]
    r, c = divmod(at, cols)
    i = 0
    if c:
        i = min(cols - c, k)
        out[r, c : c + i] = halves[:i]
        r += 1
    whole = (k - i) // cols
    if whole:
        out[r : r + whole] = halves[i : i + whole * cols].reshape(whole, cols, limbs)
        i += whole * cols
        r += whole
    if i < k:
        out[r, : k - i] = halves[i:]


def draw_limbs(fs: FieldSpec, rng, shape, out: np.ndarray | None = None) -> np.ndarray:
    """``prod(shape)`` uniform field elements as a uint32 limb array
    ``(*shape, L)`` — THE definition of a coefficient draw: bit for bit
    ``encode(fs, [fs.rand_int(rng) for _ in range(prod(shape))])
    .reshape(*shape, L)``, with ``rng`` left in the state that loop
    leaves it in.  Written into ``out`` where given (uint32, ``(*shape,
    L)``, C-contiguous or, for a two-axis ``shape``, any strided view:
    the real lanes of a padded tensor), else into a fresh array.

    ``random.Random.getrandbits(k)`` for ``k > 32`` concatenates
    successive 32-bit Mersenne outputs, low word first, and shifts only
    the LAST word right by ``32 * words - k``.  So ``32 * words * m``
    bits of the stream are the words of ``m`` successive
    ``getrandbits(fs.bits)`` attempts, but for each attempt's top word,
    which is shifted here.  Attempts ``>= modulus`` are dropped as
    ``rand_int`` drops them, and each read asks for at most as many
    attempts as scalars are still missing, so the attempts are the
    sequential loop's attempts in order and the stream is never
    overshot.

    Which generators take such reads is observed from their type:
    ``random.Random`` (its stream is defined, above) and
    ``random.SystemRandom`` (``os.urandom`` bits: any slicing of them is
    uniform, no stream to preserve).  Anything else (a subclass, a stub
    with its own ``getrandbits``) takes the ``rand_int`` loop.  How the
    words are read is observed from the draw's size: under
    :data:`BLOCK_MIN_SCALARS` one ``getrandbits`` a round through a
    Python int (``bulk``), from it on :func:`_block_reader`'s chunks of
    :data:`_BLOCK_ROWS` attempts (``block``); the stream, and so every
    value, is the same on both sides.  Books
    ``coeff_draw_scalars_total{path}`` (scalars delivered) and, except on
    the loop, ``coeff_draw_rejected_total`` (attempts thrown away), once
    a call.
    """
    from ..utils import metrics  # utils imports dkg, which imports this module

    shape = tuple(shape)
    need = math.prod(shape)
    if out is None:
        out = np.empty((*shape, fs.limbs), np.uint32)
    elif out.shape != (*shape, fs.limbs) or out.dtype != np.uint32:
        raise ValueError(f"draw_limbs: out is {out.dtype}{out.shape}, not uint32{(*shape, fs.limbs)}")
    if type(rng) not in (random.Random, random.SystemRandom):
        out[...] = encode(fs, [fs.rand_int(rng) for _ in range(need)]).reshape(out.shape)
        metrics.REGISTRY.inc("coeff_draw_scalars_total", need, path="sequential")
        return out

    if out.flags.c_contiguous:
        lanes = out.reshape(1, need, fs.limbs)  # one row of all the scalars
    elif len(shape) == 2:
        lanes = out
    else:
        raise ValueError("draw_limbs: a strided out needs a two-axis shape")
    words = (fs.bits + 31) // 32
    shift = 32 * words - fs.bits
    mod_words = np.frombuffer(fs.modulus.to_bytes(4 * words, "little"), "<u4")
    if need >= BLOCK_MIN_SCALARS:
        path, chunk = "block", _BLOCK_ROWS
        read, done = _block_reader(rng, words)
    else:
        path, chunk, done = "bulk", need, None

        def read(m):
            buf = rng.getrandbits(32 * words * m).to_bytes(4 * words * m, "little")
            return np.frombuffer(buf, "<u4").reshape(m, words)

    filled = rejected = 0
    while filled < need:
        m = min(need - filled, chunk)
        rows = read(m)
        if shift:
            if not rows.flags.writeable:  # a view of bytes
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
        _store_rows(lanes, filled, rows.view("<u2")[:, : fs.limbs])
        filled += kept
    if done is not None:
        done()
    metrics.REGISTRY.inc("coeff_draw_scalars_total", need, path=path)
    metrics.REGISTRY.inc("coeff_draw_rejected_total", rejected)
    return out


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
