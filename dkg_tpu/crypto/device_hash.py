"""Device-resident transcript hashing: a BLAKE2s-compression Merkle tree.

Why: Fiat-Shamir batch randomizers must bind the COMPLETE round-1
transcript (commitments + share matrices).  Hashing on host means
shipping the full tensors over PCIe — ~2.1 GB at n=4096 — so the
digest is computed where the data lives and only 32 bytes cross to the
host.  This is the device-side reduction the protocol layer
(dkg.ceremony.transcript_digest) uses on its hot path; the byte-level
host path remains for wire parity.

Construction (documented because it is a custom tree mode — public,
deterministic, recomputable by any verifier from the broadcast data):

* Input: any uint32 tensor, flattened to words, zero-padded to 16-word
  (64-byte) blocks, block count padded to a power of two.
* Leaf i: one BLAKE2s compression (RFC 7693 §3.2) of block i with
  h = IV ^ params(node_depth=0), t = 64*i (position binding), f0 = -1.
* Interior: compression of (left || right) digests with
  h = IV ^ params(node_depth=1), t = level, f0 = -1; fixed arity 2, so
  with domain-separated leaves this is a standard Merkle
  collision-resistance argument.
* Root: one final compression binding the ORIGINAL word count and a
  caller domain tag, so zero-padding and tree-height ambiguities cannot
  collide (interior compressions always carry t = level >= 1; the root
  carries t = 0, separating it from them).

The initial state is IV XOR the RFC 7693 §2.5 parameter block: word 0
packs digest_length=32 | key_length=0 | fanout=2 | depth=255
(P_WORD0), and word 3's node_depth byte (parameter-block byte 14) is 0
for leaves and 1 for interior/root compressions, with inner_length=32
(byte 15) — so leaf/interior domain separation is exactly the RFC's
tree-hashing node_depth mechanism.  Collision resistance reduces to
that of the BLAKE2s compression function.

The pure-Python twin (``tree_digest_host``) is the test oracle and the
multi-host fold reference.

Dispatch: the public entry points (:func:`tree_digest`,
:func:`row_digests`) are BACKEND-DISPATCHED.  The device leg runs the
whole tree as ONE jitted program per (shape, domain-arity) — rounds
roll up in a ``lax.fori_loop`` and the four column/diagonal G-calls of
each half-round vectorize over a 4-wide lane axis, so the traced graph
stays small and the per-op XLA dispatch that made the eager tree the
ceremony's slowest phase (BENCH_r06: 5.5 s at n=64 on CPU) disappears.
The host leg (``crypto.blake2s``) is the same tree in batched numpy —
on CPU backends XLA per-op overhead dominates the tiny uint32 ops
exactly as it did for point encoding (``groups.device.encode_batch``),
so ``digest_dispatch`` routes CPU transcripts there.  Both legs are
bit-identical; ``DKG_TPU_DIGEST=device|host|auto`` (validated) forces a
leg.
"""

from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

IV = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)

# RFC 7693 §2.5 parameter words.  Word 0: digest_length=32 (byte 0),
# key_length=0 (byte 1), fanout=2 (byte 2), depth=255 (byte 3).
# Word 3: node_depth (byte 14 -> bits 16..23) 0 for leaves / 1 for
# interior+root, inner_length=32 (byte 15 -> bits 24..31).
P_WORD0 = 0xFF020020
P3_LEAF = 32 << 24
P3_NODE = (1 << 16) | (32 << 24)

SIGMA = (
    (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15),
    (14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3),
    (11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4),
    (7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8),
    (9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13),
    (2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9),
    (12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11),
    (13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10),
    (6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5),
    (10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0),
)

MASK32 = 0xFFFFFFFF


# ---------------------------------------------------------------------------
# device (jnp) compression, batched over leading axes
# ---------------------------------------------------------------------------


def _ror(x, n):
    return (x >> n) | (x << (32 - n))


def _compress_dev(h, m, t, f0):
    """Batched BLAKE2s compression: h (..., 8), m (..., 16), t (...,) or
    scalar, f0 scalar -> (..., 8).  All uint32.

    Trace-size discipline (this runs INSIDE the jitted tree): the ten
    rounds roll up in a ``lax.fori_loop`` with the message schedule as a
    gathered (10, 16) constant, and each half-round's four independent
    G-calls run as ONE G over a 4-wide lane axis — the standard
    column/diagonal formulation (diagonals are lane-rolls of the state
    quarters).  The traced graph is ~2 G-bodies instead of 80, so a
    whole Merkle level compiles in milliseconds while the compiled code
    is identical arithmetic to the unrolled form."""
    t = jnp.asarray(t, jnp.uint32)
    batch = jnp.broadcast_shapes(h.shape[:-1], m.shape[:-1], t.shape)
    h = jnp.broadcast_to(h, batch + (8,))
    m = jnp.broadcast_to(m, batch + (16,))
    iv = jnp.asarray(np.asarray(IV, np.uint32))
    v = jnp.concatenate([h, jnp.broadcast_to(iv, h.shape)], axis=-1)
    v = v.at[..., 12].set(v[..., 12] ^ jnp.broadcast_to(t, batch))
    v = v.at[..., 14].set(v[..., 14] ^ jnp.uint32(f0))
    sigma = jnp.asarray(np.asarray(SIGMA, np.int32))

    def g(a, b, c, d, x, y):
        a = a + b + x  # uint32 wraps mod 2^32 natively
        d = _ror(d ^ a, 16)
        c = c + d
        b = _ror(b ^ c, 12)
        a = a + b + y
        d = _ror(d ^ a, 8)
        c = c + d
        b = _ror(b ^ c, 7)
        return a, b, c, d

    def round_body(rnd, v):
        ms = jnp.take(m, sigma[rnd], axis=-1)
        a, b, c, d = (v[..., 0:4], v[..., 4:8], v[..., 8:12], v[..., 12:16])
        # columns: G(v0,v4,v8,v12) .. G(v3,v7,v11,v15)
        a, b, c, d = g(a, b, c, d, ms[..., 0:8:2], ms[..., 1:8:2])
        # diagonals: G(v0,v5,v10,v15) .. G(v3,v4,v9,v14) == lane rolls
        b = jnp.roll(b, -1, axis=-1)
        c = jnp.roll(c, -2, axis=-1)
        d = jnp.roll(d, -3, axis=-1)
        a, b, c, d = g(a, b, c, d, ms[..., 8:16:2], ms[..., 9:16:2])
        b = jnp.roll(b, 1, axis=-1)
        c = jnp.roll(c, 2, axis=-1)
        d = jnp.roll(d, 3, axis=-1)
        return jnp.concatenate([a, b, c, d], axis=-1)

    v = lax.fori_loop(0, 10, round_body, v)
    return h ^ v[..., 0:8] ^ v[..., 8:16]


def _h_init(p3: int, batch: tuple) -> jax.Array:
    h = np.asarray(IV, np.uint32).copy()
    h[0] ^= np.uint32(P_WORD0)
    h[3] ^= np.uint32(p3)
    return jnp.broadcast_to(jnp.asarray(h), batch + (8,))


def _pad_blocks(words: jax.Array) -> jax.Array:
    """(..., W) words -> (..., NL, 16) blocks, NL a power of two."""
    w = words.shape[-1]
    nl = max(1, -(-w // 16))
    nl_pow2 = 1 << (nl - 1).bit_length()
    pad = nl_pow2 * 16 - w
    if pad:
        words = jnp.pad(words, [(0, 0)] * (words.ndim - 1) + [(0, pad)])
    return words.reshape(words.shape[:-1] + (nl_pow2, 16))


def digest_dispatch() -> str:
    """Which transcript-digest leg runs: ``"device"`` or ``"host"``.

    ``DKG_TPU_DIGEST=device|host|auto`` (validated via envknobs — a typo
    must fail loudly, not silently measure the wrong leg) forces it;
    ``auto``/unset picks the jitted device tree on TPU and the batched
    numpy tree (``crypto.blake2s``) elsewhere, where XLA per-op overhead
    on tiny uint32 ops dominates.  Both legs are bit-identical
    (tests/test_digest_dispatch.py), so the choice is pure performance —
    rho never depends on it.
    """
    from ..fields import device as fd
    from ..utils import envknobs

    mode = envknobs.choice(
        "DKG_TPU_DIGEST",
        ("device", "host", "auto"),
        "a typo would silently run the slow digest leg",
    )
    if mode is None or mode == "auto":
        return "device" if fd._on_tpu() else "host"
    return mode


def tree_digest(tensor, domain: int = 0, dispatch: str | None = None):
    """Merkle digest of a uint32 tensor's words -> (8,) uint32.

    Leading axes before the last are flattened into the word stream;
    use :func:`row_digests` to keep a batch axis independent.
    Backend-dispatched (see :func:`digest_dispatch`); ``dispatch``
    pins a leg (the cross-leg equality tests do).
    """
    if dispatch is None:
        dispatch = digest_dispatch()
    if dispatch == "host":
        from . import blake2s

        return blake2s.tree_digest_np(np.asarray(tensor), domain)
    words = jnp.asarray(tensor, jnp.uint32).reshape(-1)
    return _tree_from_words((words[None, :],), domain)[0]


def row_digests(tensor, domain: int = 0, dispatch: str | None = None, lead: int = 1):
    """Independent Merkle digest per row: (R, ...) -> (R, 8) uint32.

    Each row's digest depends only on that row (and the shared shape),
    so dealer-sharded tensors hash shard-locally and only (R, 8) words
    ever need to cross hosts — the shard-foldable structure
    transcript hashing requires.  Backend-dispatched like
    :func:`tree_digest`; the host leg returns numpy, the device leg a
    jax array (every consumer folds through ``np.asarray`` anyway).

    ``tensor`` is one array or a tuple of arrays; the first ``lead``
    axes (shared by all of them) are the row axes and flatten to
    R = their product, the rest of each array is its share of the row's
    words, the arrays' shares in the tuple's order.  On the device leg
    that flattening, the ``uint32`` cast and the joining happen inside
    the one jitted program: a call is ONE dispatch whatever it is handed,
    device arrays are read where they are, and numpy arrays go in as the
    program's arguments.
    """
    parts = tuple(tensor) if isinstance(tensor, (tuple, list)) else (tensor,)
    if dispatch is None:
        dispatch = digest_dispatch()
    if dispatch == "host":
        from . import blake2s

        rows = math.prod(np.shape(parts[0])[:lead])
        words = [np.asarray(p).reshape(rows, -1) for p in parts]
        return blake2s.row_digests_np(
            words[0] if len(words) == 1 else np.concatenate(words, axis=-1), domain
        )
    return _tree_from_words(parts, domain, lead)


def _tree_from_words(parts: tuple, domain: int, lead: int = 1) -> jax.Array:
    """Jit entry for the device tree: one compiled program per tuple of
    shapes, shared across domains (the domain tag rides in as a traced
    scalar, handed over as a numpy scalar so that no device array is
    made for it first: the rows_a/rows_e calls of
    ``_dealer_rows_device`` — same shape, different domain — reuse one
    executable).  ``parts`` is a tuple as :func:`row_digests` takes it;
    what is not a device array yet goes in as ``uint32`` numpy."""
    parts = tuple(
        p if isinstance(p, jax.Array) else np.asarray(p, np.uint32) for p in parts
    )
    return _tree_from_words_jit(parts, np.uint32(int(domain) & MASK32), lead)


@functools.partial(jax.jit, static_argnums=2)
def _tree_from_words_jit(parts: tuple, domain: jax.Array, lead: int) -> jax.Array:
    r = math.prod(parts[0].shape[:lead])
    flat = [p.astype(jnp.uint32).reshape(r, -1) for p in parts]
    words = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=-1)
    w = words.shape[-1]
    blocks = _pad_blocks(words)  # (R, NL, 16)
    nl = blocks.shape[-2]
    t_leaf = jnp.arange(nl, dtype=jnp.uint32) * 64
    h = _compress_dev(_h_init(P3_LEAF, (r, nl)), blocks, t_leaf[None, :], MASK32)
    level = 1
    while h.shape[-2] > 1:  # trace-time loop: log2(NL) compressions
        pairs = h.reshape(r, h.shape[-2] // 2, 16)
        h = _compress_dev(
            _h_init(P3_NODE, pairs.shape[:-1]), pairs, jnp.uint32(level), MASK32
        )
        level += 1
    tail = (
        jnp.zeros((8,), jnp.uint32)
        .at[0]
        .set(jnp.uint32(w & MASK32))
        .at[1]
        .set(domain)
    )
    root_block = jnp.concatenate(
        [h[:, 0, :], jnp.broadcast_to(tail, (r, 8))], axis=-1
    )
    return _compress_dev(_h_init(P3_NODE, (r,)), root_block, jnp.uint32(0), MASK32)


# ---------------------------------------------------------------------------
# pure-Python twin (test oracle + spec)
# ---------------------------------------------------------------------------


def _compress_py(h, m, t, f0):
    def ror(x, n):
        return ((x >> n) | (x << (32 - n))) & MASK32

    v = list(h) + list(IV)
    v[12] ^= t & MASK32
    v[14] ^= f0 & MASK32

    def g(a, b, c, d, x, y):
        a = (a + b + x) & MASK32
        d = ror(d ^ a, 16)
        c = (c + d) & MASK32
        b = ror(b ^ c, 12)
        a = (a + b + y) & MASK32
        d = ror(d ^ a, 8)
        c = (c + d) & MASK32
        b = ror(b ^ c, 7)
        return a, b, c, d

    for rnd in range(10):
        s = SIGMA[rnd]
        v[0], v[4], v[8], v[12] = g(v[0], v[4], v[8], v[12], m[s[0]], m[s[1]])
        v[1], v[5], v[9], v[13] = g(v[1], v[5], v[9], v[13], m[s[2]], m[s[3]])
        v[2], v[6], v[10], v[14] = g(v[2], v[6], v[10], v[14], m[s[4]], m[s[5]])
        v[3], v[7], v[11], v[15] = g(v[3], v[7], v[11], v[15], m[s[6]], m[s[7]])
        v[0], v[5], v[10], v[15] = g(v[0], v[5], v[10], v[15], m[s[8]], m[s[9]])
        v[1], v[6], v[11], v[12] = g(v[1], v[6], v[11], v[12], m[s[10]], m[s[11]])
        v[2], v[7], v[8], v[13] = g(v[2], v[7], v[8], v[13], m[s[12]], m[s[13]])
        v[3], v[4], v[9], v[14] = g(v[3], v[4], v[9], v[14], m[s[14]], m[s[15]])
    return [h[i] ^ v[i] ^ v[i + 8] for i in range(8)]


def tree_digest_host(words, domain: int = 0) -> list[int]:
    """Pure-Python twin of :func:`tree_digest` on a 1-D word list."""
    words = [int(x) & MASK32 for x in words]
    w = len(words)
    nl = max(1, -(-w // 16))
    nl_pow2 = 1 << (nl - 1).bit_length()
    words = words + [0] * (nl_pow2 * 16 - w)

    def h_init(p3):
        h = list(IV)
        h[0] ^= P_WORD0
        h[3] ^= p3
        return h

    level_nodes = [
        _compress_py(h_init(P3_LEAF), words[i * 16 : (i + 1) * 16], 64 * i, MASK32)
        for i in range(nl_pow2)
    ]
    level = 1
    while len(level_nodes) > 1:
        level_nodes = [
            _compress_py(
                h_init(P3_NODE),
                level_nodes[2 * i] + level_nodes[2 * i + 1],
                level,
                MASK32,
            )
            for i in range(len(level_nodes) // 2)
        ]
        level += 1
    root_block = level_nodes[0] + [w & MASK32, domain & MASK32, 0, 0, 0, 0, 0, 0]
    return _compress_py(h_init(P3_NODE), root_block, 0, MASK32)


def digest_to_bytes(digest) -> bytes:
    """(8,) uint32 digest -> 32 little-endian bytes.

    Host-side convenience for EXTERNAL verifiers serialising tree/row
    digests; the in-package transcript fold consumes the uint32 arrays
    directly (dkg.ceremony._fold_digest_device)."""
    return b"".join(int(x).to_bytes(4, "little") for x in np.asarray(digest))
