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
whole tree as ONE jitted program per (shape, domain-arity), so the
per-op XLA dispatch that made the eager tree the ceremony's slowest
phase (BENCH_r06: 5.5 s at n=64 on CPU) is gone, and it runs it
WORD-MAJOR: the sixteen words of a message block are an array's LEADING
axis and the batch its others, ``(16, nodes, rows)`` with a level's
nodes major of the rows.  A level is ONE Pallas kernel
(``ops.pallas_blake2s.blake2s_level``; interpret mode off the TPU): a
step takes a block of nodes x rows, reads its sixteen message slabs once
and keeps the sixteen state slabs on the chip through the ten rounds
(a ``lax.fori_loop``, so a level traces one round's G-bodies).  Every add,
xor and rotate is on whole vectors with the rows on the lanes; the
message schedule picks slabs by ``SIGMA[round]`` on the leading axis
(which slab, never which lane); the diagonal step renames four state
words; and a level's pairs (left || right) are the even and the odd
nodes of a major axis, so no lane is de-interleaved.  The words of a row
arrive row-major, so there is one transposition on the way in; the
``(rows, 8)`` digests come out as they always did.  (Until PR 46 the
words were the LAST axis, the schedule a ``take`` under a traced index
and the diagonals ``roll`` s of a 4-wide axis; what that cost on the
chip, and what the same layout costs as plain ``jnp`` ops under a
``fori_loop``, is in PERF.md section 6, PR 46.)
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
    what is not a device array yet goes in as ``uint32`` numpy.  The
    kernels run in interpret mode wherever the backend is not a TPU: a
    static argument, so a program traced for one never serves the other."""
    from ..fields import device as fd

    parts = tuple(
        p if isinstance(p, jax.Array) else np.asarray(p, np.uint32) for p in parts
    )
    return _tree_from_words_jit(parts, np.uint32(int(domain) & MASK32), lead, not fd._on_tpu())


@functools.partial(jax.jit, static_argnums=(2, 3))
def _tree_from_words_jit(parts: tuple, domain: jax.Array, lead: int, interpret: bool) -> jax.Array:
    from ..ops.pallas_blake2s import blake2s_level

    r = math.prod(parts[0].shape[:lead])
    flat = [p.astype(jnp.uint32).reshape(r, -1) for p in parts]
    words = flat[0] if len(flat) == 1 else jnp.concatenate(flat, axis=-1)
    w = words.shape[-1]
    nl = max(1, -(-w // 16))
    nl = 1 << (nl - 1).bit_length()  # leaves: a power of two of 16-word blocks
    if nl * 16 != w:
        words = jnp.pad(words, ((0, 0), (0, nl * 16 - w)))
    # the one transposition: word w of leaf i of row j at [w, i, j]
    blocks = jnp.transpose(words.reshape(r, nl, 16), (2, 1, 0))
    h = blake2s_level(blocks, P3_LEAF, True, 0, interpret=interpret)  # (8, nl, r)
    level = 1
    while h.shape[1] > 1:  # trace-time loop: log2(nl) levels
        # children 2i, 2i+1 are neighbours on the major axis
        pairs = jnp.concatenate([lax.slice_in_dim(h, k, None, stride=2, axis=1) for k in (0, 1)])
        h = blake2s_level(pairs, P3_NODE, False, level, interpret=interpret)
        level += 1
    tail = [jnp.full((1, 1, r), x, jnp.uint32) for x in (w & MASK32, domain, 0, 0, 0, 0, 0, 0)]
    root = blake2s_level(jnp.concatenate([h] + tail), P3_NODE, False, 0, interpret=interpret)  # (8, 1, r)
    return jnp.transpose(root[:, 0, :])


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
