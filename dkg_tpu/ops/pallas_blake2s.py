"""One level of the transcript's BLAKE2s tree as a Pallas kernel
(``crypto/device_hash.py`` has the tree and the construction).

WORD-MAJOR: a level's message words are ``(16, nodes, rows)``, the word
index the LEADING axis, nodes major of rows.  A grid step takes a block
of ``BLOCK_NODES`` x ``BLOCK_ROWS`` compressions, reads its sixteen
message slabs once and keeps the sixteen state slabs on the chip through
the ten rounds: every add, xor and rotate of a G-call is elementwise on
whole vectors with the rows on the lanes, a round's message words are
slabs picked by ``SIGMA[round]`` on the leading axis (which slab, never
which lane) and the diagonal step is a renaming of four state words.

Measured alone on a v5e (PERF.md section 6, PR 46): the leaf level of a
(1024 rows x 2048 leaves) tree, 2.1 M compressions, in 0.87 ms; the
whole tree in 2.8 ms of device time, where the same layout as plain
``jnp`` ops under a ``fori_loop`` took 17.7 ms (its state crosses HBM
every round) and the words-last form before it 59.4 ms.  Blocks of
8 x 128 were 19 % slower, 16 x 256, 32 x 128 and 8 x 512 the same;
unrolling the ten rounds gave 10 % for 5 s more lowering a tree.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..crypto.device_hash import IV, MASK32, P_WORD0, SIGMA
from ..utils import metrics

#: A grid step's block: this many nodes by this many rows, two vectors a
#: word (the chip's tile is 8 sublanes by 128 lanes).
BLOCK_NODES, BLOCK_ROWS = 8, 256


def _ror(x, n):
    return (x >> n) | (x << (32 - n))


def _g(a, b, c, d, x, y):
    a = a + b + x  # uint32 wraps mod 2^32 natively
    d = _ror(d ^ a, 16)
    c = c + d
    b = _ror(b ^ c, 12)
    a = a + b + y
    d = _ror(d ^ a, 8)
    c = c + d
    b = _ror(b ^ c, 7)
    return a, b, c, d


def _level_kernel(sigma_ref, m_ref, out_ref, *, p3, leaf, level):
    """One block: ``m_ref`` (16, nodes, rows) message words -> ``out_ref``
    (8, nodes, rows) chaining values.  ``sigma_ref`` is SIGMA flat in
    scalar memory: a round's message word is the slab ``m_ref[sigma]``."""
    batch = m_ref.shape[1:]
    h = np.asarray(IV, np.uint32).copy()
    h[0] ^= np.uint32(P_WORD0)
    h[3] ^= np.uint32(p3)
    v = [jnp.full(batch, x, jnp.uint32) for x in (*h, *IV)]
    if leaf:  # t = 64 * the leaf's position in its row
        node = pl.program_id(0) * batch[0] + lax.broadcasted_iota(jnp.int32, batch, 0)
        v[12] = v[12] ^ (node.astype(jnp.uint32) * jnp.uint32(64))
    else:
        v[12] = v[12] ^ jnp.uint32(level)
    v[14] = v[14] ^ jnp.uint32(MASK32)  # f0: every compression is final

    def round_body(rnd, v):
        v = list(v)
        x = [m_ref[sigma_ref[rnd * 16 + j]] for j in range(16)]
        v[0], v[4], v[8], v[12] = _g(v[0], v[4], v[8], v[12], x[0], x[1])
        v[1], v[5], v[9], v[13] = _g(v[1], v[5], v[9], v[13], x[2], x[3])
        v[2], v[6], v[10], v[14] = _g(v[2], v[6], v[10], v[14], x[4], x[5])
        v[3], v[7], v[11], v[15] = _g(v[3], v[7], v[11], v[15], x[6], x[7])
        v[0], v[5], v[10], v[15] = _g(v[0], v[5], v[10], v[15], x[8], x[9])
        v[1], v[6], v[11], v[12] = _g(v[1], v[6], v[11], v[12], x[10], x[11])
        v[2], v[7], v[8], v[13] = _g(v[2], v[7], v[8], v[13], x[12], x[13])
        v[3], v[4], v[9], v[14] = _g(v[3], v[4], v[9], v[14], x[14], x[15])
        return tuple(v)

    # a loop, not eighty G-bodies a level: a tree is a dozen levels, and the
    # tests run this in interpret mode, where XLA:CPU compiles the body
    v = lax.fori_loop(0, 10, round_body, tuple(v))
    for i in range(8):
        out_ref[i] = jnp.uint32(h[i]) ^ v[i] ^ v[i + 8]


def blake2s_level(m: jax.Array, p3: int, leaf: bool, level: int, *, interpret: bool) -> jax.Array:
    """All of one level's compressions: ``m`` (16, nodes, rows) message
    words -> (8, nodes, rows) chaining values, with h = IV ^ params(``p3``),
    f0 = -1 and t = 64 * the node's index (``leaf``) or ``level``."""
    metrics.REGISTRY.inc("pallas_calls_total", kernel="blake2s_level")
    _, nodes, rows = m.shape
    bn, br = min(nodes, BLOCK_NODES), min(rows, BLOCK_ROWS)
    return pl.pallas_call(
        functools.partial(_level_kernel, p3=p3, leaf=leaf, level=level),
        out_shape=jax.ShapeDtypeStruct((8, nodes, rows), jnp.uint32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(nodes, bn), pl.cdiv(rows, br)),
            in_specs=[pl.BlockSpec((16, bn, br), lambda i, j, sigma: (0, i, j))],
            out_specs=pl.BlockSpec((8, bn, br), lambda i, j, sigma: (0, i, j)),
        ),
        interpret=interpret,
        name="blake2s_level",
    )(jnp.asarray(np.asarray(SIGMA, np.int32).reshape(-1)), m)
