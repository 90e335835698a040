"""Device Merkle-tree transcript hash (crypto/device_hash.py).

Three layers: (1) the BLAKE2s compression function is validated against
CPython's hashlib.blake2s on single-block messages (same IV/SIGMA/G —
the only difference in a standard single-block hash is the parameter
word, which we set to the standard 0x01010020); (2) the jnp tree equals
the pure-Python twin on assorted shapes; (3) the ceremony-level device
transcript digest binds every limb, like the host digest it replaces on
the hot path.
"""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dkg_tpu.crypto import device_hash as dh
from dkg_tpu.ops import pallas_blake2s as pb

RNG = random.Random(0xD167)


def _std_single_block_hash_py(data: bytes) -> bytes:
    """Standard BLAKE2s-256 of <=64 bytes via our compression function."""
    assert len(data) <= 64
    h = list(dh.IV)
    h[0] ^= 0x01010020  # digest_length=32, fanout=1, depth=1
    block = data + b"\x00" * (64 - len(data))
    words = [int.from_bytes(block[i * 4 : (i + 1) * 4], "little") for i in range(16)]
    out = dh._compress_py(h, words, len(data), dh.MASK32)
    return b"".join(w.to_bytes(4, "little") for w in out)


@pytest.mark.parametrize("size", [0, 1, 3, 31, 32, 63, 64])
def test_compression_matches_hashlib_blake2s(size):
    data = bytes(RNG.randrange(256) for _ in range(size))
    assert _std_single_block_hash_py(data) == hashlib.blake2s(data).digest()


@pytest.mark.parametrize("words", [1, 15, 16, 17, 64, 100, 1024])
def test_device_tree_matches_python_twin(words):
    vals = [RNG.randrange(1 << 32) for _ in range(words)]
    dev = np.asarray(dh.tree_digest(jnp.asarray(vals, jnp.uint32), domain=7))
    ref = dh.tree_digest_host(vals, domain=7)
    assert [int(x) for x in dev] == ref
    # byte serialisation (external-verifier convenience) agrees too
    assert dh.digest_to_bytes(dev) == dh.digest_to_bytes(ref)


def test_row_digests_are_independent_rows():
    rows = np.asarray(
        [[RNG.randrange(1 << 32) for _ in range(40)] for _ in range(5)], np.uint32
    )
    got = np.asarray(dh.row_digests(jnp.asarray(rows), domain=3))
    for i in range(5):
        solo = np.asarray(dh.tree_digest(jnp.asarray(rows[i]), domain=3))
        assert (got[i] == solo).all()


def test_domain_and_length_bind():
    vals = [7] * 32
    a = dh.tree_digest_host(vals, domain=1)
    b = dh.tree_digest_host(vals, domain=2)
    assert a != b
    # trailing zeros change the word count, hence the digest
    c = dh.tree_digest_host(vals + [0], domain=1)
    assert a != c
    # leaf vs interior domains differ: a 16-word input's digest is not
    # the digest of its own leaf hash reinterpreted
    leaf_only = dh.tree_digest_host(vals[:16], domain=1)
    assert leaf_only != dh.tree_digest_host(
        [int(x) for x in np.asarray(dh.tree_digest_host(vals[:16], domain=1))],
        domain=1,
    )


@pytest.mark.slow
def test_ceremony_device_digest_binds_every_tensor():
    import jax.numpy as jnp
    import random as _random

    from dkg_tpu.dkg import ceremony as ce

    c = ce.BatchedCeremony("ristretto255", 4, 1, b"dh", _random.Random(3))
    a, e, s, r = ce.deal(c.cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    base = ce.transcript_digest_device(c.cfg, a, e, s, r)
    for k, t in enumerate((a, e, s, r)):
        flipped = np.asarray(t).copy()
        flipped.flat[k * 3 + 1] ^= 1
        args = [a, e, s, r]
        args[k] = jnp.asarray(flipped)
        assert ce.transcript_digest_device(c.cfg, *args) != base, k


# --- the word-major device leg (PR 46) ---------------------------------
#
# The device leg is what a TPU runs; no chip is here, so it is forced on
# the CPU and held bit for bit to the numpy leg and the Python twin, over
# the shapes the benchmark's cells send (rows = width x n of a convoy).


def _parts(seed, *shapes):
    rng = np.random.default_rng(seed)
    return tuple(rng.integers(0, 1 << 32, size=s, dtype=np.uint32) for s in shapes)


def _host_rows(parts, lead, domain):
    from dkg_tpu.crypto import blake2s

    rows = int(np.prod(parts[0].shape[:lead]))
    return blake2s.row_digests_np(np.concatenate([p.reshape(rows, -1) for p in parts], axis=-1), domain)


DEVICE_LEG_CASES = {
    # rows 16: a width-1 (16,5) convoy's commitments, 192 words (12 leaves of 16)
    "rows16_lead2_16limb": (((1, 16, 6, 2, 16),), 2),
    # rows 64: the heavy bucket's share and hiding rows, a tuple of two parts
    "rows64_tuple": (((64, 64, 16), (64, 64, 16)), 1),
    # rows 128: a width-8 stack of (16,5), lead=2, a tuple
    "rows128_lead2_tuple": (((8, 16, 16, 16), (8, 16, 16, 16)), 2),
    # rows 256 (ristretto255's n; the mesh's digest chunk): 24-limb rows, 7 x 2 x 24 = 336 words = 21 leaves
    "rows256_24limb_leaves_no_power_of_two": (((256, 7, 2, 24),), 1),
    # a word count that is no multiple of 16
    "rows5_words_no_multiple_of_16": (((5, 41),), 1),
    # one row, one leaf: the root compression alone over a padded block
    "rows1_one_leaf": (((1, 3),), 1),
    # rows no multiple of the lanes, parts of unequal width
    "rows130_unequal_parts": (((130, 9), (130, 40)), 1),
    # rows past one block of the kernel and no multiple of it: a width-5 stack of (64,16), the last block cut
    "rows320_last_block_cut": (((5, 64, 40),), 2),
}


@pytest.mark.parametrize("case", sorted(DEVICE_LEG_CASES))
def test_device_leg_word_major_equals_the_host_leg_bit_for_bit(case):
    shapes, lead = DEVICE_LEG_CASES[case]
    parts = _parts(len(case), *shapes)
    dev = np.asarray(dh.row_digests(parts, domain=0xD0, dispatch="device", lead=lead))
    assert dev.shape == (int(np.prod(shapes[0][:lead])), 8) and dev.dtype == np.uint32
    np.testing.assert_array_equal(dev, _host_rows(parts, lead, 0xD0))
    # and a row of it is the Python twin's (the spec), by the tree's definition
    row = np.concatenate([p.reshape(dev.shape[0], -1)[-1] for p in parts])
    assert [int(x) for x in dev[-1]] == dh.tree_digest_host(row.tolist(), domain=0xD0)


def test_device_leg_takes_device_arrays_and_numpy_alike():
    parts = _parts(7, (4, 3, 16), (4, 5))
    want = _host_rows(parts, 1, 9)
    for handed in (parts, tuple(jnp.asarray(p) for p in parts), (jnp.asarray(parts[0]), parts[1])):
        np.testing.assert_array_equal(np.asarray(dh.row_digests(handed, domain=9, dispatch="device")), want)


def _tree_jaxpr(shapes, lead):
    specs = tuple(jax.ShapeDtypeStruct(s, jnp.uint32) for s in shapes)
    return jax.make_jaxpr(lambda parts, dom: dh._tree_from_words_jit(parts, dom, lead, True))(
        specs, jax.ShapeDtypeStruct((), jnp.uint32)
    )


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations carry."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _equations(inner)


@pytest.mark.parametrize(
    "shapes, lead, rows, leaves",
    [
        (((8, 16, 6, 2, 16),), 2, 128, 16),  # a width-8 stack's commitments: 192 words, 12 -> 16 leaves
        (((1, 16, 16, 16), (1, 16, 16, 16)), 2, 16, 32),  # a width-1 convoy's share and hiding rows
        (((1, 64, 64, 16), (1, 64, 64, 16)), 2, 64, 128),  # the heavy bucket, width 1
        (((256, 86, 2, 16),), 1, 256, 256),  # ristretto255's commitments: 2,752 words, 172 -> 256 leaves
        (((1024, 342, 2, 24),), 1, 1024, 2048),  # BLS12-381: 16,416 words, 1026 -> 2048 leaves
    ],
    ids=["rows128", "rows16", "rows64", "rows256", "rows1024_24limb"],
)
def test_the_traced_tree_is_a_kernel_a_level_with_the_rows_on_the_lanes(shapes, lead, rows, leaves):
    """Nothing is compiled.  The traced program has no gather and no roll
    (the parent's message schedule was a ``take`` along the minor axis
    under a traced index, its diagonal step a ``roll`` of a 4-wide one);
    a level is ONE kernel whose operand is the level's sixteen message
    slabs, nodes major of rows, cut into blocks with the rows on the
    minor axis: a whole vector's lanes from 128 rows on, and every row
    there is below that; inside a kernel a round's message words are
    picked on the block's LEADING axis and nothing shuffles lanes."""
    eqns = list(_equations(_tree_jaxpr(shapes, lead).jaxpr))
    assert not {e.primitive.name for e in eqns} & {"gather", "scatter", "roll", "dynamic_slice", "while"}
    kernels = [e for e in eqns if e.primitive.name == "pallas_call"]
    nodes = [leaves >> k for k in range(leaves.bit_length())] + [1]  # the leaves, the levels above, the root
    assert [k.invars[1].aval.shape for k in kernels] == [(16, n, rows) for n in nodes]
    for k, n in zip(kernels, nodes):
        assert k.params["name"] == "blake2s_level"
        block_in, block_out = (
            tuple(getattr(b, "block_size", b) for b in bm.block_shape) for bm in k.params["grid_mapping"].block_mappings
        )
        assert block_in[0] == 16 and block_out[0] == 8 and block_in[1:] == block_out[1:]
        assert block_in[1] == min(n, pb.BLOCK_NODES) and block_in[2] == min(rows, pb.BLOCK_ROWS)
        assert block_in[2] % 128 == 0 or block_in[2] == rows  # whole vectors, or every row there is
        body = list(_equations(k.params["jaxpr"]))
        assert {e.primitive.name for e in body} <= {
            "add", "xor", "or", "shift_left", "shift_right_logical", "mul", "iota", "program_id",
            "broadcast_in_dim", "convert_element_type", "get", "swap", "scan",
        }
        (rounds,) = [e for e in body if e.primitive.name == "scan"]
        assert rounds.params["length"] == 10
        picks = [e for e in _equations(rounds.params["jaxpr"].jaxpr) if e.primitive.name == "get" and e.outvars[0].aval.ndim == 2]
        assert len(picks) == 16 and all(e.outvars[0].aval.shape == block_in[1:] for e in picks)
