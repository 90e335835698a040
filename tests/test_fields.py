"""Field-layer tests: device limb arithmetic vs the Python-int host oracle.

Mirrors the reference's oracle style (internal-consistency asserts,
reference: src/polynomial.rs:186-280) but adds what it lacks per SURVEY §4:
randomized cross-checks against an independent implementation and edge-case
known-answer values per field.
"""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from dkg_tpu.fields import (
    ALL_FIELDS,
    L25519,
    P25519,
    device as fd,
    host as fh,
    limbs_to_int,
)
from dkg_tpu.fields.spec import FieldSpec
from dkg_tpu.groups import device as gd
from dkg_tpu.utils import metrics

RNG = random.Random(0xD1C6)

# every case compiles its own programs: tests/conftest.py says why they go
pytestmark = pytest.mark.usefixtures("free_compiled_programs")

FIELDS = list(ALL_FIELDS.values())
FIELD_IDS = [fs.name for fs in FIELDS]


def sample(fs, k):
    """k random field elements incl. adversarial edge values."""
    edge = [0, 1, 2, fs.modulus - 1, fs.modulus - 2, (1 << (fs.bits - 1)) % fs.modulus]
    vals = edge + [RNG.randrange(fs.modulus) for _ in range(k - len(edge))]
    return vals[:k]


@pytest.mark.parametrize("fs", FIELDS, ids=FIELD_IDS)
def test_limb_roundtrip(fs):
    vals = sample(fs, 16)
    limbs = fh.encode(fs, vals)
    back = fh.decode(fs, limbs)
    assert [int(v) for v in back] == vals


@pytest.mark.parametrize("fs", FIELDS, ids=FIELD_IDS)
def test_add_sub_neg(fs):
    a = sample(fs, 24)
    b = list(reversed(sample(fs, 24)))
    da, db = jnp.asarray(fh.encode(fs, a)), jnp.asarray(fh.encode(fs, b))
    got_add = fh.decode(fs, np.asarray(fd.add(fs, da, db)))
    got_sub = fh.decode(fs, np.asarray(fd.sub(fs, da, db)))
    got_neg = fh.decode(fs, np.asarray(fd.neg(fs, da)))
    for i in range(24):
        assert int(got_add[i]) == fh.add(fs, a[i], b[i])
        assert int(got_sub[i]) == fh.sub(fs, a[i], b[i])
        assert int(got_neg[i]) == fh.neg(fs, a[i])


@pytest.mark.parametrize("fs", FIELDS, ids=FIELD_IDS)
def test_mul_wide_and_reduce(fs):
    a = sample(fs, 24)
    b = list(reversed(sample(fs, 24)))
    da, db = jnp.asarray(fh.encode(fs, a)), jnp.asarray(fh.encode(fs, b))
    wide = np.asarray(fd.mul_wide(da, db))
    red = np.asarray(fd.mul(fs, da, db))
    for i in range(24):
        assert limbs_to_int(wide[i]) == a[i] * b[i]
        assert limbs_to_int(red[i]) == fh.mul(fs, a[i], b[i])


@pytest.mark.parametrize("fs", FIELDS, ids=FIELD_IDS)
def test_pow_inv(fs):
    a = [v for v in sample(fs, 8) if v != 0]
    da = jnp.asarray(fh.encode(fs, a))
    e = RNG.randrange(1 << 64)
    got_pow = fh.decode(fs, np.asarray(fd.pow_const(fs, da, e)))
    got_inv = fh.decode(fs, np.asarray(fd.inv(fs, da)))
    for i, v in enumerate(a):
        assert int(got_pow[i]) == pow(v, e, fs.modulus)
        assert int(got_inv[i]) == fh.inv(fs, v)


_EXPONENTS = {
    "0": lambda p: 0,
    "1": lambda p: 1,
    "2": lambda p: 2,
    "15": lambda p: 15,
    "16": lambda p: 16,
    "17": lambda p: 17,
    "p-2": lambda p: p - 2,
    "(p-5)//8": lambda p: (p - 5) // 8,
}


@pytest.mark.parametrize("exp", list(_EXPONENTS))
@pytest.mark.parametrize("fs", FIELDS, ids=FIELD_IDS)
def test_pow_const_window_chain_matches_python_pow(fs, exp):
    """The fixed-window chain at the edges of its schedule: no digit, one
    digit below / at / above the table's end, a digit boundary (16, 17),
    and the two exponents the program uses (the Fermat inverse of every
    field, ristretto's square-root exponent)."""
    e = _EXPONENTS[exp](fs.modulus)
    a = sample(fs, 8)
    got = fh.decode(fs, np.asarray(fd.pow_const(fs, jnp.asarray(fh.encode(fs, a)), e)))
    assert [int(v) for v in got] == [pow(v, e, fs.modulus) for v in a]


@pytest.mark.parametrize(
    "name",
    [
        "secp256k1_base",  # ~17 s of interpret-mode compile: the cells' field stays in tier 1
        pytest.param("ed25519_base", marks=pytest.mark.slow),  # ~17 s
        pytest.param("bls12_381_base", marks=pytest.mark.slow),  # ~5 min at 24 limbs
    ],
)
def test_fused_pow_kernel_matches_inv(name):
    """ops.pallas_field.mod_pow_const (the kernel fd.pow_const dispatches
    to where the fused kernels are active), in interpret mode at one
    128-lane block, against the XLA chain and the host oracle."""
    from dkg_tpu.ops import pallas_field as pf

    fs = ALL_FIELDS[name]
    a = sample(fs, pf.BLOCK)
    da = jnp.asarray(fh.encode(fs, a))
    got = np.asarray(pf.mod_pow_const(fs, da, fs.modulus - 2, interpret=True))
    np.testing.assert_array_equal(got, np.asarray(fd.inv(fs, da)))
    assert [int(v) for v in fh.decode(fs, got)] == [fh.inv(fs, v) if v else 0 for v in a]


def test_batch_inv_one_row_is_the_plain_inversion():
    """k == 1 along the scan axis takes no prefix or suffix scan and
    gives the same values as the Montgomery trick over k == 15."""
    fs = P25519
    a = [v for v in sample(fs, 16) if v != 0]
    da = jnp.asarray(fh.encode(fs, a))
    got = np.asarray(fd.batch_inv(fs, da[None], axis=0))
    np.testing.assert_array_equal(got[0], np.asarray(fd.batch_inv(fs, da, axis=0)))
    np.testing.assert_array_equal(got[0], np.asarray(fd.inv(fs, da)))


def test_batch_inv_matches_scalar_inv():
    fs = P25519
    a = [v for v in sample(fs, 16) if v != 0]
    da = jnp.asarray(fh.encode(fs, a))
    got = fh.decode(fs, np.asarray(fd.batch_inv(fs, da, axis=0)))
    for i, v in enumerate(a):
        assert int(got[i]) == fh.inv(fs, v)


def test_scalar_field_matches_reference_order():
    # ed25519 group order l = 2^252 + 27742...493 (reference uses dalek's
    # Scalar which reduces mod this l; src/groups.rs:11-53).
    assert L25519.modulus == (1 << 252) + 27742317777372353535851937790883648493
    assert P25519.modulus == (1 << 255) - 19


def test_broadcasting_constant_operand():
    fs = P25519
    a = sample(fs, 10)
    c = 123456789
    da = jnp.asarray(fh.encode(fs, a))
    dc = fd.constant(fs, c)
    got = fh.decode(fs, np.asarray(fd.mul(fs, da, dc)))
    for i, v in enumerate(a):
        assert int(got[i]) == fh.mul(fs, v, c)


def test_sub_broadcasts_scalar_minuend():
    # regression: a smaller-rank than b must broadcast, not crash
    fs = P25519
    b = sample(fs, 3)
    db = jnp.asarray(fh.encode(fs, b))
    got = fh.decode(fs, np.asarray(fd.sub(fs, fd.ones(fs), db)))
    for i, v in enumerate(b):
        assert int(got[i]) == fh.sub(fs, 1, v)


def test_from_bytes_strict_length():
    fs = P25519
    assert fh.from_bytes(fs, b"\x01") is None  # short encodings rejected
    assert fh.from_bytes(fs, fh.to_bytes(fs, 1)) == 1
    assert fh.from_bytes(fs, fh.to_bytes(fs, 0) + b"\x00") is None
    assert fh.from_bytes(fs, (fs.modulus).to_bytes(fs.nbytes, "little")) is None


def test_2d_batch_shapes():
    fs = L25519
    vals = [[RNG.randrange(fs.modulus) for _ in range(3)] for _ in range(4)]
    d = jnp.asarray(fh.encode(fs, vals))
    got = fh.decode(fs, np.asarray(fd.mul(fs, d, d)))
    for i in range(4):
        for j in range(3):
            assert int(got[i][j]) == fh.mul(fs, vals[i][j], vals[i][j])


# ---------------------------------------------------------------------------
# fh.draw_limbs: the bulk read of a generator's stream against the
# scalar-at-a-time loop it replaces (fs.rand_int + fh.encode)
# ---------------------------------------------------------------------------

#: just over a power of two: half of all attempts are thrown away, and
#: every attempt that is kept ties with the modulus in its top word
JUST_OVER = FieldSpec("just_over_2_64", (1 << 64) + 13, 5)


def _draw_counts():
    c = metrics.REGISTRY.snapshot()["counters"]
    return {
        "bulk": c.get('coeff_draw_scalars_total{path="bulk"}', 0),
        "block": c.get('coeff_draw_scalars_total{path="block"}', 0),
        "sequential": c.get('coeff_draw_scalars_total{path="sequential"}', 0),
        "rejected": c.get("coeff_draw_rejected_total", 0),
    }


def _delta(before):
    after = _draw_counts()
    return {k: after[k] - before[k] for k in after if after[k] != before[k]}


def _sequential(fs, rng, count):
    """The loop ``draw_limbs`` stands for, and how many attempts it threw
    away."""
    vals, rejected = [], 0
    while len(vals) < count:
        x = rng.getrandbits(fs.bits)
        if x < fs.modulus:
            vals.append(x)
        else:
            rejected += 1
    return fh.encode(fs, vals).reshape(count, fs.limbs), rejected


def _booked(path, count, rejected=0):
    want = {path: count, "rejected": rejected}
    return {k: v for k, v in want.items() if v}


@pytest.mark.parametrize("seed", [0, 0xD1C6, 2**31 + 5])
@pytest.mark.parametrize("curve", sorted(gd.ALL_CURVES))
def test_draw_limbs_is_the_rand_int_loop(curve, seed):
    fs = gd.ALL_CURVES[curve].scalar
    bulk, loop = random.Random(seed), random.Random(seed)
    got = fh.draw_limbs(fs, bulk, (9, 11))
    want = fh.encode(fs, [[fs.rand_int(loop) for _ in range(11)] for _ in range(9)])
    assert got.dtype == np.uint32 and got.shape == (9, 11, fs.limbs)
    np.testing.assert_array_equal(got, want)
    assert bulk.getrandbits(64) == loop.getrandbits(64)


@pytest.mark.parametrize("count", [0, 1, 7, 5000])
@pytest.mark.parametrize("fs", [L25519, JUST_OVER], ids=lambda fs: fs.name)
def test_draw_limbs_under_rejection(fs, count):
    bulk, loop = random.Random(count + 3), random.Random(count + 3)
    before = _draw_counts()
    got = fh.draw_limbs(fs, bulk, (count,))
    want, rejected = _sequential(fs, loop, count)
    np.testing.assert_array_equal(got, want)
    assert bulk.getrandbits(64) == loop.getrandbits(64)
    path = "block" if count >= fh.BLOCK_MIN_SCALARS else "bulk"
    assert _delta(before) == _booked(path, count, rejected)
    assert count < 7 or rejected > 0


def test_draw_limbs_reads_in_rounds(monkeypatch):
    """More scalars than one read asks for: the rounds' attempts are
    still the loop's attempts in order."""
    monkeypatch.setattr(fh, "BLOCK_MIN_SCALARS", 0)
    monkeypatch.setattr(fh, "_BLOCK_ROWS", 64)
    bulk, loop = random.Random(8), random.Random(8)
    got = fh.draw_limbs(JUST_OVER, bulk, (3, 100))
    want, _ = _sequential(JUST_OVER, loop, 300)
    np.testing.assert_array_equal(got.reshape(300, -1), want)
    assert bulk.getrandbits(64) == loop.getrandbits(64)


#: the block path's threshold and chunk, set down for the cases below
_T, _CHUNK = 12, 30


@pytest.mark.parametrize(
    "shape",
    [(11, 1), (3, 4), (1, 13), (29, 1), (8, 12)],
    ids=["T-1", "T", "T+1", "chunk-1", "chunks_and_rest"],
)
@pytest.mark.parametrize(
    "fs",
    [gd.ALL_CURVES[c].scalar for c in sorted(gd.ALL_CURVES)] + [JUST_OVER],
    ids=lambda fs: fs.name,
)
def test_block_path_is_the_loop_and_the_bulk_path(monkeypatch, fs, shape):
    """From ``BLOCK_MIN_SCALARS`` on the words come from numpy's Mersenne
    generator in chunks, into a caller's array: the same scalars as the
    ``rand_int`` loop and as the bulk read, the same generator state
    after (from a position inside the 624-word block), the same refusals
    booked; the array's other lanes untouched."""
    monkeypatch.setattr(fh, "_BLOCK_ROWS", _CHUNK)
    need = shape[0] * shape[1]
    block, bulk, loop = (random.Random(need) for _ in range(3))
    for rng in (block, bulk, loop):
        for _ in range(7):
            rng.getrandbits(32)
    want, rejected = _sequential(fs, loop, need)

    monkeypatch.setattr(fh, "BLOCK_MIN_SCALARS", 1 << 40)
    before = _draw_counts()
    got_bulk = fh.draw_limbs(fs, bulk, shape)
    assert _delta(before) == _booked("bulk", need, rejected)

    monkeypatch.setattr(fh, "BLOCK_MIN_SCALARS", _T)
    padded = np.zeros((shape[0] + 2, shape[1] + 3, fs.limbs), np.uint32)
    real = padded[: shape[0], : shape[1]]
    before = _draw_counts()
    assert fh.draw_limbs(fs, block, shape, out=real) is real
    assert _delta(before) == _booked("block" if need >= _T else "bulk", need, rejected)

    np.testing.assert_array_equal(real.reshape(need, -1), want)
    np.testing.assert_array_equal(got_bulk.reshape(need, -1), want)
    assert not padded[shape[0] :].any() and not padded[:, shape[1] :].any()
    assert block.getrandbits(64) == bulk.getrandbits(64) == loop.getrandbits(64)


def test_numpy_continues_the_mersenne_stream():
    """The contract the block path holds numpy to: ``MT19937`` with
    ``random.Random``'s state carried in yields its ``getrandbits(32)``
    words through ``integers(0, 2**32, dtype=uint32)``, and its state
    carried back continues the stream — from a fresh block, from inside
    one, and across a block's end."""
    for advance, count in ((0, 5), (7, 700), (623, 3), (624, 1300)):
        ours, theirs = random.Random(0xD1C6), random.Random(0xD1C6)
        for rng in (ours, theirs):
            for _ in range(advance):
                rng.getrandbits(32)
        version, internal, gauss_next = ours.getstate()
        assert version == 3 and len(internal) == 625
        bit_gen = np.random.MT19937()
        bit_gen.state = {
            "bit_generator": "MT19937",
            "state": {"key": np.array(internal[:-1], np.uint32), "pos": internal[-1]},
        }
        words = np.random.Generator(bit_gen).integers(0, 1 << 32, size=count, dtype=np.uint32)
        assert words.tolist() == [theirs.getrandbits(32) for _ in range(count)]
        state = bit_gen.state["state"]
        ours.setstate((version, (*state["key"].tolist(), int(state["pos"])), gauss_next))
        assert ours.getstate() == theirs.getstate()
        assert ours.getrandbits(256) == theirs.getrandbits(256)


def test_draw_limbs_refuses_an_out_of_another_shape():
    fs = L25519
    with pytest.raises(ValueError, match="out is"):
        fh.draw_limbs(fs, random.Random(1), (4, 5), out=np.zeros((4, 6, fs.limbs), np.uint32))
    with pytest.raises(ValueError, match="out is"):
        fh.draw_limbs(fs, random.Random(1), (4, 5), out=np.zeros((4, 5, fs.limbs), np.int64))
    with pytest.raises(ValueError, match="two-axis"):
        fh.draw_limbs(fs, random.Random(1), (20,), out=np.zeros((40, fs.limbs), np.uint32)[::2])


class _SubclassedRandom(random.Random):
    """Inherits the Mersenne stream, but a subclass may have changed it."""


class _StubGenerator:
    """A test's own generator: only ``getrandbits``."""

    def __init__(self, seed):
        self._inner = random.Random(seed)

    def getrandbits(self, k):
        return self._inner.getrandbits(k) ^ 1


@pytest.mark.parametrize("make", [_SubclassedRandom, _StubGenerator])
def test_draw_limbs_other_generators_take_the_loop(make):
    fs = L25519
    before = _draw_counts()
    got = fh.draw_limbs(fs, make(21), (4, 5))
    loop = make(21)
    want = fh.encode(fs, [[fs.rand_int(loop) for _ in range(5)] for _ in range(4)])
    np.testing.assert_array_equal(got, want)
    assert _delta(before) == {"sequential": 20}


@pytest.mark.parametrize("path", ["bulk", "block"])
@pytest.mark.parametrize("fs", [L25519, JUST_OVER], ids=lambda fs: fs.name)
def test_draw_limbs_system_random(monkeypatch, fs, path):
    """``os.urandom``'s bits on both sides of the threshold (the block
    path reads them without a Python int): range, distinctness, counters."""
    monkeypatch.setattr(fh, "BLOCK_MIN_SCALARS", 0 if path == "block" else 1 << 40)
    monkeypatch.setattr(fh, "_BLOCK_ROWS", 128)
    before = _draw_counts()
    got = fh.draw_limbs(fs, random.SystemRandom(), (6, 50))
    assert got.dtype == np.uint32 and got.shape == (6, 50, fs.limbs)
    assert int(got.max()) < 1 << 16
    vals = [int(v) for v in fh.decode(fs, got).ravel()]
    assert all(0 <= v < fs.modulus for v in vals)
    assert len(set(vals)) == 300
    d = _delta(before)
    assert d[path] == 300 and d["rejected"] > 0 and set(d) == {path, "rejected"}
