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
