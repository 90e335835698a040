"""Vectorized DEM pipeline bit-exactness: RFC 8439 / RFC 7693 vectors,
batch-vs-scalar equivalence, and scalar-vs-batch wire-byte identity.

The batched dealing path (hybrid_batch.seal_shares_batch and friends)
re-implements the byte-level DEM tail — point compression, Blake2b KDF,
ChaCha20 — as numpy array kernels.  Every test here pins those kernels
to an external oracle (RFC vectors, hashlib) or to the scalar reference
leg, because a silent mismatch would produce ciphertexts honest
recipients cannot open (a liveness break, not just a perf bug).
"""

import hashlib
import random

import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.crypto import Keypair
from dkg_tpu.crypto.blake2 import blake2b_batch, kdf_batch
from dkg_tpu.crypto.chacha import (
    chacha20_block_batch,
    chacha20_xor,
    chacha20_xor_batch,
)
from dkg_tpu.crypto.elgamal import keystream_from_kem_bytes
from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.dkg import hybrid_batch as hb
from dkg_tpu.fields import host as fh
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh

RNG = random.Random(0xDE77)

CURVES = [
    "ristretto255",
    pytest.param("secp256k1", marks=pytest.mark.slow),
    pytest.param("bls12_381_g1", marks=pytest.mark.slow),
]


# ---------------------------------------------------------------------------
# ChaCha20 (RFC 8439)
# ---------------------------------------------------------------------------

_RFC_KEY = bytes(range(32))


def test_chacha20_block_batch_rfc8439_vector():
    # RFC 8439 §2.3.2: block function, counter = 1
    nonce = bytes.fromhex("000000090000004a00000000")
    expect = bytes.fromhex(
        "10f1e7e4d13b5915500fdd1fa32071c4"
        "c7d1f4c733c068030422aa9ac3d46c4e"
        "d2826446079faa0914c2d705d98b02a2"
        "b5129cd1de164eb9cbd083e8a2503c4e"
    )
    keys = np.frombuffer(_RFC_KEY, dtype="<u4").reshape(1, 8)
    nonces = np.frombuffer(nonce, dtype="<u4").reshape(1, 3)
    ks = chacha20_block_batch(keys, np.array([1], dtype=np.uint32), nonces)
    assert ks.shape == (1, 64)
    assert ks[0].tobytes() == expect


def test_chacha20_xor_rfc8439_encryption_vector():
    # RFC 8439 §2.4.2: sunscreen plaintext, counter = 1
    nonce = bytes.fromhex("000000000000004a00000000")
    plaintext = (
        b"Ladies and Gentlemen of the class of '99: If I could offer you "
        b"only one tip for the future, sunscreen would be it."
    )
    expect = bytes.fromhex(
        "6e2e359a2568f98041ba0728dd0d6981"
        "e97e7aec1d4360c20a27afccfd9fae0b"
        "f91b65c5524733ab8f593dabcd62b357"
        "1639d624e65152ab8f530c359f0861d8"
        "07ca0dbf500d6a6156a38e088a22b65e"
        "52bc514d16ccf806818ce91ab7793736"
        "5af90bbf74a35be6b40b8eedf2785e42"
        "874d"
    )
    assert chacha20_xor(_RFC_KEY, nonce, plaintext, counter=1) == expect
    data = np.frombuffer(plaintext, dtype=np.uint8).reshape(1, -1)
    got = chacha20_xor_batch(
        np.frombuffer(_RFC_KEY, dtype=np.uint8).reshape(1, 32),
        np.frombuffer(nonce, dtype=np.uint8).reshape(1, 12),
        data,
        counter=1,
    )
    assert got[0].tobytes() == expect


def test_chacha20_batch_matches_scalar_random_lengths():
    # multi-row batches at lengths spanning 0 / sub-block / block
    # boundaries / multi-block must equal the scalar implementation
    for mlen in (0, 1, 31, 32, 63, 64, 65, 128, 130):
        rows = 5
        keys = np.frombuffer(RNG.randbytes(32 * rows), np.uint8).reshape(rows, 32)
        nonces = np.frombuffer(RNG.randbytes(12 * rows), np.uint8).reshape(rows, 12)
        data = np.frombuffer(RNG.randbytes(mlen * rows), np.uint8).reshape(rows, mlen)
        got = chacha20_xor_batch(keys, nonces, data)
        for r in range(rows):
            want = chacha20_xor(
                keys[r].tobytes(), nonces[r].tobytes(), data[r].tobytes()
            )
            assert got[r].tobytes() == want


# ---------------------------------------------------------------------------
# Blake2b (RFC 7693, hashlib as oracle)
# ---------------------------------------------------------------------------

def test_blake2b_batch_matches_hashlib():
    persons = (b"", b"dkgtpu-kdf", b"dkgtpu-kd2", b"p" * 16)
    for mlen in (0, 1, 63, 64, 127, 128, 129, 255, 256, 300):
        for person in persons:
            for digest_size in (1, 32, 64):
                rows = 4
                msgs = np.frombuffer(
                    RNG.randbytes(mlen * rows), np.uint8
                ).reshape(rows, mlen)
                got = blake2b_batch(msgs, digest_size=digest_size, person=person)
                assert got.shape == (rows, digest_size)
                for r in range(rows):
                    want = hashlib.blake2b(
                        msgs[r].tobytes(), digest_size=digest_size, person=person
                    ).digest()
                    assert got[r].tobytes() == want


def test_kdf_batch_matches_elgamal_keystream():
    # kdf_batch must agree with THE one KDF definition (elgamal.py)
    for enc_len in (32, 33, 49):
        rows = 6
        kem_enc = np.frombuffer(
            RNG.randbytes(enc_len * rows), np.uint8
        ).reshape(rows, enc_len)
        for person in (b"dkgtpu-kdf", b"dkgtpu-kd2"):
            keys, nonces = kdf_batch(kem_enc, person)
            for r in range(rows):
                k, n = keystream_from_kem_bytes(kem_enc[r].tobytes(), person)
                assert keys[r].tobytes() == k
                assert nonces[r].tobytes() == n


# ---------------------------------------------------------------------------
# batched point compression (groups.device.encode_batch)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("curve", CURVES)
def test_encode_batch_matches_host_encode_both_dispatches(curve, monkeypatch):
    """encode_batch must be bit-identical to per-point HostGroup.encode
    on BOTH dispatch legs — the host big-int Montgomery path (CPU) and
    the device affine_canon path (TPU) — including identity (zero-Z)
    lanes, since the encoding keys the DEM's KDF."""
    from dkg_tpu.fields import device as fd

    g = gh.ALL_GROUPS[curve]
    cs = gd.ALL_CURVES[curve]
    fs = cs.scalar
    scalars = [fs.rand_int(RNG) for _ in range(6)] + [0]  # 0 -> identity lane
    base = gd.from_host(cs, [g.generator()] * len(scalars))
    dev = np.asarray(gd.scalar_mul(cs, jnp.asarray(fh.encode(fs, scalars)), base))
    want = [g.encode(g.scalar_mul(s, g.generator())) for s in scalars]

    monkeypatch.setattr(fd, "_on_tpu", lambda: False)
    host_leg = gd.encode_batch(cs, dev)
    # the device leg, on this CPU: steer the leg, not the kernels (a
    # pretended TPU would send affine_canon's inversion to Mosaic)
    monkeypatch.setenv("DKG_TPU_PALLAS", "0")
    monkeypatch.setattr(fd, "_on_tpu", lambda: True)
    device_leg = gd.encode_batch(cs, dev)
    for i, w in enumerate(want):
        assert host_leg[i].tobytes() == w
        assert device_leg[i].tobytes() == w
    # batch shape is preserved: (2, k, C, L) -> (2, k, enc_len)
    monkeypatch.setattr(fd, "_on_tpu", lambda: False)
    stacked = gd.encode_batch(cs, np.stack([dev, dev]))
    assert stacked.shape[:2] == (2, len(scalars))
    assert stacked[1, 0].tobytes() == want[0]


# ---------------------------------------------------------------------------
# seal/open batch legs vs scalar legs
# ---------------------------------------------------------------------------

def _sealed_bytes(group, sealed):
    """Flatten a sealed matrix to comparable wire bytes (canonical e1
    encoding + raw ciphertexts) — what serde puts on the wire, so equal
    projective representations compare equal."""
    out = []
    for row in sealed:
        for share_ct, hiding_ct in row:
            out.append(
                (
                    group.encode(share_ct.e1),
                    share_ct.ciphertext,
                    group.encode(hiding_ct.e1),
                    hiding_ct.ciphertext,
                )
            )
    return out


@pytest.mark.parametrize("curve", CURVES)
def test_seal_shares_batch_bytes_match_scalar(curve):
    n_d, n_r, t = 3, 4, 1
    g = gh.ALL_GROUPS[curve]
    cfg = ce.CeremonyConfig(curve, n_r, t)
    cs = cfg.cs
    fs = cs.scalar

    keys = [Keypair.generate(g, RNG) for _ in range(n_r)]
    pks_dev = gd.from_host(cs, [k.pk for k in keys])
    shares = np.asarray(
        fh.encode(fs, [[fs.rand_int(RNG) for _ in range(n_r)] for _ in range(n_d)])
    )
    hidings = np.asarray(
        fh.encode(fs, [[fs.rand_int(RNG) for _ in range(n_r)] for _ in range(n_d)])
    )
    r = jnp.asarray(
        fh.encode(fs, [[fs.rand_int(RNG) for _ in range(n_r)] for _ in range(n_d)])
    )
    c = ce.BatchedCeremony(curve, n_r, t, b"dem-eq", RNG)
    c1, kem = hb.kem_batch(cfg, pks_dev, r, c.g_table)
    c1, kem = np.asarray(c1), np.asarray(kem)

    scalar_leg = _sealed_bytes(g, hb.seal_shares(g, cfg, shares, hidings, c1, kem))
    batch_sealed = hb.seal_shares_batch(g, cfg, shares, hidings, c1, kem)
    assert _sealed_bytes(g, batch_sealed) == scalar_leg

    # chunked pipeline == unchunked == direct kem+seal, byte for byte
    piped = _sealed_bytes(
        g,
        hb.seal_shares_pipeline(
            g, cfg, shares, hidings, pks_dev, r, c.g_table, chunk=2
        ),
    )
    assert piped == scalar_leg

    # and every recipient opens its column back to the dealt scalars
    for i in range(n_r):
        pairs = [batch_sealed[d][i] for d in range(n_d)]
        got = hb.open_shares_batch(g, cfg, keys[i].sk, pairs)
        for d in range(n_d):
            assert got[d] == (
                fh.decode_int(fs, shares[d, i]),
                fh.decode_int(fs, hidings[d, i]),
            )


def test_open_shares_batch_matches_open_share_on_garbage():
    # wrong-length and random ciphertexts must degrade exactly like the
    # scalar open_share: None, never an exception
    curve = "ristretto255"
    g = gh.ALL_GROUPS[curve]
    cfg = ce.CeremonyConfig(curve, 4, 1)
    fs = cfg.cs.scalar
    kp = Keypair.generate(g, RNG)
    e1 = g.scalar_mul(fs.rand_int(RNG), g.generator())
    from dkg_tpu.crypto.elgamal import HybridCiphertext

    pairs = [
        (HybridCiphertext(e1, b"short"), HybridCiphertext(e1, b"x" * fs.nbytes)),
        (
            HybridCiphertext(e1, RNG.randbytes(fs.nbytes)),
            HybridCiphertext(e1, RNG.randbytes(fs.nbytes + 1)),
        ),
    ]
    got = hb.open_shares_batch(g, cfg, kp.sk, pairs)
    want = [hb.open_share(g, kp.sk, p) for p in pairs]
    assert got == want
    assert got[0][0] is None  # wrong length
    assert hb.open_shares_batch(g, cfg, kp.sk, []) == []


@pytest.mark.slow
def test_open_shares_batch_roundtrips_full_ceremony_n16():
    from dkg_tpu.dkg.committee import Environment
    from dkg_tpu.dkg.committee_batch import batched_dealing
    from dkg_tpu.dkg.procedure_keys import MemberCommunicationKey, sort_committee

    n, t = 16, 5
    g = gh.RISTRETTO255
    cfg = ce.CeremonyConfig(g.name, n, t)
    fs = cfg.cs.scalar
    env = Environment.init(g, t, n, b"dem-n16")
    keys = [MemberCommunicationKey.generate(g, RNG) for _ in range(n)]
    dealt = batched_dealing(env, RNG, keys)
    broadcasts = [b for _, b in dealt]
    pks = sort_committee(g, [k.public() for k in keys])
    key_by_enc = {k.public().sort_key(g): k for k in keys}
    sorted_keys = [key_by_enc[p.sort_key(g)] for p in pks]

    for i in (1, 7, 16):  # spot-check recipients across the range
        es = [b.shares_for(i) for b in broadcasts]
        pairs = [(e.share_ct, e.randomness_ct) for e in es]
        got = hb.open_shares_batch(g, cfg, sorted_keys[i - 1].sk, pairs)
        want = [hb.open_share(g, sorted_keys[i - 1].sk, p) for p in pairs]
        assert got == want
        for s, h in got:
            assert s is not None and 0 <= s < fs.modulus
            assert h is not None and 0 <= h < fs.modulus
    # dealer d's own recorded share agrees with the opened wire share
    phase1 = dealt[0][0]
    assert got[0] != (None, None)
    own = phase1._state.received_shares[1]
    opened = hb.open_shares_batch(
        g,
        cfg,
        sorted_keys[0].sk,
        [
            (
                broadcasts[0].shares_for(1).share_ct,
                broadcasts[0].shares_for(1).randomness_ct,
            )
        ],
    )[0]
    assert opened == own


# ---------------------------------------------------------------------------
# DKG_TPU_DEM knob + wire-byte identity through batched_dealing
# ---------------------------------------------------------------------------

def test_dem_mode_knob(monkeypatch):
    monkeypatch.delenv("DKG_TPU_DEM", raising=False)
    assert hb.dem_mode() == "batch"
    monkeypatch.setenv("DKG_TPU_DEM", "")
    assert hb.dem_mode() == "batch"  # empty == unset (shell idiom)
    monkeypatch.setenv("DKG_TPU_DEM", "scalar")
    assert hb.dem_mode() == "scalar"
    monkeypatch.setenv("DKG_TPU_DEM", "batch")
    assert hb.dem_mode() == "batch"
    monkeypatch.setenv("DKG_TPU_DEM", "turbo")
    with pytest.raises(ValueError):
        hb.dem_mode()


def test_broadcast_phase1_bytes_identical_scalar_vs_batch(monkeypatch):
    """The acceptance gate: a ceremony dealt with DKG_TPU_DEM=scalar and
    one dealt with =batch (same seeds, same keys) must serialize to
    bit-identical BroadcastPhase1 wire bytes."""
    from dkg_tpu.dkg.committee import Environment
    from dkg_tpu.dkg.committee_batch import batched_dealing
    from dkg_tpu.dkg.procedure_keys import MemberCommunicationKey
    from dkg_tpu.utils import serde

    n, t = 3, 1
    g = gh.RISTRETTO255
    env = Environment.init(g, t, n, b"dem-wire")
    keys = [MemberCommunicationKey.generate(g, random.Random(0x5EED)) for _ in range(n)]

    def deal_with(mode):
        monkeypatch.setenv("DKG_TPU_DEM", mode)
        dealt = batched_dealing(env, random.Random(0xABCD), keys)
        return [serde.encode_phase1(g, b) for _, b in dealt]

    assert deal_with("scalar") == deal_with("batch")
