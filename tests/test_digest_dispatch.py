"""Backend dispatch of the transcript digest (DKG_TPU_DIGEST) and the
vectorized Fiat-Shamir rho derivation.

The dispatch contract: the jitted device Merkle tree and the numpy host
batch are BIT-IDENTICAL — which leg runs is purely a performance
choice, so the knob may never change a ceremony's rho.  Golden
constants below were captured from the repo BEFORE the jit/dispatch/
vectorization rewrite (eager device tree + per-dealer hashlib loop),
pinning cross-version byte-identity, not just internal consistency.
"""

import hashlib
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from chip_smoke import projective_batch  # the on-chip proof's batches, at the repo's root
from dkg_tpu.crypto import device_hash as dh
from dkg_tpu.crypto.blake2 import blake2b_batch
from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.fields import host as fh
from dkg_tpu.utils.metrics import REGISTRY

RNG = random.Random(0xD15B)

# every case compiles its own programs: tests/conftest.py says why they go
pytestmark = pytest.mark.usefixtures("free_compiled_programs")

# --- goldens from the pre-rewrite implementation (BatchedCeremony(
# curve, n=4, t=1, b"golden", random.Random(0xD16)), deal_chunked,
# transcript_digest_device hex / derive_rho(rho_bits=128) limb bytes)
GOLDEN_DIGEST = {
    "secp256k1": "6628ed68f5fef43054eb8cce6ce4cbe7e265c29df9bac397c2888b8041e75ac3",
    "ristretto255": "0fbb51b1207c95865139fc055686f95f4a2f37588aaa8f4d772f678f4b204355",
}
GOLDEN_RHO = {
    "secp256k1": (
        "8f8d000075820000b94a0000bfc2000079ba000070a80000193300002e7d0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "9f870000d0770000bb6f00001fd30000d59a00006829000004aa0000ae230000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "d8ec000023d30000f48b0000255f000026500000c448000054f60000a0090000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "1f9d0000d7520000c448000029ea0000a0d90000ca360000016300004b3d0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
    ),
    "ristretto255": (
        "9f140000af3c0000e81b00002f8c000010be0000a6480000124000000bcd0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "9081000058220000667c000080ae0000622a0000bdc50000e80a000050230000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "6a8d0000c7d700002737000067e50000b69c000009db000039010000104b0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "2c3f0000912400000b3c0000f4530000660c0000a2e00000aa600000c19e0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
    ),
}


# --- knob + dispatch resolution ---------------------------------------


def test_digest_knob_rejects_bogus_value(monkeypatch):
    monkeypatch.setenv("DKG_TPU_DIGEST", "gpu")
    with pytest.raises(ValueError, match="DKG_TPU_DIGEST"):
        dh.digest_dispatch()


@pytest.mark.parametrize("val", [None, "auto"])
def test_digest_auto_follows_backend(monkeypatch, val):
    if val is None:
        monkeypatch.delenv("DKG_TPU_DIGEST", raising=False)
    else:
        monkeypatch.setenv("DKG_TPU_DIGEST", val)
    expect = "device" if jax.default_backend() == "tpu" else "host"
    assert dh.digest_dispatch() == expect


def test_digest_knob_forces_leg(monkeypatch):
    for leg in ("device", "host"):
        monkeypatch.setenv("DKG_TPU_DIGEST", leg)
        assert dh.digest_dispatch() == leg


# --- leg parity --------------------------------------------------------


@pytest.mark.parametrize("rows,words", [(1, 7), (5, 40), (3, 2048)])
def test_row_digests_legs_bit_identical(rows, words):
    arr = np.asarray(
        [[RNG.randrange(1 << 32) for _ in range(words)] for _ in range(rows)],
        np.uint32,
    )
    dev = np.asarray(dh.row_digests(jnp.asarray(arr), domain=5, dispatch="device"))
    host = dh.row_digests(arr, domain=5, dispatch="host")
    np.testing.assert_array_equal(dev, np.asarray(host))


def test_tree_digest_legs_bit_identical():
    vals = np.asarray([RNG.randrange(1 << 32) for _ in range(333)], np.uint32)
    dev = np.asarray(dh.tree_digest(jnp.asarray(vals), domain=11, dispatch="device"))
    host = np.asarray(dh.tree_digest(vals, domain=11, dispatch="host"))
    np.testing.assert_array_equal(dev, host)


# --- ceremony-level goldens -------------------------------------------


def _golden_ceremony(curve):
    c = ce.BatchedCeremony(curve, 4, 1, b"golden", random.Random(0xD16))
    return c, ce.deal_chunked(
        c.cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table
    )


def _check_goldens(curve, monkeypatch):
    c, (a, e, s, r) = _golden_ceremony(curve)
    for leg in ("device", "host"):
        monkeypatch.setenv("DKG_TPU_DIGEST", leg)
        digest = ce.transcript_digest_device(c.cfg, a, e, s, r)
        assert digest.hex() == GOLDEN_DIGEST[curve], leg
        rho = ce.derive_rho(c.cfg, a, e, s, r, 128)
        assert rho.tobytes().hex() == GOLDEN_RHO[curve], leg


def test_transcript_and_rho_golden_secp256k1(monkeypatch):
    """Both dispatch legs reproduce the pre-rewrite digest AND rho
    byte-for-byte (acceptance criterion: the knob never changes a
    ceremony's randomizers)."""
    _check_goldens("secp256k1", monkeypatch)


@pytest.mark.slow  # second curve = second deal compile; nightly tier
def test_transcript_and_rho_golden_ristretto255(monkeypatch):
    _check_goldens("ristretto255", monkeypatch)


# --- fiat_shamir_rho: the hashlib lanes, the scalar loop, the numpy BLAKE2b ---


def _rho_reference(cfg, transcript: bytes, rho_bits: int) -> np.ndarray:
    """The per-dealer hashlib loop with ``fh.encode`` a lane, as the seed had it."""
    fs = cfg.cs.scalar
    nbytes = (rho_bits + 7) // 8
    mask = (1 << rho_bits) - 1
    out = np.zeros((cfg.n, fs.limbs), np.uint32)
    for j in range(cfg.n):
        h = hashlib.blake2b(
            transcript + j.to_bytes(4, "little"),
            digest_size=nbytes,
            person=b"dkgtpu-rlc",
        )
        out[j] = fh.encode(fs, int.from_bytes(h.digest(), "little") & mask)
    return out


def _rho_lanes_numpy(cfg, transcript: bytes, rho_bits: int) -> np.ndarray:
    """The lanes' digests through the numpy BLAKE2b, the form
    ``fiat_shamir_rho`` had until PR 37: an implementation of RFC 7693
    that shares no code with ``hashlib``."""
    msgs = np.zeros((cfg.n, len(transcript) + 4), np.uint8)
    msgs[:, :-4] = np.frombuffer(transcript, np.uint8)
    msgs[:, -4:] = np.arange(cfg.n, dtype="<u4").reshape(cfg.n, 1).view(np.uint8)
    return blake2b_batch(msgs, digest_size=(rho_bits + 7) // 8, person=b"dkgtpu-rlc")


# 280 > the 256-bit scalar field: exercises the reduce-per-lane fallback;
# the shapes after the six are the cells' (16 lanes a fleet ceremony, 64
# the mix's heavy bucket, 1024 on both curves of the large ones) and a
# 253-bit order
@pytest.mark.parametrize(
    "curve,n,t,rho_bits",
    [("secp256k1", 6, 2, bits) for bits in (8, 24, 64, 128, 255, 280)]
    + [
        ("secp256k1", 16, 5, 128),
        ("secp256k1", 64, 16, 128),
        ("secp256k1", 1024, 341, 128),
        ("bls12_381_g1", 1024, 341, 128),
        ("ristretto255", 16, 5, 253),
    ],
)
def test_fiat_shamir_rho_matches_scalar_loop(curve, n, t, rho_bits):
    cfg = ce.CeremonyConfig(curve, n, t)
    fs = cfg.cs.scalar
    transcript = bytes(RNG.randrange(256) for _ in range(32))
    before = REGISTRY.snapshot()["counters"].get("rho_lanes_total", 0)
    got = ce.fiat_shamir_rho(cfg, transcript, rho_bits)
    assert REGISTRY.snapshot()["counters"]["rho_lanes_total"] == before + n
    np.testing.assert_array_equal(got, _rho_reference(cfg, transcript, rho_bits))
    # the same lanes from the numpy BLAKE2b, masked and reduced as ints
    mask = (1 << rho_bits) - 1
    lanes = _rho_lanes_numpy(cfg, transcript, rho_bits)
    want = fh.encode(fs, [int.from_bytes(lane.tobytes(), "little") & mask for lane in lanes])
    np.testing.assert_array_equal(got, want)


def test_fiat_shamir_rho_golden_128():
    """Anchored constant (captured pre-rewrite): guards the reference
    loop above and the batch path from drifting together."""
    cfg = ce.CeremonyConfig("secp256k1", 6, 2)
    got = ce.fiat_shamir_rho(cfg, bytes(range(32)), 128)
    assert got.tobytes().hex() == (
        "4ec60000d89f0000f1500000f3fa000002fe000092cc0000f6a6000030b20000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "9b580000d80e0000452d0000bdec000016680000a86800005d0900005c500000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "3f900000c7ca0000467d00008c0a00000a8900008494000019f50000b70f0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "63fe00001f8a0000c5390000167200003ad3000078490000c7eb00007c680000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "cab90000a3da00009c8e00006f1e0000e1da0000bae30000a23d0000df9d0000"
        "0000000000000000000000000000000000000000000000000000000000000000"
        "e20d000068260000575f000026f3000035c70000fad00000c96600007b520000"
        "0000000000000000000000000000000000000000000000000000000000000000"
    )


# --- host canonicalisation twin ---------------------------------------


# the shapes that run: a convoy's master keys (8), one kernel block
# (128), a width-8 (16,5) convoy's commitment tensor (128, 6) = 768
# lanes, just past one row of the Montgomery scan (1030: still one row),
# and the first lane count that scans two rows
@pytest.mark.parametrize(
    "shape", [(1,), (5,), (8,), (128,), (128, 6), (1030,), (2050,)], ids=str
)
@pytest.mark.parametrize("curve", ["secp256k1", "ristretto255", "bls12_381_g1"])
def test_affine_canon_host_matches_device(curve, shape):
    """The host digest leg's big-int canonicalisation agrees limb for
    limb with the jitted device one at every lane count the row rule
    tells apart, on 16- and 24-limb fields, identity lanes included
    (canon must map them to the canonical identity encoding, not divide
    by zero)."""
    from dkg_tpu.groups import device as gd

    cs = gd.ALL_CURVES[curve]
    n_lanes = int(np.prod(shape))
    rows = gd._canon_rows(n_lanes)
    assert rows == (2 if n_lanes == 2050 else 1)
    pts = projective_batch(cs, shape, random.Random(0xCA9 + n_lanes))
    series = 'affine_canon_calls_total{path="xla",rows="%s"}' % ("1" if rows == 1 else ">1")
    before = REGISTRY.snapshot()["counters"]
    dev = np.asarray(gd.affine_canon(cs, jnp.asarray(pts)))
    after = REGISTRY.snapshot()["counters"]
    host = gd.affine_canon_host(cs, pts)
    np.testing.assert_array_equal(dev, host)
    np.testing.assert_array_equal(dev.reshape(-1, *dev.shape[-2:])[0], gd.identity(cs))
    # booked per dispatch from the host, never at trace time only
    assert after[series] - before.get(series, 0) == 1
    assert (
        after["affine_canon_lanes_total"] - before.get("affine_canon_lanes_total", 0)
        == n_lanes
    )


def test_affine_canon_is_one_module_named_for_the_trace():
    """The inversion stays inside the one jitted module whose name the
    device trace (and the benchmark's digest_time_share) matches."""
    from dkg_tpu.groups import device as gd

    cs = gd.SECP256K1
    spec = jax.ShapeDtypeStruct((8, cs.ncoords, cs.field.limbs), jnp.uint32)
    assert "module @jit_affine_canon " in gd._affine_canon_jit.lower(cs, "xla", spec).as_text()


def test_affine_canon_path_keys_the_compiled_program(monkeypatch):
    """The inversion is picked from the environment when the program is
    traced, so the path is a static key of the jitted program: one shape
    called under the other switch traces again (the kernel's wrapper is
    entered) and books the path it runs, where a key without it would
    run the cached program under the other label."""
    from dkg_tpu.groups import device as gd

    cs = gd.SECP256K1
    pts = projective_batch(cs, (3,), random.Random(0xCA9))
    monkeypatch.delenv("DKG_TPU_ASSUME_BACKEND", raising=False)
    monkeypatch.setenv("DKG_TPU_PALLAS", "0")
    on_xla = np.asarray(gd.affine_canon(cs, jnp.asarray(pts)))
    monkeypatch.setenv("DKG_TPU_PALLAS", "1")
    before = REGISTRY.snapshot()["counters"]
    on_kernel = np.asarray(gd.affine_canon(cs, jnp.asarray(pts)))
    after = REGISTRY.snapshot()["counters"]
    np.testing.assert_array_equal(on_xla, gd.affine_canon_host(cs, pts))
    np.testing.assert_array_equal(on_kernel, on_xla)
    rose = {
        k: v - before.get(k, 0)
        for k, v in after.items()
        if k.startswith(("affine_canon_", "pallas_calls_total")) and v != before.get(k, 0)
    }
    assert rose == {
        'affine_canon_calls_total{path="fused_interpret",rows="1"}': 1,
        "affine_canon_lanes_total": 3,
        'pallas_calls_total{kernel="mod_pow_const"}': 1,
    }


# --- the device leg reads the round-1 tensors where deal left them (ISSUE 43) ---

CURVES = ["secp256k1", "ristretto255", "bls12_381_g1"]


def _round1(curve: str, k: int, n: int, t: int, seed: int):
    """A convoy's four round-1 tensors as numpy, (k, n, ...): projective points with
    identities among them and limbs of 16 bits; nothing is dealt, so nothing compiles
    but the digest leg."""
    from dkg_tpu.groups import device as gd

    cs = gd.ALL_CURVES[curve]
    rng = np.random.default_rng(seed)
    a = projective_batch(cs, (k, n, t + 1), random.Random(seed))
    e = projective_batch(cs, (k, n, t + 1), random.Random(seed + 1))
    s, r = (rng.integers(0, 1 << 16, (k, n, n, cs.scalar.limbs), dtype=np.uint32) for _ in range(2))
    return ce.CeremonyConfig(curve, n, t), (a, e, s, r)


def _host_bytes() -> float:
    return REGISTRY.snapshot()["counters"].get("round1_host_bytes_total", 0)


@pytest.mark.parametrize("curve", CURVES)
def test_convoy_rho_on_device_arrays_on_numpy_and_alone_is_one_rho(curve, monkeypatch):
    """Width 4: `derive_rho_convoy` on device arrays, on numpy arrays, and
    `derive_rho` ceremony by ceremony, on both legs: one rho.  `round1_host_bytes_total`
    stands still on the device leg; the host leg books the four tensors' bytes."""
    from dkg_tpu.service import engine

    cfg, host = _round1(curve, 4, 4, 1, 0x43)
    dev = tuple(jnp.asarray(x) for x in host)
    tensors = sum(x.nbytes for x in host)
    rhos = []
    for leg in ("device", "host"):
        monkeypatch.setenv("DKG_TPU_DIGEST", leg)
        before = _host_bytes()
        rhos.append(engine.derive_rho_convoy(cfg, *dev, 128))
        assert _host_bytes() - before == (0 if leg == "device" else tensors)
        rhos.append(engine.derive_rho_convoy(cfg, *host, 128))
        rhos.append(np.stack([ce.derive_rho(cfg, *(x[i] for x in dev), 128) for i in range(4)]))
    assert rhos[0].shape == (4, 4, cfg.cs.scalar.limbs)
    for other in rhos[1:]:
        np.testing.assert_array_equal(rhos[0], other)
    assert len({rhos[0][i].tobytes() for i in range(4)}) == 4  # four transcripts, four rhos


def _jit_calls(fn, *args) -> list:
    """What `fn(*args)` dispatches, traced: the name of every jitted call at the top
    level, or the primitive's name for an operation outside one.  The arguments are
    tracers, so a fetch to the host (`np.asarray`) raises."""
    return [
        eqn.params["name"] if eqn.primitive.name == "jit" else f"eager:{eqn.primitive.name}"
        for eqn in jax.make_jaxpr(fn)(*args).eqns
    ]


@pytest.mark.parametrize("k", [None, 4], ids=["derive_rho", "convoy"])
def test_device_leg_is_five_jitted_dispatches_and_nothing_between(k):
    """No eager reshape, cast, concatenate or copy between the programs, with or without
    a ceremony axis: two `affine_canon` and three `_tree_from_words_jit`, whose names the
    device trace (the benchmark's `digest_time_share`) finds them by."""
    cfg, host = _round1("secp256k1", k or 1, 4, 1, 0x44)
    if k is None:
        host = tuple(x[0] for x in host)
    dev = tuple(jnp.asarray(x) for x in host)
    leg = lambda *t: ce._dealer_rows_device(cfg, *t, dispatch="device")
    assert _jit_calls(leg, *dev) == ["affine_canon"] * 2 + ["_tree_from_words_jit"] * 3
    with pytest.raises(jax.errors.TracerArrayConversionError):
        _jit_calls(lambda *t: ce._dealer_rows_device(cfg, *t, dispatch="host"), *dev)  # the host leg fetches


def test_device_leg_hands_the_programs_deals_own_arrays(monkeypatch):
    """The run itself: five calls, the tensors handed over as the very arrays that
    came in, the domain as a numpy scalar (no device array is made for it)."""
    from dkg_tpu.groups import device as gd

    cfg, host = _round1("secp256k1", 4, 4, 1, 0x45)
    a, e, s, r = dev = tuple(jnp.asarray(x) for x in host)
    want = [np.asarray(x) for x in ce._dealer_rows_device(cfg, *host, dispatch="host")]
    canon, tree = [], []
    canon_jit, tree_jit = gd._affine_canon_jit, dh._tree_from_words_jit
    monkeypatch.setattr(gd, "_affine_canon_jit", lambda cs, path, pts: canon.append(pts) or canon_jit(cs, path, pts))
    monkeypatch.setattr(
        dh, "_tree_from_words_jit", lambda parts, domain, lead, interp: tree.append((parts, domain, lead)) or tree_jit(parts, domain, lead, interp)
    )
    rows = ce._dealer_rows_device(cfg, *dev, dispatch="device")
    assert len(canon) == 2 and canon[0] is a and canon[1] is e
    assert [(len(p), type(d), int(d), lead) for p, d, lead in tree] == [
        (1, np.uint32, 1, 2), (1, np.uint32, 2, 2), (2, np.uint32, 3, 2),
    ]
    assert tree[2][0][0] is s and tree[2][0][1] is r
    assert [x.shape for x in rows] == [(16, 8)] * 3
    for got, exp in zip(rows, want):
        np.testing.assert_array_equal(np.asarray(got), exp)


def test_the_tree_keeps_its_module_name_for_the_trace():
    spec = jax.ShapeDtypeStruct((2, 4, 4, 16), jnp.uint32)
    text = dh._tree_from_words_jit.lower((spec, spec), np.uint32(3), 2, True).as_text()
    assert "module @jit__tree_from_words_jit " in text
