"""Sharded-ceremony tests on the 8-virtual-device CPU mesh (conftest.py
forces xla_force_host_platform_device_count=8, mirroring the driver's
multichip dryrun)."""

import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.parallel import mesh as pm

RNG = random.Random(0x5A4D)


@pytest.mark.slow
def test_sharded_ceremony_smoke():
    """Sharded smoke: the full mesh ceremony (deal -> digest -> rho ->
    verify/finalise) runs and self-verifies on the 8-virtual-device
    mesh.  Slow tier: the mesh engine compile alone costs ~100s on the
    1-core box, and the bit-parity twin below re-covers this path
    whenever the slow tier runs."""
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    n, t = 8, 3
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-test", RNG)
    mesh = pm.make_mesh(8)
    ok, finals, master, qualified = pm.sharded_ceremony(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table, rho_bits=64
    )
    assert np.asarray(ok).all()
    assert np.asarray(qualified).all()
    assert np.asarray(finals).shape == (n, c.cfg.cs.scalar.limbs)


@pytest.mark.slow
def test_sharded_matches_single_device():
    assert len(jax.devices()) >= 8, "conftest must provide 8 virtual devices"
    n, t = 8, 3
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-test", RNG)
    rho_bits = 64

    # single-device reference (rho from the same real-transcript digest
    # the sharded path derives internally)
    a, e, s, r = ce.deal(c.cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    rho = jnp.asarray(ce.derive_rho(c.cfg, a, e, s, r, rho_bits))
    ok_ref = ce.verify_batch(c.cfg, e, s, r, rho, rho_bits, c.g_table, c.h_table)
    finals_ref = ce.aggregate_shares(c.cfg, s, jnp.ones((n,), bool))
    master_ref = ce.master_key_from_bare(c.cfg, a, jnp.ones((n,), bool))

    mesh = pm.make_mesh(8)
    ok, finals, master, qualified = pm.sharded_ceremony(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table, rho_bits=rho_bits
    )

    assert np.asarray(ok).all()
    assert np.asarray(ok_ref).all()
    assert np.asarray(qualified).all()
    # bit-exact parity between sharded and single-device paths
    np.testing.assert_array_equal(np.asarray(finals), np.asarray(finals_ref))
    np.testing.assert_array_equal(np.asarray(master), np.asarray(master_ref))


@pytest.mark.slow
def test_sharded_deal_matches_single_device_transcript():
    """The sharded round-1 output (all four tensors dealer-sharded — the
    commitments are deliberately never replicated) is bit-identical to
    the single-device one, so both derive the same Fiat-Shamir
    randomizers."""
    n, t = 8, 3
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-tr", RNG)
    a, e, s, r = ce.deal(c.cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
    mesh = pm.make_mesh(8)
    a_sh, e_sh, s_sh, r_sh = pm.sharded_deal(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table
    )
    np.testing.assert_array_equal(np.asarray(e_sh), np.asarray(e))
    np.testing.assert_array_equal(np.asarray(a_sh), np.asarray(a))
    np.testing.assert_array_equal(np.asarray(s_sh), np.asarray(s))
    # the shard-folded digest equals the flat canonical (device) digest
    # bit-for-bit — sharded and single-chip engines derive the same rho
    assert ce.sharded_transcript_digest(
        c.cfg, a_sh, e_sh, s_sh, r_sh
    ) == ce.transcript_digest_device(c.cfg, a, e, s, r)


@pytest.mark.slow
def test_sharded_verify_finalise_chunked_matches_oneshot(monkeypatch):
    """The recipient-chunked round-2 body (``pm._verify_chunk_default``, the
    n=16384 HBM fix: per-chunk all_to_all + verify + aggregate through
    lax.map with a ragged tail) is bit-identical to the one-shot body.

    n=24 over 8 devices gives block=3; chunk=2 exercises BOTH the
    sequential-map full chunks (k=1) and the smaller tail call (rem=1).
    The blame-path re-finalise (_aggregate_chunked) is checked the same
    way over a non-trivial qualified mask.  Slow tier: ~8 min of XLA:CPU
    compiles (6 sharded program variants) on the 1-core box.
    """
    n, t = 24, 5
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-chunk", RNG)
    rho_bits = 64
    mesh = pm.make_mesh(8)
    a, e, s, r = pm.sharded_deal(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table
    )
    digest = ce.sharded_transcript_digest(c.cfg, a, e, s, r)
    rho = jnp.asarray(ce.fiat_shamir_rho(c.cfg, digest, rho_bits))

    def chunk(width):
        # the rule is the one width a program is traced at: patch it, and
        # drop the memoized programs traced at the other
        monkeypatch.setattr(pm, "_verify_chunk_default", lambda cfg, block: min(width, block))
        pm._verify_finalise_prog.cache_clear()
        pm._finalise_prog.cache_clear()

    def run_once():
        ok, finals, master = pm.sharded_verify_finalise(
            c.cfg, mesh, a[:, 0], e, s, r, c.g_table, c.h_table, rho, rho_bits
        )
        return np.asarray(ok), np.asarray(finals), np.asarray(master)

    chunk(n)  # >= block: unchunked
    ok_ref, fin_ref, m_ref = run_once()
    chunk(2)
    ok_ch, fin_ch, m_ch = run_once()
    assert ok_ref.all() and ok_ch.all()
    np.testing.assert_array_equal(fin_ch, fin_ref)
    np.testing.assert_array_equal(m_ch, m_ref)

    qual = jnp.asarray([i % 5 != 0 for i in range(n)])
    chunk(n)
    fin2_ref, m2_ref = map(np.asarray, pm.sharded_finalise(c.cfg, mesh, a[:, 0], s, qual))
    chunk(2)
    fin2_ch, m2_ch = map(np.asarray, pm.sharded_finalise(c.cfg, mesh, a[:, 0], s, qual))
    np.testing.assert_array_equal(fin2_ch, fin2_ref)
    np.testing.assert_array_equal(m2_ch, m2_ref)


def test_mesh_shapes():
    mesh = pm.make_mesh(8)
    assert mesh.devices.size == 8
    # committee size must divide over the mesh
    c = ce.BatchedCeremony("ristretto255", 6, 2, b"x", RNG)
    try:
        pm.sharded_ceremony(
            c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table, rho_bits=64
        )
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_multihost_helpers_single_process():
    """init_multihost is a no-op single-process; the global mesh spans
    the 8 virtual devices and reports a full party block."""
    from dkg_tpu.parallel import multihost

    multihost.init_multihost()  # no-op path
    m = multihost.global_party_mesh()
    assert m.devices.size == len(jax.devices())
    start, stop = multihost.process_party_block(16)
    assert (start, stop) == (0, 16)


def test_party_block_derives_from_mesh_positions(monkeypatch):
    """The host-side party block follows the devices' POSITIONS on the
    party axis, not their raw ids — and refuses non-contiguous layouts
    loudly (silently sealing the wrong parties' shares is the failure
    mode the round-2 review flagged)."""
    import pytest as _pytest

    from dkg_tpu.parallel import multihost
    from jax.sharding import Mesh

    devs = jax.devices()
    # a process owning devices at positions 2..3 of a permuted mesh
    order = [devs[4], devs[5], devs[0], devs[1], devs[6], devs[7], devs[2], devs[3]]
    mesh = Mesh(np.asarray(order), ("parties",))
    monkeypatch.setattr(jax, "local_devices", lambda: [devs[0], devs[1]])
    assert multihost.process_party_block(16, mesh) == (4, 8)
    # the same devices at NON-contiguous positions must raise
    order_bad = [devs[0], devs[4], devs[1], devs[5], devs[6], devs[7], devs[2], devs[3]]
    mesh_bad = Mesh(np.asarray(order_bad), ("parties",))
    with _pytest.raises(RuntimeError, match="non-contiguous"):
        multihost.process_party_block(16, mesh_bad)
    # uneven sharding is rejected up front
    with _pytest.raises(ValueError, match="evenly"):
        multihost.process_party_block(17, mesh)


@pytest.mark.slow
def test_sharded_blame_disqualifies_cheating_dealer():
    """An injected cheat on the mesh drops the ceremony into
    sharded_blame: the guilty dealer is disqualified on every shard and
    the re-finalised results equal the single-device engine's blame-path
    results over the same qualified set."""
    from dkg_tpu.fields import host as fh

    n, t = 8, 3
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-blame", RNG)
    fs = c.cfg.cs.scalar

    def corrupt(s_np):
        bad = np.asarray(s_np).copy()
        # dealer 3 (index 2) deals garbage to recipients 2 and 7
        for i in (1, 6):
            bad[2, i] = fh.encode(fs, (fh.decode_int(fs, bad[2, i]) + 5) % fs.modulus)
        return bad

    # single-device reference with the same corruption
    out_ref = c.run(rho_bits=64, tamper=lambda a, e, s, r: (a, e, jnp.asarray(corrupt(s)), r))
    assert out_ref["complaints"] == [(2, 3), (7, 3)]

    def tamper(a, e, s, r):
        bad = jax.device_put(corrupt(np.asarray(s)), s.sharding)
        return a, e, bad, r

    mesh = pm.make_mesh(8)
    ok, finals, master, qualified = pm.sharded_ceremony(
        c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table,
        rho_bits=64, tamper=tamper,
    )
    assert np.asarray(qualified).tolist() == [
        True, True, False, True, True, True, True, True,
    ]
    # pre-adjudication check: exactly the victim recipients failed
    assert np.asarray(ok).tolist() == [
        True, False, True, True, True, True, False, True,
    ]
    np.testing.assert_array_equal(
        np.asarray(finals), np.asarray(out_ref["final_shares"])
    )
    np.testing.assert_array_equal(np.asarray(master), np.asarray(out_ref["master"]))


@pytest.mark.slow
def test_sharded_ceremony_aborts_past_threshold():
    """More than t cheating dealers raises MISBEHAVIOUR_HIGHER_THRESHOLD
    (committee.rs:340-347) instead of finalising a key backed by fewer
    than t+1 honest dealers."""
    import pytest

    from dkg_tpu.fields import host as fh
    from dkg_tpu.dkg.errors import DkgError, DkgErrorKind

    n, t = 8, 2
    c = ce.BatchedCeremony("ristretto255", n, t, b"sharded-abort", RNG)
    fs = c.cfg.cs.scalar

    def tamper(a, e, s, r):
        bad = np.asarray(s).copy()
        for j in (0, 3, 5):  # 3 cheating dealers > t=2
            bad[j, 1] = fh.encode(fs, (fh.decode_int(fs, bad[j, 1]) + 1) % fs.modulus)
        return a, e, jax.device_put(bad, s.sharding), r

    mesh = pm.make_mesh(8)
    with pytest.raises(DkgError) as exc:
        pm.sharded_ceremony(
            c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table,
            rho_bits=64, tamper=tamper,
        )
    assert exc.value.kind == DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD


@pytest.mark.slow
def test_multihost_two_process_smoke():
    """Two REAL jax processes (gloo collectives) run the sharded
    ceremony over a global mesh and agree on the master key — the DCN
    branches (process_allgather digest fold, _host_global) execute for
    real.  Slow tier: spawns subprocesses, ~5 min on this box."""
    import subprocess
    import sys
    from pathlib import Path

    repo = Path(__file__).resolve().parent.parent
    rc = subprocess.call(
        [sys.executable, str(repo / "scripts" / "multihost_smoke.py")],
        cwd=repo,
        timeout=2400,
    )
    assert rc == 0


def test_party_block_rejects_multi_axis_mesh():
    """A multi-axis mesh must be rejected: flat positions would not map
    to party-axis coordinates."""
    from dkg_tpu.parallel import multihost
    from jax.sharding import Mesh

    devs = np.asarray(jax.devices()).reshape(2, 4)
    with pytest.raises(ValueError, match="1-D"):
        multihost.process_party_block(16, Mesh(devs, ("replicas", "parties")))


def test_sharded_transcript_digest_rejects_mixed_layout():
    """Mixed dealer layouts (some tensors sharded, some replicated) must
    raise a typed ValueError, not silently fold the wrong rows into the
    digest (a wrong-but-valid rho is a soundness bug, and a bare assert
    would vanish under ``python -O``)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    cfg = ce.CeremonyConfig("ristretto255", 8, 2)
    mesh = pm.make_mesh(8)
    sharded = NamedSharding(mesh, P(pm.PARTY_AXIS))
    replicated = NamedSharding(mesh, P())
    cs = cfg.cs
    comm = jnp.zeros((cfg.n, cfg.t + 1, cs.ncoords, cs.field.limbs), jnp.uint32)
    sh = jnp.zeros((cfg.n, cfg.n, cs.scalar.limbs), jnp.uint32)
    a = jax.device_put(comm, sharded)
    e = jax.device_put(comm, sharded)
    s = jax.device_put(sh, replicated)  # the odd one out
    r = jax.device_put(sh, sharded)
    with pytest.raises(ValueError, match="dealer-axis layout"):
        ce.sharded_transcript_digest(cfg, a, e, s, r)
