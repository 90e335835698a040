"""North-star sharded-ceremony path: layout helpers, the sign-lane
mesh knob, the perf_regress NORTHSTAR gate, and (slow tier) sharded
vs single-chip bit-exactness in a forced-mesh subprocess.

The default-tier tests here are deliberately sub-second: they exercise
placement/layout logic (device_put only — no program compiles) and the
pure-python gate/seam logic.  Everything that compiles a sharded XLA
program rides the slow tier, like the rest of tests/test_parallel.py.
"""

from __future__ import annotations

import inspect
import json
import os
import pathlib
import random
import subprocess
import sys

import numpy as np
import pytest

import jax
from jax.sharding import PartitionSpec as P

from dkg_tpu.parallel import mesh as pm

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_script(name: str):
    sys.path.insert(0, str(REPO / "scripts"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# shard_map wrapper
# ---------------------------------------------------------------------------


def test_installed_shard_map_takes_check_vma():
    """mesh.py passes check_vma by name — the installed jax's spelling."""
    assert "check_vma" in inspect.signature(pm._shard_map).parameters


def test_shard_map_nocheck_disables_the_vma_check(monkeypatch):
    seen = {}

    def recording_shard_map(f, **kw):
        seen.update(kw)
        return f

    monkeypatch.setattr(pm, "_shard_map", recording_shard_map)
    wrapped = pm._shard_map_nocheck(
        lambda x: x + 1, mesh="m", in_specs="i", out_specs="o"
    )
    assert wrapped(41) == 42
    assert seen == {"mesh": "m", "in_specs": "i", "out_specs": "o", "check_vma": False}


# ---------------------------------------------------------------------------
# placement / slab layout helpers (device_put only — no compiles)
# ---------------------------------------------------------------------------


def test_place_sharded_party_axis_layout():
    mesh = pm.make_mesh(8)
    x = np.arange(16 * 3, dtype=np.uint32).reshape(16, 3)
    arr = pm.place_sharded(mesh, x)
    assert arr.sharding.mesh == mesh
    assert arr.sharding.spec == P(pm.PARTY_AXIS)
    starts = sorted(sh.index[0].start or 0 for sh in arr.addressable_shards)
    assert starts == [0, 2, 4, 6, 8, 10, 12, 14]
    np.testing.assert_array_equal(np.asarray(arr), x)


def test_place_sharded_replicated_spec():
    mesh = pm.make_mesh(8)
    x = np.arange(12, dtype=np.uint32).reshape(3, 4)
    arr = pm.place_sharded(mesh, x, spec=P())
    assert len(arr.addressable_shards) == 8
    for sh in arr.addressable_shards:
        np.testing.assert_array_equal(np.asarray(sh.data), x)


def test_mesh_slabs_prefers_shard_views():
    """On a party-sharded array whose shard starts equal the requested
    spans, _mesh_slabs hands back the per-shard blocks (zero-copy on the
    owning device); on a plain ndarray it degrades to slices."""
    mesh = pm.make_mesh(8)
    x = np.arange(16 * 2, dtype=np.uint32).reshape(16, 2)
    arr = pm.place_sharded(mesh, x)
    spans = [(k * 2, (k + 1) * 2) for k in range(8)]
    from dkg_tpu.dkg import hybrid_batch as hb

    slabs = hb._mesh_slabs(arr, spans)
    assert len(slabs) == 8
    for (a, b), slab in zip(spans, slabs):
        np.testing.assert_array_equal(np.asarray(slab), x[a:b])
    # non-matching spans (one big span) fall back to plain slicing
    whole = hb._mesh_slabs(arr, [(0, 16)])
    assert len(whole) == 1
    np.testing.assert_array_equal(np.asarray(whole[0]), x)
    # plain host arrays always slice
    host = hb._mesh_slabs(x, spans)
    for (a, b), slab in zip(spans, host):
        np.testing.assert_array_equal(slab, x[a:b])


# ---------------------------------------------------------------------------
# sign-lane mesh knob (parallel.signmesh)
# ---------------------------------------------------------------------------


def test_sign_mesh_knob_gating(monkeypatch):
    from dkg_tpu.parallel import signmesh

    monkeypatch.delenv("DKG_TPU_SIGN_MESH", raising=False)
    assert signmesh.sign_mesh() is None, "unset keeps the single-device ladder"
    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "0")
    assert signmesh.sign_mesh() is None
    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "")
    assert signmesh.sign_mesh() is None, "empty value means unset"
    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "force")
    mesh = signmesh.sign_mesh()
    assert mesh is not None and mesh.devices.size == len(jax.devices())
    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "yes")
    with pytest.raises(ValueError, match="DKG_TPU_SIGN_MESH"):
        signmesh.sign_mesh()


def test_sign_mesh_auto_guards_on_host_parallelism(monkeypatch):
    """``1`` is the auto setting: the depth-dominated ladder only
    shards where shard programs actually run concurrently, so a
    single-core CPU host keeps the single-device lane while a
    multi-core one (or any accelerator backend) engages the mesh."""
    import dkg_tpu.parallel.signmesh as signmesh

    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "1")
    monkeypatch.setattr(signmesh.os, "cpu_count", lambda: 1)
    assert signmesh.sign_mesh() is None, "1 core: sharding serialises"
    monkeypatch.setattr(signmesh.os, "cpu_count", lambda: 8)
    mesh = signmesh.sign_mesh()
    assert mesh is not None and mesh.devices.size == len(jax.devices())


def test_sign_mesh_requires_two_devices(monkeypatch):
    from dkg_tpu.parallel import signmesh

    monkeypatch.setenv("DKG_TPU_SIGN_MESH", "force")
    only = jax.devices()[0]
    monkeypatch.setattr(jax, "devices", lambda: [only])
    assert signmesh.sign_mesh() is None, "a 1-device mesh shards nothing"


# ---------------------------------------------------------------------------
# perf_regress NORTHSTAR gate + northstar_bench helpers (pure python)
# ---------------------------------------------------------------------------


def _ns_round(tmp_path, i, **over):
    doc = {
        "bench": "northstar",
        "curve": "secp256k1",
        "n": 16,
        "t": 5,
        "mesh_shape": [8],
        "platform": "cpu",
        "wall_s": 1.0,
        "bit_exact_vs_unsharded": True,
        "bit_exact_shape": [16, 5],
    }
    doc.update(over)
    (tmp_path / f"NORTHSTAR_r{i:02d}.json").write_text(json.dumps(doc))


def test_perf_regress_northstar_gate(tmp_path):
    perf_regress = _load_script("perf_regress")

    assert perf_regress.main([str(tmp_path)]) == 0  # no rounds: skip
    _ns_round(tmp_path, 1)
    assert perf_regress.main([str(tmp_path)]) == 0  # one round: floor only
    _ns_round(tmp_path, 2, wall_s=1.1)
    assert perf_regress.main([str(tmp_path)]) == 0  # 10% slower: within gate
    _ns_round(tmp_path, 3, wall_s=1.5)
    assert perf_regress.main([str(tmp_path)]) == 1  # 36% slower: trips
    _ns_round(tmp_path, 4, wall_s=9.0, n=64, t=21)
    assert perf_regress.main([str(tmp_path)]) == 0  # shape mismatch: skip
    _ns_round(tmp_path, 5, n=64, t=21, bit_exact_vs_unsharded=False)
    assert perf_regress.main([str(tmp_path)]) == 1  # correctness floor


def test_northstar_bench_helpers(tmp_path):
    ns = _load_script("northstar_bench")

    assert ns._next_round(tmp_path) == 1
    (tmp_path / "NORTHSTAR_r03.json").write_text("{}")
    assert ns._next_round(tmp_path) == 4
    # the extrapolation cost model is monotone in both n and t
    assert ns._pair_cost(4096, 1365) > ns._pair_cost(64, 21) > ns._pair_cost(16, 5)
    assert ns.TARGET["n"] == 4096 and ns.TARGET["chips"] == 8


# ---------------------------------------------------------------------------
# slow tier: sharded vs single-chip bit-exactness in a forced-mesh child
# ---------------------------------------------------------------------------

_BITEXACT_CHILD = r"""
import json, random, sys
import numpy as np
import jax, jax.numpy as jnp
from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.parallel import mesh as pm

n, t = int(sys.argv[1]), int(sys.argv[2])
assert len(jax.devices()) == 8, jax.devices()
rho_bits = 64
rng = random.Random(0xB17E)
c = ce.BatchedCeremony("secp256k1", n, t, b"bit-exact-child", rng)

a, e, s, r = ce.deal(c.cfg, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table)
rho_ref = np.asarray(ce.derive_rho(c.cfg, a, e, s, r, rho_bits))
finals_ref = np.asarray(ce.aggregate_shares(c.cfg, s, jnp.ones((n,), bool)))
master_ref = np.asarray(ce.master_key_from_bare(c.cfg, a, jnp.ones((n,), bool)))

mesh = pm.make_mesh(8)
res = pm.run_sharded_ceremony(
    c.cfg, mesh, c.coeffs_a, c.coeffs_b, c.g_table, c.h_table,
    rho_bits=rho_bits, ceremony_id="bit-exact-child",
)
out = {
    "rho_equal": bool(np.array_equal(np.asarray(res["rho"]), rho_ref)),
    "master_equal": bool(np.array_equal(np.asarray(res["master"]), master_ref)),
    "finals_equal": bool(np.array_equal(np.asarray(res["final_shares"]), finals_ref)),
    "ok": bool(np.asarray(res["ok"]).all()),
    "n_devices": res["n_devices"],
}
print(json.dumps(out))
"""


@pytest.mark.slow
@pytest.mark.parametrize("shape", [(16, 5), (64, 21)])
def test_sharded_ceremony_bit_exact_vs_single_chip_subprocess(shape, tmp_path):
    """The acceptance oracle at both ISSUE shapes: master key bytes,
    the Fiat-Shamir rho, and every party's final share from the mesh
    path equal the single-chip engine's, bit for bit, on a freshly
    forced 8-device CPU mesh (the child owns its XLA_FLAGS, so the
    check cannot silently inherit a different topology)."""
    n, t = shape
    script = tmp_path / "bitexact_child.py"
    script.write_text(_BITEXACT_CHILD)
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        PYTHONPATH=str(REPO),
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
    )
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run(
        [sys.executable, str(script), str(n), str(t)],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=2400,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out == {
        "rho_equal": True,
        "master_equal": True,
        "finals_equal": True,
        "ok": True,
        "n_devices": 8,
    }


@pytest.mark.slow
def test_seal_shares_mesh_bytes_match_pipeline():
    """The mesh-overlapped transport sealer is byte-identical to the
    whole-round pipeline: same DEM blocks, same KEM points, per shard
    and per recipient — the overlap only reorders host work."""
    import jax.numpy as jnp

    from dkg_tpu.crypto.keys import Keypair
    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.dkg import hybrid_batch as hb
    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh

    rng = random.Random(0x5EA1)
    curve, n, t = "secp256k1", 8, 3
    g = gh.ALL_GROUPS[curve]
    cfg = ce.CeremonyConfig(curve, n, t)
    fs = cfg.cs.scalar
    keys = [Keypair.generate(g, rng) for _ in range(n)]
    pks_dev = gd.from_host(cfg.cs, [k.pk for k in keys])
    rand2 = lambda: np.asarray(
        fh.encode(fs, [[fs.rand_int(rng) for _ in range(n)] for _ in range(n)])
    )
    shares, hidings = rand2(), rand2()
    r = jnp.asarray(rand2())
    c = ce.BatchedCeremony(curve, n, t, b"seal-mesh", rng)

    def flat(sealed):
        out = []
        for row in sealed:
            for s_ct, h_ct in row:
                out.append(
                    (
                        g.encode(s_ct.e1),
                        s_ct.ciphertext,
                        g.encode(h_ct.e1),
                        h_ct.ciphertext,
                    )
                )
        return out

    ref = flat(
        hb.seal_shares_pipeline(g, cfg, shares, hidings, pks_dev, r, c.g_table)
    )
    mesh = pm.make_mesh(8)
    sh_dev = pm.place_sharded(mesh, shares)
    hid_dev = pm.place_sharded(mesh, hidings)
    got = flat(
        hb.seal_shares_mesh(
            g, cfg, mesh, sh_dev, hid_dev, pks_dev, r, c.g_table
        )
    )
    assert got == ref
