"""One owner for the knobs that shape a traced program.

``utils.envknobs.PROGRAM_SHAPING`` is the only list; the memoized mesh
builders (parallel/mesh, parallel/signmesh) and the AOT store's digest
header (service/aot) all key on ``envknobs.program_shape()``.  Nothing
here compiles: the builders are replaced by recorders.
"""

from __future__ import annotations

import ast
import pathlib
import re

import numpy as np
import pytest

from dkg_tpu.dkg import ceremony as ce
from dkg_tpu.fields import device as fd
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import precompute as gp
from dkg_tpu.parallel import mesh as pm
from dkg_tpu.parallel import signmesh
from dkg_tpu.service import aot
from dkg_tpu.utils import envknobs

PKG = pathlib.Path(aot.__file__).resolve().parent.parent

#: Read in the scanned files, never under a tracer.
NOT_SHAPING = {
    "DKG_TPU_TABLE_CACHE",  # groups.precompute: where host tables are stored
    "DKG_TPU_SIGN_MESH",  # parallel.signmesh: WHETHER to shard; the mesh is in the key
}

#: Removed at PR 30 (suffixes, so that this file does not name them).
REMOVED = ("CARRY", "REDUCE", "FUSED_MULTI", "FB_WINDOW", "DEAL_CHUNK", "DEM_CHUNK")

MESH_BUILDERS = (
    "_deal_commitments_prog",
    "_deal_shares_prog",
    "_verify_finalise_prog",
    "_finalise_prog",
    "_blame_prog",
)


@pytest.fixture
def clean_env(monkeypatch):
    for name in envknobs.PROGRAM_SHAPING:
        monkeypatch.delenv(name, raising=False)


def _mesh_keys(monkeypatch) -> list:
    """The cache key each memoized builder is asked for, now."""
    keys = []

    def recorder(*key):
        keys.append(key)
        return lambda *args: None

    cfg = ce.CeremonyConfig("ristretto255", 8, 2)
    mesh = pm.make_mesh(4)
    for name in MESH_BUILDERS:
        monkeypatch.setattr(pm, name, recorder)
    monkeypatch.setattr(signmesh, "_ladder_prog", recorder)
    pm.sharded_deal_commitments(cfg, mesh, None, None, None, None)
    pm.sharded_deal_shares(cfg, mesh, None, None)
    pm.sharded_verify_finalise(cfg, mesh, None, None, None, None, None, None, None, 128)
    pm.sharded_finalise(cfg, mesh, None, None, None)
    pm.sharded_blame(cfg, mesh, None, None, None, None, None)
    cs = cfg.cs
    signmesh.sign_folded_sharded(
        "ristretto255",
        np.zeros((4, cs.scalar.limbs), np.uint32),
        np.zeros((4, cs.ncoords, cs.field.limbs), np.uint32),
        mesh,
    )
    assert len(keys) == len(MESH_BUILDERS) + 1
    return keys


@pytest.mark.parametrize("name", envknobs.PROGRAM_SHAPING)
def test_setting_a_shaping_knob_changes_every_key(name, clean_env, monkeypatch):
    assert envknobs.program_shape() == () and aot.knob_tier() == ""
    base_keys = _mesh_keys(monkeypatch)
    base_header = aot._header(("k",))

    monkeypatch.setenv(name, "1")
    assert envknobs.program_shape() == ((name, "1"),)
    assert aot.knob_tier() == f"{name}=1"
    assert aot._header(("k",)) != base_header
    keys = _mesh_keys(monkeypatch)
    assert all(k != b and k[-1] == ((name, "1"),) for k, b in zip(keys, base_keys))

    # empty is unset, as everywhere in envknobs
    monkeypatch.setenv(name, "")
    assert envknobs.program_shape() == () and aot.knob_tier() == ""
    assert aot._header(("k",)) == base_header
    assert _mesh_keys(monkeypatch) == base_keys


def test_snapshot_keeps_the_tuples_order(clean_env, monkeypatch):
    for i, name in enumerate(reversed(envknobs.PROGRAM_SHAPING)):
        monkeypatch.setenv(name, str(i))
    assert [k for k, _ in envknobs.program_shape()] == list(envknobs.PROGRAM_SHAPING)
    assert len(set(envknobs.PROGRAM_SHAPING)) == len(envknobs.PROGRAM_SHAPING)


def _scanned_files() -> list:
    files = []
    for rel in aot._TRACED_SOURCES + ("parallel",):
        path = PKG / rel
        files += sorted(path.glob("*.py")) if path.is_dir() else [path]
    return files


def test_every_knob_named_in_traced_sources_is_listed():
    """A ``"DKG_TPU_..."`` string literal in a file a stored or sharded
    program can trace is a knob read there: it is in PROGRAM_SHAPING, or
    in the short not-shaping set above with its reason."""
    seen = set()
    for path in _scanned_files():
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                if re.fullmatch(r"DKG_TPU_[A-Z0-9_]+", node.value):
                    seen.add(node.value)
    assert seen - set(envknobs.PROGRAM_SHAPING) - NOT_SHAPING == set()
    # and the other way: a listed knob that nothing traced reads is a
    # key that retraces for nothing (DIGEST is read in
    # crypto/device_hash.py, which the sharded engine's digest leg
    # traces and the store fingerprints since PR 46)
    assert set(envknobs.PROGRAM_SHAPING) - seen == set()
    assert NOT_SHAPING <= seen


@pytest.mark.parametrize("suffix", REMOVED)
def test_removed_knob_is_gone_from_the_package(suffix):
    name = "DKG_TPU_" + suffix
    hits = [
        str(p.relative_to(PKG))
        for p in sorted(PKG.rglob("*.py"))
        if re.search(rf"\b{name}\b", p.read_text())
    ]
    assert hits == []


@pytest.mark.parametrize("curve", sorted(gd.ALL_CURVES))
@pytest.mark.parametrize("on_tpu", [False, True])
def test_one_fixed_base_window_rule(curve, on_tpu, monkeypatch):
    """gd.fixed_base_table and precompute.base_table build the same
    window width on either backend: both read gd.default_fixed_window."""
    cs = gd.ALL_CURVES[curve]
    monkeypatch.setattr(fd, "_on_tpu", lambda: on_tpu)
    want = 16 if on_tpu else gd.FIXED_WINDOW
    assert gd.default_fixed_window() == want

    built = []

    def table(_cs, _key, window=gd.FIXED_WINDOW):
        built.append(window)
        return np.zeros((1,), np.uint32)

    monkeypatch.setattr(gd, "_fixed_table_np", table)
    monkeypatch.setattr(gd, "fixed_base_table_dev", table)
    gd.fixed_base_table(cs, gd._gen_host(cs))
    assert built == [want]

    # precompute: the host table is asked at the window (or its half,
    # composed on device), and the device table is cached under it
    monkeypatch.setattr(gp, "_TABLES", {})
    monkeypatch.setattr(gp, "host_table", table)
    monkeypatch.setattr(gd, "_compose_table_dev", lambda _cs, t, window: t)
    monkeypatch.setattr(gd, "affine_canon", lambda _cs, t: t)
    gp.generator_table(cs)
    assert [k[2] for k in gp._TABLES] == [want]
    assert built[1:] == [want if want <= 8 else want // 2]
