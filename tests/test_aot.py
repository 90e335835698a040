"""AOT executable store contract (service.aot).

Tiny single-op programs stand in for the engine's executables: the
store's job — key → validated disk artifact → resident callable — is
identical regardless of program size, and these compile in
milliseconds so the corruption/skew matrix stays in the default tier.
The real-engine oracle (AOT masters bit-identical to the jit path) is
exercised end-to-end by scripts/aot_build.py + scripts/fleet_bench.py.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.service import aot


@pytest.fixture()
def store(tmp_path, monkeypatch):
    """Point the store at a private directory and forget process state."""
    monkeypatch.setenv("DKG_TPU_AOT_DIR", str(tmp_path))
    aot.reset()
    yield tmp_path
    aot.reset()


def _build_double():
    spec = jax.ShapeDtypeStruct((4,), jnp.uint32)
    return jax.jit(lambda x: x * 2).lower(spec).compile()


def _build_add1():
    spec = jax.ShapeDtypeStruct((4,), jnp.uint32)
    return jax.jit(lambda x: x + 1).lower(spec).compile()


_X = np.arange(4, dtype=np.uint32)

KEY = ("deal", "testcurve", 8, 2, 1, 0, (((4,), "uint32"),))
KEY2 = ("verify", "testcurve", 8, 2, 1, 64, (((4,), "uint32"),))


def _must_not_build():
    raise AssertionError("store built when it should have loaded")


def test_disabled_without_knob(monkeypatch):
    monkeypatch.delenv("DKG_TPU_AOT_DIR", raising=False)
    assert not aot.enabled()


def test_build_persist_and_disk_roundtrip(store):
    fn = aot.get_or_build(KEY, _build_double)
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X * 2)
    s = aot.stats()
    assert s["builds"] == 1 and s["resident"] == 1
    assert any(f.startswith("aot_v") for f in os.listdir(store))

    # same process: cache hit, the build thunk must not run
    fn2 = aot.get_or_build(KEY, _must_not_build)
    assert fn2 is fn
    assert aot.stats()["proc_hits"] == 1

    # "fresh process": forget in-memory state, keep disk — the artifact
    # must load and produce the same answer without rebuilding
    aot.reset()
    fn3 = aot.get_or_build(KEY, _must_not_build)
    np.testing.assert_array_equal(np.asarray(fn3(_X)), _X * 2)
    s = aot.stats()
    assert s["builds"] == 0 and s["disk_loads"] == 1 and s["disk_rejects"] == 0


@pytest.mark.parametrize("damage", ["truncate", "bitflip", "garbage"])
def test_corrupt_artifact_silently_rebuilds(store, damage):
    aot.get_or_build(KEY, _build_double)
    (path,) = [store / f for f in os.listdir(store) if f.startswith("aot_v")]
    raw = bytearray(path.read_bytes())
    if damage == "truncate":
        raw = raw[: len(raw) // 2]
    elif damage == "bitflip":
        raw[len(raw) // 2] ^= 0x40
    else:
        raw = b"not an npz at all"
    path.write_bytes(bytes(raw))

    aot.reset()
    builds = []
    fn = aot.get_or_build(KEY, lambda: builds.append(1) or _build_double())
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X * 2)
    s = aot.stats()
    assert builds == [1], "damaged artifact must trigger a rebuild"
    assert s["disk_rejects"] >= 1 and s["disk_loads"] == 0

    # the rebuild re-persisted a valid artifact: next process loads clean
    aot.reset()
    aot.get_or_build(KEY, _must_not_build)
    assert aot.stats()["disk_loads"] == 1


def test_version_skew_rebuilds_never_serves_stale(store, monkeypatch):
    aot.get_or_build(KEY, _build_double)
    aot.reset()
    # a jax upgrade changes the digest header: the old artifact must be
    # rejected and rebuilt, never deserialized into the new runtime
    monkeypatch.setattr(jax, "__version__", "999.0.0")
    builds = []
    aot.get_or_build(KEY, lambda: builds.append(1) or _build_double())
    s = aot.stats()
    assert builds == [1] and s["disk_rejects"] == 1 and s["disk_loads"] == 0


def test_knob_tier_skew_rebuilds(store, monkeypatch):
    aot.get_or_build(KEY, _build_double)
    aot.reset()
    # a program-shaping knob changed: same shapes, different traced
    # program — the stale executable must not serve
    monkeypatch.setenv("DKG_TPU_MUL", "schoolbook")
    builds = []
    aot.get_or_build(KEY, lambda: builds.append(1) or _build_double())
    assert builds == [1] and aot.stats()["disk_rejects"] == 1


def test_stale_program_for_other_key_rejected(store):
    """An artifact renamed onto another key's path (operator error,
    sync gone wrong) must fail the stored-key check, not serve the
    wrong program."""
    aot.get_or_build(KEY, _build_double)
    (path,) = [store / f for f in os.listdir(store) if f.startswith("aot_v")]
    os.rename(path, store / os.path.basename(aot._path(KEY2)))

    aot.reset()
    fn = aot.get_or_build(KEY2, _build_add1)
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X + 1)
    s = aot.stats()
    assert s["builds"] == 1 and s["disk_rejects"] == 1


def test_preload_and_has_prefix(store):
    aot.get_or_build(KEY, _build_double)
    aot.get_or_build(KEY2, _build_add1)
    # plant one damaged neighbour: preload must skip it and keep going
    (store / "aot_v1_bogus_0000000000000000.npz").write_bytes(b"torn")

    aot.reset()
    assert aot.preload() == 2
    s = aot.stats()
    assert s["disk_loads"] == 2 and s["disk_rejects"] == 1 and s["builds"] == 0
    assert aot.has_prefix(("deal", "testcurve", 8, 2, 1))
    assert aot.has_prefix(("verify",))
    assert not aot.has_prefix(("deal", "testcurve", 16))
    # idempotent: a second call is a no-op, not a rescan
    assert aot.preload() == 2
    assert aot.stats()["disk_loads"] == 2

    # the preloaded executables answer without building
    fn = aot.get_or_build(KEY, _must_not_build)
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X * 2)


def test_targeted_preload_and_disk_presence(store):
    """The warmup path: load only the hot prefix eagerly, see the rest
    on disk without deserializing it."""
    aot.get_or_build(KEY, _build_double)
    aot.get_or_build(KEY2, _build_add1)

    aot.reset()
    assert aot.preload_prefixes([("deal", "testcurve", 8, 2, 1)]) == 1
    s = aot.stats()
    assert s["resident"] == 1 and s["disk_loads"] == 1
    assert aot.has_prefix(("deal",))
    # the verify artifact is on disk but not resident: warmup can skip
    # its throwaway convoy and let dispatch load it lazily
    assert not aot.has_prefix(("verify",))
    assert aot.disk_has_prefix(("verify", "testcurve", 8, 2))
    assert not aot.disk_has_prefix(("verify", "othercurve"))
    # lazy dispatch-time load, no rebuild
    fn = aot.get_or_build(KEY2, _must_not_build)
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X + 1)
    # a key persisted after the scan is still discovered (this
    # process's own writes update the index)
    key3 = ("master", "testcurve", 8, 2, 1, 0, (((4,), "uint32"),))
    aot.get_or_build(key3, _build_double)
    assert aot.disk_has_prefix(("master",))


def test_serialized_blob_roundtrip_bit_identical(store):
    """The serialize/deserialize pair itself: payload pickles whole and
    the loaded executable answers exactly like the original — on the
    one device it was compiled for, not every local device."""
    compiled = _build_double()
    fn = aot.deserialize(aot.serialize(compiled))
    np.testing.assert_array_equal(np.asarray(fn(_X)), np.asarray(compiled(_X)))


def test_valid_artifact_that_fails_to_load_is_an_error(store, monkeypatch, caplog):
    """A digest-valid artifact whose executable will not load is counted
    in errors / aot_errors_total and logged with the exception text —
    not filed under disk_rejects as if it were a stale file."""
    aot.get_or_build(KEY, _build_double)
    aot.reset()

    def _boom(blob):
        raise RuntimeError("executable refused to load")

    monkeypatch.setattr(aot, "deserialize", _boom)
    before = aot.REGISTRY.snapshot()["counters"].get("aot_errors_total", 0)
    with caplog.at_level("ERROR", logger=aot.__name__):
        fn = aot.get_or_build(KEY, _build_double)
    np.testing.assert_array_equal(np.asarray(fn(_X)), _X * 2)
    s = aot.stats()
    assert s["errors"] == 1 and s["disk_rejects"] == 0 and s["builds"] == 1
    assert aot.REGISTRY.snapshot()["counters"]["aot_errors_total"] == before + 1
    assert "executable refused to load" in caplog.text


def test_spec_sig_pins_shapes_and_dtypes():
    sig = aot.spec_sig((np.zeros((2, 3), np.uint32), {"a": np.zeros(4, np.float32)}))
    assert sig == (((2, 3), "uint32"), ((4,), "float32"))


def test_engine_dispatch_falls_back_on_store_error(store, monkeypatch):
    """A store that throws must degrade to the jit fallback, counting
    an error — never surface to the caller."""
    from dkg_tpu.service import engine

    def _boom(key, build):
        raise RuntimeError("store exploded")

    monkeypatch.setattr(aot, "get_or_build", _boom)
    out = engine._aot_dispatch(
        ("deal", "c", 8, 2, 1, 0),
        (np.arange(4, dtype=np.uint32),),
        lambda specs: (_ for _ in ()).throw(AssertionError("must not lower")),
        lambda: "fallback-answer",
    )
    assert out == "fallback-answer"
    assert aot.stats()["errors"] == 1
