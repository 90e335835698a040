"""The store binds the source of what it holds (service.aot.source_fingerprint)
and books a build by stage.  Tiny single-op programs, as in tests/test_aot.py."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dkg_tpu.service import aot
from dkg_tpu.utils.metrics import REGISTRY

KEY = ("deal", "testcurve", 8, 2, 1, 0, (((4,), "uint32"),))
_X = np.arange(4, dtype=np.uint32)
_SPEC = jax.ShapeDtypeStruct((4,), jnp.uint32)


@pytest.fixture()
def store(tmp_path, monkeypatch):
    monkeypatch.setenv("DKG_TPU_AOT_DIR", str(tmp_path))
    aot.reset()
    yield tmp_path
    aot.reset()


def _traced_double():
    return jax.jit(lambda x: x * 2).trace(_SPEC)


def _must_not_build():
    raise AssertionError("store built when it should have loaded")


def _stage_counts(kind):
    hist = REGISTRY.snapshot()["histograms"]
    return {
        stage: hist.get(f'aot_build_stage_seconds{{curve="testcurve",kind="{kind}",stage="{stage}"}}', {"count": 0})["count"]
        for stage in ("trace", "lower", "compile", "serialize")
    }


def test_fingerprint_is_of_the_traced_sources_and_in_every_header():
    fp = aot.source_fingerprint()
    assert len(fp) == 32 and int(fp, 16) >= 0
    assert aot.source_fingerprint() is fp  # once a process: nothing is read or lowered again
    assert f"|{fp}|".encode() in aot._header(KEY)
    root = os.path.dirname(os.path.dirname(os.path.abspath(aot.__file__)))
    for rel in aot._TRACED_SOURCES:
        assert os.path.exists(os.path.join(root, rel)), rel
    # what the four round programs and their kernels live in is covered
    assert {"dkg/ceremony.py", "ops", "groups", "fields", "crypto/device_hash.py"} <= set(aot._TRACED_SOURCES)


def test_every_module_the_mesh_digest_traces_is_fingerprinted():
    """``mesh_digest_rows`` is a stored program whose body is
    ``dealer_rows_traced``: every module of the package that runs while
    that is traced shapes the program, so its text has to be under
    ``_TRACED_SOURCES`` or a store that outlives a checkout serves the
    other checkout's digest (PR 46: ``crypto/device_hash.py`` was not).
    Nothing is compiled: the body is traced to a jaxpr with the
    interpreter's profile hook on."""
    import sys

    from dkg_tpu.dkg import ceremony as ce

    root = os.path.dirname(os.path.dirname(os.path.abspath(aot.__file__)))
    seen = set()

    def hook(frame, event, arg):
        if event == "call":
            path = frame.f_code.co_filename
            if path.startswith(root + os.sep):
                seen.add(os.path.relpath(path, root).replace(os.sep, "/"))

    # shapes no other test traces: a tree or a canon already in this process's
    # jit caches would run no module at all (the first assert below says so)
    cfg = ce.CeremonyConfig("secp256k1", 7, 2)
    point = jax.ShapeDtypeStruct((7, 3, 3, 16), jnp.uint32)
    rows = jax.ShapeDtypeStruct((7, 7, 16), jnp.uint32)
    sys.setprofile(hook)
    try:
        jax.make_jaxpr(lambda *x: ce.dealer_rows_traced(cfg, *x))(point, point, rows, rows)
    finally:
        sys.setprofile(None)
    assert {"dkg/ceremony.py", "crypto/device_hash.py", "groups/device.py", "fields/device.py"} <= seen, seen
    # what books, logs or reads a switch shapes no program: the switches a
    # tracer reads are the store's key by another road
    # (envknobs.program_shape), and JAX's monitoring events reach the
    # listeners an earlier test of the process installed (runtimeobs, obslog)
    shapes_nothing = {
        f"utils/{name}.py" for name in ("metrics", "envknobs", "runtimeobs", "obslog", "tracing")
    }
    covered = tuple(aot._TRACED_SOURCES)
    loose = {
        m for m in seen - shapes_nothing
        if not any(m == src or m.startswith(src + "/") for src in covered)
    }
    assert not loose, loose


def test_an_executable_baked_from_other_source_is_rebuilt_never_served(store, monkeypatch):
    fn = aot.get_or_build(KEY, _traced_double)
    assert np.array_equal(np.asarray(fn(_X)), _X * 2)
    assert aot.stats()["builds"] == 1
    ours = set(os.listdir(store))
    assert len(ours) == 1

    # another checkout (a re-formed program): same key, other source
    monkeypatch.setattr(aot, "source_fingerprint", lambda: "f" * 32)
    aot.reset()
    assert not aot.disk_has_prefix(("deal", "testcurve"))  # the other checkout's artifact is not ours
    built = []
    fn2 = aot.get_or_build(KEY, lambda: built.append(1) or jax.jit(lambda x: x * 3).trace(_SPEC))
    assert built == [1] and aot.stats()["builds"] == 1 and aot.stats()["disk_loads"] == 0
    assert np.array_equal(np.asarray(fn2(_X)), _X * 3)  # never the stale program
    assert ours < set(os.listdir(store)) and len(os.listdir(store)) == 2  # side by side, nothing baked away

    # the artifact renamed onto ours is still refused: the digest binds the source
    theirs = (set(os.listdir(store)) - ours).pop()
    monkeypatch.undo()
    monkeypatch.setenv("DKG_TPU_AOT_DIR", str(store))
    os.replace(store / theirs, store / ours.copy().pop())
    aot.reset()
    built.clear()
    fn3 = aot.get_or_build(KEY, lambda: built.append(1) or _traced_double())
    assert built == [1] and aot.stats()["disk_rejects"] >= 1
    assert np.array_equal(np.asarray(fn3(_X)), _X * 2)

    # and the first checkout, back, loads its own without a build
    aot.reset()
    fn4 = aot.get_or_build(KEY, _must_not_build)
    assert aot.stats()["disk_loads"] == 1 and np.array_equal(np.asarray(fn4(_X)), _X * 2)


@pytest.mark.parametrize(
    "thunk_stage, booked",
    [
        ("trace", {"trace": 1, "lower": 1, "compile": 1, "serialize": 1}),
        ("lower", {"trace": 0, "lower": 1, "compile": 1, "serialize": 1}),
        ("compile", {"trace": 0, "lower": 0, "compile": 1, "serialize": 1}),
    ],
)
def test_a_build_is_booked_by_stage(store, thunk_stage, booked):
    kind = f"stagetest_{thunk_stage}"
    key = (kind,) + KEY[1:]

    def build():
        traced = _traced_double()
        if thunk_stage == "trace":
            return traced
        lowered = traced.lower()
        return lowered if thunk_stage == "lower" else lowered.compile()

    fn = aot.get_or_build(key, build)
    assert np.array_equal(np.asarray(fn(_X)), _X * 2)
    assert _stage_counts(kind) == booked
    aot.reset()
    aot.get_or_build(key, _must_not_build)  # a load books no build stage
    assert _stage_counts(kind) == booked


def test_the_digest_leg_books_its_first_call_once_a_shape():
    from dkg_tpu.dkg import ceremony as ce

    def count():
        hist = REGISTRY.snapshot()["histograms"]
        return sum(v["count"] for k, v in hist.items() if k.startswith("digest_leg_first_call_seconds"))

    cfg = ce.CeremonyConfig("secp256k1", 8, 2)
    ce._DIGEST_LEG_SEEN.discard(("secp256k1", "8x3"))
    ident = np.zeros((8, 3, 3, 16), np.uint32)
    ident[..., 1, 0] = 1
    zeros = np.zeros((8, 8, 16), np.uint32)
    before = count()
    host = ce._dealer_rows_device(cfg, ident, ident, zeros, zeros, dispatch="host")
    assert count() == before  # the host leg traces nothing
    dev = ce._dealer_rows_device(cfg, ident, ident, zeros, zeros, dispatch="device")
    assert count() == before + 1
    ce._dealer_rows_device(cfg, ident, ident, zeros, zeros, dispatch="device")
    assert count() == before + 1
    for h, d in zip(host, dev):
        assert np.array_equal(np.asarray(h), np.asarray(d))
