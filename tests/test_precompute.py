"""Persistent fixed-base table cache (dkg_tpu.groups.precompute).

Covers the cache's contract from docs/perf.md: tables round-trip the
disk byte-identically, ANY corruption is detected and silently repaired
by a rebuild (the cache is an optimisation, never a trust root), and a
ceremony fed cached tables produces a bit-identical master key to one
that built them fresh — with the second ceremony paying zero builds
(the amortisation the cache exists for).
"""

from __future__ import annotations

import random

import numpy as np
import pytest

from dkg_tpu.groups import device as gd
from dkg_tpu.groups import precompute as gp


@pytest.fixture()
def table_cache(tmp_path, monkeypatch):
    """Fresh empty disk cache + zeroed process cache, torn down after."""
    monkeypatch.setenv("DKG_TPU_TABLE_CACHE", str(tmp_path))
    gp.reset()
    yield tmp_path
    gp.reset()


CS = gd.ALL_CURVES["secp256k1"]


def _gen_key():
    return gd.base_key(CS, gd._gen_host(CS))


def test_disk_round_trip_is_byte_identical(table_cache):
    # window 4 keeps the host build cheap; the layout/digest logic is
    # window-independent
    fresh = gp.host_table(CS, _gen_key(), window=4)
    assert gp.stats()["builds"] == 1
    files = list(table_cache.glob("*.npz"))
    assert len(files) == 1

    gp.reset()  # drop process cache, keep disk
    loaded = gp.host_table(CS, _gen_key(), window=4)
    st = gp.stats()
    assert st["disk_loads"] == 1 and st["builds"] == 0
    assert loaded.dtype == np.uint32
    np.testing.assert_array_equal(np.asarray(fresh), np.asarray(loaded))

    # process cache serves the repeat without touching disk
    again = gp.host_table(CS, _gen_key(), window=4)
    assert gp.stats()["proc_hits"] == 1
    assert again is loaded


@pytest.mark.parametrize("damage", ["truncate", "bitflip"])
def test_corrupt_cache_file_is_rejected_and_rebuilt(table_cache, damage):
    fresh = np.asarray(gp.host_table(CS, _gen_key(), window=4))
    [path] = table_cache.glob("*.npz")
    raw = path.read_bytes()
    if damage == "truncate":
        path.write_bytes(raw[: len(raw) // 2])
    else:
        flipped = bytearray(raw)
        flipped[len(raw) // 2] ^= 0x40
        path.write_bytes(bytes(flipped))

    gp.reset()
    rebuilt = np.asarray(gp.host_table(CS, _gen_key(), window=4))
    st = gp.stats()
    assert st["disk_rejects"] >= 1, "corruption must be detected, not trusted"
    assert st["builds"] == 1, "rejected file must trigger a rebuild"
    np.testing.assert_array_equal(fresh, rebuilt)
    # and the rebuild re-persisted a GOOD file
    gp.reset()
    reloaded = np.asarray(gp.host_table(CS, _gen_key(), window=4))
    assert gp.stats()["disk_loads"] == 1
    np.testing.assert_array_equal(fresh, reloaded)


def test_base_table_matches_device_builder(table_cache):
    """precompute.base_table is a drop-in for gd.fixed_base_table:
    limb-for-limb the same array (same builder, different cache)."""
    via_cache = np.asarray(gp.base_table(CS, gd._gen_host(CS), window=4))
    direct = gd._fixed_table_np.__wrapped__(CS, _gen_key(), 4)
    np.testing.assert_array_equal(via_cache, direct)


def _table_seconds():
    from dkg_tpu.utils.metrics import REGISTRY

    hist = REGISTRY.snapshot()["histograms"]
    return {
        k.partition("source=")[2].strip('"}'): v["count"]
        for k, v in hist.items()
        if k.startswith("fixed_base_table_seconds{") and 'curve="secp256k1"' in k
    }


def test_table_seconds_are_booked_once_a_table_by_source(table_cache, monkeypatch):
    """``fixed_base_table_seconds{curve,source}``: a host table books
    ``build`` or ``disk`` where it is made, a 16-bit table ``compose`` on
    top of its half-width host table's, a process-cache hit nothing."""
    import jax.numpy as jnp

    before = _table_seconds()

    def delta():
        return {k: v - before.get(k, 0) for k, v in _table_seconds().items() if v != before.get(k, 0)}

    gp.host_table(CS, _gen_key(), window=4)
    assert delta() == {"build": 1}
    gp.host_table(CS, _gen_key(), window=4)  # process cache
    assert delta() == {"build": 1}
    gp.reset()  # keep the disk
    gp.base_table(CS, gd._gen_host(CS), window=4)
    gp.base_table(CS, gd._gen_host(CS), window=4)  # process cache
    assert delta() == {"build": 1, "disk": 1}
    # the composition itself is a TPU-scale job (65536 entries a window):
    # stand-ins here, the booking around them is what is held
    monkeypatch.setattr(gd, "_compose_table_dev", lambda cs, t_half, window: t_half[:, :1])
    monkeypatch.setattr(gd, "affine_canon", lambda cs, x: x + jnp.uint32(0))
    gp.base_table(CS, gd._gen_host(CS), window=16)
    gp.base_table(CS, gd._gen_host(CS), window=16)  # process cache
    assert delta() == {"build": 2, "disk": 1, "compose": 1}


def test_concurrent_warmers_build_exactly_once(table_cache):
    """N threads racing to warm the SAME table (the multi-tenant
    service's workers all ask for g/h at startup) serialize into exactly
    one build; everyone gets the same array object."""
    import threading

    n_threads = 8
    barrier = threading.Barrier(n_threads)
    results: list = [None] * n_threads
    errors: list = []

    def warm(i):
        try:
            barrier.wait(timeout=10)
            results[i] = gp.host_table(CS, _gen_key(), window=4)
        except Exception as exc:  # noqa: BLE001 — surfaced below
            errors.append(exc)

    threads = [threading.Thread(target=warm, args=(i,)) for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors
    st = gp.stats()
    assert st["builds"] == 1, f"racing warmers built {st['builds']} tables"
    assert st["disk_loads"] == 0
    assert st["proc_hits"] == n_threads - 1
    first = results[0]
    assert first is not None
    assert all(r is first for r in results), "all threads must share one table"
    # and the winning build produced a valid, persisted table
    np.testing.assert_array_equal(
        np.asarray(first), gd._fixed_table_np.__wrapped__(CS, _gen_key(), 4)
    )


@pytest.mark.slow
def test_ceremony_master_key_identical_cached_vs_fresh(table_cache):
    """Three full secp256k1 engine runs (fresh build, warm process
    cache, disk reload) — ~2 min of compile on the 1-core box, so it
    rides the slow tier; the cache plumbing itself is covered at the
    table level by the default-tier tests above."""
    from dkg_tpu.dkg import ceremony as ce

    def run_ceremony():
        c = ce.BatchedCeremony("secp256k1", 6, 2, b"precompute-test", random.Random(42))
        out = c.run(rho_bits=32)
        return np.asarray(out["master"]), c.table_stats

    master_fresh, stats_fresh = run_ceremony()
    assert stats_fresh["builds"] >= 1, "first ceremony builds its tables"

    # same process, warm cache: zero builds, zero disk loads
    master_warm, stats_warm = run_ceremony()
    assert stats_warm["builds"] == 0 and stats_warm["disk_loads"] == 0
    assert stats_warm["proc_hits"] >= 2  # g and h both served from memory
    np.testing.assert_array_equal(master_fresh, master_warm)

    # "new process": process cache gone, disk survives — tables load,
    # nothing rebuilds, master key stays bit-identical
    gp.reset()
    master_disk, stats_disk = run_ceremony()
    assert stats_disk["builds"] == 0 and stats_disk["disk_loads"] >= 1
    np.testing.assert_array_equal(master_fresh, master_disk)
