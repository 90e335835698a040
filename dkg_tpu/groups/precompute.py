"""Persistent fixed-base table precomputation (generator g / Pedersen h).

The deal phase is fixed-base-bound: every coefficient commitment is
g·a + h·b through window tables (groups/device.py fixed_base_mul), and
before this module each PROCESS rebuilt those tables from scratch —
host-side ladder work plus, on TPU, a device composition — even though
g and h never change for a given ceremony environment.  This module
makes the tables a durable artifact:

* in-process cache keyed ``(curve, base, window)`` — the second
  ceremony in a process pays zero table cost;
* disk persistence alongside the JAX compilation cache — the second
  PROCESS pays one validated ``np.load`` instead of a build.  Files are
  written atomically (temp + ``os.replace``) and carry a BLAKE2b digest
  over both the header (format version, curve, window, base key) and
  the table bytes; any mismatch, truncation, or unreadable file is
  treated as absent and the table is rebuilt — the cache is an
  optimisation, never a trust root.

Consumers: ``base_table`` (device table for any fixed base, the
persistent replacement for ``groups.device.fixed_base_table``) and
``comb_mul`` (fixed-base scalar-mul over those tables).  The table
layout is a fixed-window comb: entry ``T[w][d] = d·(2**c)^w·B``, so a
scalar k = Σ_w d_w·(2**c)^w is assembled with NW mixed adds and ZERO
doublings — all doubling work was hoisted into the precomputation.

``stats()`` exposes build/load counters and seconds so callers
(utils/tracing.py CeremonyTrace, bench.py's ``warm`` flag) can attribute
table-build cost vs steady-state cost.  The registry carries the same
split as ``fixed_base_table_seconds{curve=,source=}``, one observation
per table and source: ``disk`` (a validated load of the host table),
``build`` (the host table computed from scratch), ``compose`` (a width
> 8 composed on the device from the half-width host table, waited
for); a process-cache hit books nothing.

Concurrency: both caches are guarded by one process-wide build lock, so
N threads warming the same curve's tables (the multi-tenant service's
workers all start by asking for g/h) serialize into exactly ONE
build/load; the rest are ``proc_hits``.  Disk writes stay atomic
(temp + ``os.replace``) so concurrent *processes* can still race only
into identical, validly-digested files.
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.metrics import REGISTRY
from . import device as gd

_FORMAT_VERSION = 1

# in-process device-table cache: (curve, base_key, window) -> jax.Array
_TABLES: dict = {}
# in-process host-table cache (the persisted artifact): same key -> ndarray
_HOST: dict = {}

# Build-once discipline for concurrent warmers: N service workers (or
# party threads) asking for the same table must produce ONE build/load —
# without this, every thread that misses the dict races into its own
# multi-second comb build and the last writer wins.  One re-entrant lock
# (base_table -> host_table nests) is enough: builds are rare and
# cache hits only pay an uncontended acquire.
_BUILD_LOCK = threading.RLock()

_STATS = {
    "builds": 0,  # host tables computed from scratch
    "build_s": 0.0,
    "disk_loads": 0,  # host tables loaded (and validated) from disk
    "load_s": 0.0,
    "disk_rejects": 0,  # on-disk files that failed validation
    "proc_hits": 0,  # served from the in-process caches
}


#: One table's load, build or composition: from a few milliseconds (an
#: 8-bit host table from the disk) to tens of seconds (a 16-bit table's
#: first composition in a process, its program's compile included).
_TABLE_BUCKETS = (0.01, 0.1, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 60.0, 300.0)


def _book(cs: gd.CurveSpec, source: str, seconds: float) -> None:
    REGISTRY.observe(
        "fixed_base_table_seconds", seconds, _TABLE_BUCKETS, curve=cs.name, source=source
    )


def stats() -> dict:
    """Snapshot of the cache counters (copy — safe to diff)."""
    return dict(_STATS)


def reset(clear_disk: bool = False) -> None:
    """Drop the in-process caches and zero the counters (tests).  With
    ``clear_disk`` also remove this process's on-disk table files."""
    _TABLES.clear()
    _HOST.clear()
    for k in _STATS:
        _STATS[k] = 0 if isinstance(_STATS[k], int) else 0.0
    if clear_disk:
        d = cache_dir()
        if d.is_dir():
            for f in d.glob("*.npz"):
                try:
                    f.unlink()
                except OSError:
                    pass


def cache_dir() -> pathlib.Path:
    """Where table files live: ``DKG_TPU_TABLE_CACHE`` if set, else a
    ``dkg_tpu_fb_tables/`` directory alongside the JAX compilation
    cache (utils.compilecache — same lifecycle: wiping one wipes
    both)."""
    from ..utils import compilecache, envknobs

    env = envknobs.string("DKG_TPU_TABLE_CACHE", "fixed-base table cache directory")
    if env is not None:
        return pathlib.Path(env)
    return pathlib.Path(compilecache.cache_root()) / "dkg_tpu_fb_tables"


def _table_path(cs: gd.CurveSpec, key: tuple, window: int) -> pathlib.Path:
    kh = hashlib.blake2b(repr(key).encode(), digest_size=8).hexdigest()
    return cache_dir() / f"fb_v{_FORMAT_VERSION}_{cs.name}_w{window}_{kh}.npz"


def _digest(cs: gd.CurveSpec, key: tuple, window: int, table: np.ndarray) -> bytes:
    header = f"{_FORMAT_VERSION}|{cs.name}|{window}|{key!r}|{table.shape}|{table.dtype}"
    return hashlib.blake2b(header.encode() + table.tobytes(), digest_size=32).digest()


def _load_disk(cs: gd.CurveSpec, key: tuple, window: int) -> np.ndarray | None:
    """Validated load: any failure (missing, truncated, wrong shape,
    digest mismatch) returns None — the caller rebuilds."""
    path = _table_path(cs, key, window)
    try:
        with np.load(path, allow_pickle=False) as z:
            table = np.asarray(z["table"])
            digest = np.asarray(z["digest"]).tobytes()
    except Exception:
        if path.exists():
            _STATS["disk_rejects"] += 1
        return None
    expect = (
        gd._n_windows(cs, window),
        1 << window,
        cs.ncoords,
        cs.field.limbs,
    )
    if (
        table.shape != expect
        or table.dtype != np.uint32
        or digest != _digest(cs, key, window, table)
    ):
        _STATS["disk_rejects"] += 1
        return None
    return table


def _persist(cs: gd.CurveSpec, key: tuple, window: int, table: np.ndarray) -> None:
    """Atomic best-effort write (temp file + rename); an unwritable
    cache directory degrades to building per process, never an error."""
    path = _table_path(cs, key, window)
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        fd_, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        try:
            with os.fdopen(fd_, "wb") as fh:
                np.savez(
                    fh,
                    table=table,
                    digest=np.frombuffer(
                        _digest(cs, key, window, table), dtype=np.uint8
                    ),
                )
            os.replace(tmp, path)
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    except OSError:
        pass


def host_table(
    cs: gd.CurveSpec, key: tuple, window: int = gd.FIXED_WINDOW
) -> np.ndarray:
    """Host window table for a fixed base, through the persistent cache:
    process cache -> validated disk cache -> build (and persist).

    ``key`` is ``groups.device.base_key(cs, base)``.  The array layout
    is identical to ``groups.device._fixed_table_np`` (the builder it
    delegates to), so swapping call sites is bit-exact.
    """
    ck = (cs.name, key, window)
    with _BUILD_LOCK:
        hit = _HOST.get(ck)
        if hit is not None:
            _STATS["proc_hits"] += 1
            return hit
        t0 = time.perf_counter()
        table = _load_disk(cs, key, window)
        if table is not None:
            dt = time.perf_counter() - t0
            _STATS["disk_loads"] += 1
            _STATS["load_s"] += dt
            _book(cs, "disk", dt)
        else:
            t0 = time.perf_counter()
            # the undecorated builder: gd's lru_cache would double-count
            # memory and hide rebuilds from the counters
            table = gd._fixed_table_np.__wrapped__(cs, key, window)
            dt = time.perf_counter() - t0
            _STATS["builds"] += 1
            _STATS["build_s"] += dt
            _book(cs, "build", dt)
            _persist(cs, key, window, table)
        _HOST[ck] = table
        return table


def base_table(cs: gd.CurveSpec, base, window: int | None = None) -> jax.Array:
    """Device window table for a fixed base, persistently cached.

    The drop-in replacement for ``groups.device.fixed_base_table`` for
    protocol code (dkg/ — enforced by lint DKG002): same layout, same
    backend-matched default width, but the host-side work goes through
    :func:`host_table` (disk + process cache) and the resulting device
    array is cached per ``(curve, base, window)`` for the process.
    Widths > 8 are composed on device from the persisted half-width
    host table (one batched add + one batched inversion); the
    composition is waited for here, so that its seconds can be booked
    (``fixed_base_table_seconds{source="compose"}``) apart from the
    host table's.
    """
    if window is None:
        window = gd.default_fixed_window()
    key = gd.base_key(cs, base)
    ck = (cs.name, key, window)
    with _BUILD_LOCK:
        hit = _TABLES.get(ck)
        if hit is not None:
            _STATS["proc_hits"] += 1
            return hit
        if window > 8:
            half = window // 2
            if window % 2 or half > 8 or 16 % window:
                raise ValueError(f"unsupported fixed-base window width {window}")
            t_half = jnp.asarray(host_table(cs, key, half))
            t0 = time.perf_counter()
            table = jax.block_until_ready(
                gd.affine_canon(cs, gd._compose_table_dev(cs, t_half, window))
            )
            _book(cs, "compose", time.perf_counter() - t0)
        else:
            table = jnp.asarray(host_table(cs, key, window))
        _TABLES[ck] = table
        return table


def generator_table(cs: gd.CurveSpec, window: int | None = None) -> jax.Array:
    """:func:`base_table` for the curve generator g."""
    return base_table(cs, gd._gen_host(cs), window)


def comb_mul(cs: gd.CurveSpec, table: jax.Array, k: jax.Array) -> jax.Array:
    """Batched fixed-base k·B over a precomputed comb table.

    The table IS the comb: entry ``T[w][d] = d·(2**c)^w·B`` holds every
    tooth's multiple, so evaluation is NW gathered mixed adds with no
    doublings (groups.device._fixed_base_mul_core does the masked-madd
    scan).  Window width is encoded in the table's entry count.
    """
    return gd.fixed_base_mul(cs, table, k)
