"""AOT-serialized executable store: kill the per-process cold start.

A fresh serving process pays minutes of XLA compiles before its first
ceremony (FLEET_r01: 222.6s of warmup) even though every hot program is
static per (curve, bucket shape, convoy width, sign rung).  This module
persists the *compiled executables themselves* — lowered + compiled once
via ``jax.jit(...).lower(specs).compile()``, serialized with
:mod:`jax.experimental.serialize_executable` — beside the fixed-base
table cache, exactly on :mod:`dkg_tpu.groups.precompute`'s store
contract:

* process-level cache first (RLock-guarded dict), then a validated disk
  load, then build-and-persist;
* atomic writes (``mkstemp`` + ``os.replace``) so concurrent worker
  processes never observe a torn file;
* every artifact carries a BLAKE2b digest over a header binding the
  format version, jax/jaxlib versions, backend, knob tier, the full
  program key and :func:`source_fingerprint` (a hash of the text of the
  package modules a stored program can trace) — corruption, truncation,
  version skew or an executable baked from other source all fail the
  digest check and fall through to a silent rebuild (counted in
  :func:`stats`), never a crash and never a stale program.

The store is OFF unless ``DKG_TPU_AOT_DIR`` is set (the engine then
dispatches through its jitted twins exactly as before): XLA:CPU's
*compilation-cache* writer has corrupted entries on some images
(tests/conftest.py), so opting into executable persistence is an
explicit deployment decision.  ``serialize_executable`` takes a
different path (PjRt executable serialize + pickle) and round-trips this
package's large CPU executables bit-identically, but the loaded blob is
a pickle: the digest check guards *integrity*, not *trust* — point
``DKG_TPU_AOT_DIR`` only at a directory you would also trust as a JAX
compilation cache.

Key shape: ``(kind, curve, n, t, width, rho_bits, specsig)`` for
ceremony programs, ``(kind, curve, n, t, rho_bits, mesh shape, device
kind, device ids, specsig)`` for the ``shard_map`` programs of a
sharded bucket (``service/engine.py`` ``stored_mesh_program``; an executable
over several devices loads back onto the same ones),
``("sign_folded", curve, rung, specsig)`` for the
steady sign lane's folded ladder rungs — ``specsig`` pins every operand
shape/dtype (tables included, so a fixed-base window change keys new
artifacts).  :func:`preload` deserializes every valid artifact in the
store into the process cache so a fresh worker warms in seconds;
:func:`has_prefix` lets :meth:`WarmRuntime.warmup
<dkg_tpu.service.engine.WarmRuntime.warmup>` skip its throwaway convoy
when a bucket's programs are already resident.
"""

from __future__ import annotations

import ast
import contextlib
import functools
import hashlib
import logging
import os
import pickle
import tempfile
import threading
import time

import jax
import numpy as np
from jax.experimental import serialize_executable as _se

from ..utils import compilecache, envknobs
from ..utils.metrics import REGISTRY

#: Bump when the artifact layout changes; old files fail the digest
#: check and silently rebuild.
_FORMAT_VERSION = 3

#: What a stored program can trace, relative to the package: the round
#: programs and their stacked twins, and everything under them down to
#: the kernels.  :func:`source_fingerprint` hashes the text of these.
_TRACED_SOURCES = (
    "dkg/ceremony.py",
    "service/engine.py",
    "parallel/mesh.py",
    "utils/scanchunk.py",
    "crypto/device_hash.py",
    "fields",
    "groups",
    "ops",
    "poly",
)

#: Build-stage buckets: one program's trace or compile runs from
#: milliseconds to many minutes (a (1024,341) verify on a serving host).
_BUILD_BUCKETS = (0.1, 1.0, 5.0, 15.0, 30.0, 60.0, 120.0, 300.0, 600.0, 1200.0)

_LOCK = threading.RLock()
_LOG = logging.getLogger(__name__)
#: Per-key build/load locks: the global lock only guards the maps, so a
#: minutes-long XLA compile for one program never stalls an unrelated
#: key's lookup (e.g. the steady sign lane behind a ceremony build).
_KEY_LOCKS: dict[tuple, threading.Lock] = {}
_PROC: dict[tuple, object] = {}
_STATS = {
    "builds": 0,
    "build_s": 0.0,
    "disk_loads": 0,
    "load_s": 0.0,
    "disk_rejects": 0,
    "proc_hits": 0,
    "errors": 0,
}
_PRELOADED = False
#: Lazy {key: path} disk index (``_scan_disk``); None until first scan.
_DISK: dict | None = None


def enabled() -> bool:
    """True when the store is active (``DKG_TPU_AOT_DIR`` set)."""
    return envknobs.string("DKG_TPU_AOT_DIR", "AOT executable store directory") is not None


def cache_dir() -> str:
    """The artifact directory: ``DKG_TPU_AOT_DIR``, else beside the JAX
    compilation cache (utils.compilecache — mirrors precompute.cache_dir
    so the two stores land together)."""
    override = envknobs.string("DKG_TPU_AOT_DIR", "AOT executable store directory")
    if override:
        return override
    return os.path.join(compilecache.cache_root(), "dkg_tpu_aot_store")


def knob_tier() -> str:
    """Canonical ``k=v`` string of every set program-shaping knob
    (``envknobs.program_shape``): two processes with different tiers
    must never serve each other's executables, so it is bound into
    every artifact digest."""
    return ",".join(f"{k}={v}" for k, v in envknobs.program_shape())


def spec_sig(args: tuple) -> tuple:
    """Shape/dtype signature of a tuple of (pytree) operands — part of
    every key, so executables are only ever served to calls with the
    exact operand layout they were compiled for."""
    out = []
    for a in args:
        for leaf in jax.tree_util.tree_leaves(a):
            out.append((tuple(np.shape(leaf)), str(leaf.dtype)))
    return tuple(out)


@functools.lru_cache(maxsize=1)
def source_fingerprint() -> str:
    """Hex BLAKE2b over the text of every module a stored program can
    trace (:data:`_TRACED_SOURCES`), names included.  Bound into every
    artifact's digest, so a checkout whose programs were re-formed never
    serves, or is measured with, another checkout's executables where
    the store outlives it.  Reads a megabyte of source once a process;
    nothing is lowered to learn it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    files = []
    for rel in _TRACED_SOURCES:
        path = os.path.join(root, rel)
        if os.path.isdir(path):
            files += [
                os.path.join(path, name)
                for name in os.listdir(path)
                if name.endswith(".py")
            ]
        else:
            files.append(path)
    h = hashlib.blake2b(digest_size=16)
    for path in sorted(files):
        h.update(os.path.relpath(path, root).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read() + b"\0")
    return h.hexdigest()


def _header(key: tuple) -> bytes:
    import jaxlib

    return (
        f"aot|{_FORMAT_VERSION}|{jax.__version__}|{jaxlib.__version__}|"
        f"{jax.default_backend()}|{knob_tier()}|{source_fingerprint()}|{key!r}"
    ).encode()


def _digest(header: bytes, blob: bytes) -> bytes:
    h = hashlib.blake2b(digest_size=32)
    h.update(header)
    h.update(blob)
    return h.digest()


def _path(key: tuple) -> str:
    # the source is in the name too, so that two checkouts sharing one
    # store keep their artifacts side by side and never re-bake each
    # other's away
    tag = hashlib.blake2b(
        f"{source_fingerprint()}|{key!r}".encode(), digest_size=8
    ).hexdigest()
    return os.path.join(cache_dir(), f"aot_v{_FORMAT_VERSION}_{key[0]}_{tag}.npz")


def serialize(compiled) -> bytes:
    """One compiled program as store payload bytes: the ids of the
    device(s) it was compiled for, then jax's serialized executable."""
    dev_ids = [d.id for d in compiled.runtime_executable().local_devices()]
    return pickle.dumps((dev_ids, *_se.serialize(compiled)), protocol=4)


def deserialize(blob: bytes):
    """Load :func:`serialize`'s payload back onto the SAME device(s).
    ``deserialize_and_load``'s own default is every local device, which
    a one-device executable cannot run on."""
    dev_ids, *payload = pickle.loads(blob)
    by_id = {d.id: d for d in jax.devices()}
    return _se.deserialize_and_load(
        *payload, execution_devices=[by_id[i] for i in dev_ids]
    )


def _load_blob(path: str, key: tuple):
    """Deserialize one artifact.  Missing, torn, digest-mismatched or
    version-skewed files are cache misses (None, ``disk_rejects``); an
    artifact that passes its digest and then fails to LOAD is an error
    (None too — the caller rebuilds — but counted and logged)."""
    t0 = time.perf_counter()
    try:
        with np.load(path, allow_pickle=False) as z:
            blob = z["blob"].tobytes()
            digest = z["digest"].tobytes()
            stored_key = z["key"].tobytes().decode()
        if stored_key != repr(key):
            raise ValueError("key mismatch")
        if digest != _digest(_header(key), blob):
            raise ValueError("digest mismatch")
    except FileNotFoundError:
        return None
    except Exception:
        with _LOCK:  # may run outside the global lock (get_or_build)
            _STATS["disk_rejects"] += 1
        REGISTRY.inc("aot_disk_rejects_total")
        return None
    try:
        fn = deserialize(blob)
    except Exception as exc:
        note_error(exc, f"load {path}")
        return None
    dt = time.perf_counter() - t0
    with _LOCK:
        _STATS["disk_loads"] += 1
        _STATS["load_s"] += dt
    REGISTRY.inc("aot_disk_loads_total")
    REGISTRY.observe("aot_load_seconds", dt, curve=str(key[1]))
    return fn


def _persist(path: str, key: tuple, blob: bytes) -> None:
    """Atomic npz write; an unwritable store degrades silently (the
    freshly compiled executable still serves this process)."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=os.path.dirname(path), suffix=".tmp.npz"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(
                    f,
                    blob=np.frombuffer(blob, np.uint8),
                    digest=np.frombuffer(_digest(_header(key), blob), np.uint8),
                    key=np.frombuffer(repr(key).encode(), np.uint8),
                    source=np.frombuffer(source_fingerprint().encode(), np.uint8),
                )
            os.replace(tmp, path)
            with _LOCK:
                if _DISK is not None:
                    _DISK[key] = path
        finally:
            if os.path.exists(tmp):
                os.unlink(tmp)
    except OSError:
        pass


def _book_stage(key: tuple, stage: str, seconds: float) -> None:
    """``aot_build_stage_seconds{curve=,kind=,stage=}``: ``kind`` and
    ``curve`` are the program key's first two fields."""
    REGISTRY.observe(
        "aot_build_stage_seconds",
        seconds,
        _BUILD_BUCKETS,
        kind=str(key[0]),
        curve=str(key[1]),
        stage=stage,
    )


@contextlib.contextmanager
def _stage(key: tuple, stage: str):
    """One stage of one program's build, timed and booked."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _book_stage(key, stage, time.perf_counter() - t0)


def _build_staged(key: tuple, build):
    """Run ``build`` and whatever stages its result still lacks, each
    timed apart: ``trace`` (Python tracing to a jaxpr: the thunk itself,
    where it returns a ``Traced``), ``lower`` (jaxpr to StableHLO, the
    Mosaic kernels' bodies included), ``compile`` (the backend).  A
    thunk that returns a later stage has done the earlier ones inside
    itself, and its seconds are booked under the stage it returns."""
    t0 = time.perf_counter()
    obj = build()
    own = time.perf_counter() - t0
    # a Traced can be lowered, a Lowered compiled, a Compiled neither
    first = "trace" if hasattr(obj, "lower") else "lower" if hasattr(obj, "compile") else "compile"
    _book_stage(key, first, own)
    if first == "trace":
        with _stage(key, "lower"):
            obj = obj.lower()
    if first != "compile":
        with _stage(key, "compile"):
            obj = obj.compile()
    return obj


def get_or_build(key: tuple, build):
    """The store's one lookup: process cache -> validated disk load ->
    ``build()`` + persist.  ``build`` is a thunk returning the program
    at any of jax's stages: a ``jax.stages.Traced`` (``jit(f).trace(
    *specs)``: the engine's seams), a ``Lowered`` or a finished
    ``Compiled``; the store takes it through the stages that are left
    and books each one's seconds (:func:`_build_staged`).  Returns a
    loaded executable callable with the program's dynamic (non-static)
    operands."""
    with _LOCK:
        hit = _PROC.get(key)
        if hit is not None:
            _STATS["proc_hits"] += 1
            return hit
        klock = _KEY_LOCKS.setdefault(key, threading.Lock())
    # the slow path (deserialize or compile) runs under the KEY's lock
    # only: concurrent lookups of other keys proceed, concurrent
    # lookups of this key wait and then hit the cache
    with klock:
        with _LOCK:
            hit = _PROC.get(key)
            if hit is not None:
                _STATS["proc_hits"] += 1
                return hit
        path = _path(key)
        fn = _load_blob(path, key)
        if fn is None:
            t0 = time.perf_counter()
            fn = _build_staged(key, build)
            dt = time.perf_counter() - t0
            with _LOCK:
                _STATS["builds"] += 1
                _STATS["build_s"] += dt
            REGISTRY.inc("aot_builds_total")
            REGISTRY.observe("aot_build_seconds", dt)
            try:
                with _stage(key, "serialize"):
                    _persist(path, key, serialize(fn))
            except Exception as exc:
                # some backends can't serialize; the compiled program
                # still serves this process
                note_error(exc, "serialize")
        with _LOCK:
            _PROC[key] = fn
        return fn


def _stored_key(path: str):
    """The program key of one artifact, from its small ``key`` member
    (never the executable blob).  None for an artifact baked from other
    source (another checkout's: not ours to load, and no fault) and for
    one that does not parse (counted in ``disk_rejects``)."""
    try:
        with np.load(path, allow_pickle=False) as z:
            # no such member: an artifact of before the store bound its source
            if "source" not in z.files or z["source"].tobytes().decode() != source_fingerprint():
                return None
            key = ast.literal_eval(z["key"].tobytes().decode())
        if not (isinstance(key, tuple) and key and isinstance(key[0], str)):
            raise ValueError("not a program key")
    except Exception:
        with _LOCK:
            _STATS["disk_rejects"] += 1
        REGISTRY.inc("aot_disk_rejects_total")
        return None
    return key


def _scan_disk() -> dict:
    """{key: path} of every parseable artifact in the store (one cheap
    directory scan; only the small ``key`` member of each npz is read,
    never the executable blob).  Cached per process; :func:`_persist`
    keeps it current for this process's own writes."""
    global _DISK
    with _LOCK:
        if _DISK is not None:
            return _DISK
        disk: dict = {}
        try:
            names = sorted(os.listdir(cache_dir()))
        except OSError:
            names = []
        for name in names:
            if not (name.startswith("aot_v") and name.endswith(".npz")):
                continue
            path = os.path.join(cache_dir(), name)
            key = _stored_key(path)
            if key is not None:
                disk[key] = path
        _DISK = disk
        return disk


def disk_has_prefix(prefix: tuple) -> bool:
    """True when the store holds an artifact whose key starts with
    ``prefix`` — resident or not.  Lets warmup skip its throwaway
    convoy (the compile) while leaving the deserialize to first
    dispatch (lazy loads are seconds; compiles are minutes)."""
    if has_prefix(prefix):
        return True
    return any(k[: len(prefix)] == prefix for k in _scan_disk())


def preload_prefixes(prefixes) -> int:
    """Deserialize only the artifacts matching ``prefixes`` into the
    process cache — the warmup path's targeted load.  On a one-core
    host the full store deserializes at ~6 MB/s, so a worker preloads
    just its steady convoy shape and lets the long tail load lazily.
    Returns how many executables became resident."""
    prefixes = [tuple(p) for p in prefixes]
    loaded = 0
    for key, path in sorted(_scan_disk().items()):
        if not any(key[: len(p)] == p for p in prefixes):
            continue
        with _LOCK:
            if key in _PROC:
                continue
            fn = _load_blob(path, key)
            if fn is not None:
                _PROC[key] = fn
                loaded += 1
            REGISTRY.set_gauge("aot_resident_executables", len(_PROC))
    return loaded


def preload(max_seconds: float | None = None) -> int:
    """Deserialize every valid artifact in the store into the process
    cache (idempotent; at most once per process unless :func:`reset`).
    Returns the number of resident executables.  ``max_seconds`` bounds
    the scan so a worker's warmup budget is respected — remaining
    artifacts load lazily on first dispatch."""
    global _PRELOADED
    with _LOCK:
        if _PRELOADED:
            return len(_PROC)
        t0 = time.perf_counter()
        try:
            names = sorted(os.listdir(cache_dir()))
        except OSError:
            names = []
        for name in names:
            if not (name.startswith("aot_v") and name.endswith(".npz")):
                continue
            if max_seconds is not None and time.perf_counter() - t0 > max_seconds:
                break
            path = os.path.join(cache_dir(), name)
            key = _stored_key(path)
            if key is None or key in _PROC:
                continue
            fn = _load_blob(path, key)
            if fn is not None:
                _PROC[key] = fn
        _PRELOADED = True
        REGISTRY.set_gauge("aot_resident_executables", len(_PROC))
        return len(_PROC)


def has_prefix(prefix: tuple) -> bool:
    """True when some resident executable's key starts with ``prefix``
    — lets warmup skip a bucket whose programs already loaded."""
    with _LOCK:
        return any(k[: len(prefix)] == prefix for k in _PROC)


def note_error(exc: BaseException, where: str) -> None:
    """Count one store failure (the caller rebuilt or degraded to its
    jit path) and log the first with its exception text."""
    with _LOCK:
        _STATS["errors"] += 1
        first = _STATS["errors"] == 1
    REGISTRY.inc("aot_errors_total")
    if first:
        _LOG.error("AOT store error (%s): %s: %s", where, type(exc).__name__, exc)


def stats() -> dict:
    with _LOCK:
        return dict(_STATS, resident=len(_PROC))


def reset(clear_disk: bool = False) -> None:
    """Forget process state (tests); optionally delete the store."""
    global _PRELOADED, _DISK
    with _LOCK:
        _PROC.clear()
        _KEY_LOCKS.clear()
        _PRELOADED = False
        _DISK = None
        for k in _STATS:
            _STATS[k] = 0 if isinstance(_STATS[k], int) else 0.0
        if clear_disk:
            try:
                for name in os.listdir(cache_dir()):
                    if name.startswith("aot_v") and name.endswith(".npz"):
                        os.unlink(os.path.join(cache_dir(), name))
            except OSError:
                pass
