"""ctypes bindings for the native host arithmetic runtime (native/).

Builds ``libdkg_native.so`` from source with g++ on first use (cached in
``build/``), and exposes batched field/curve/ChaCha20 ops on numpy
arrays.  Python-int host code (fields.host / groups.host) remains the
canonical oracle; this library is the fast host path for bulk work
(fixed-base table generation, oracle verification sweeps, bulk DEM).

Availability is optional: ``available()`` gates every use, so the
framework runs unchanged on hosts without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import pathlib
import subprocess
from typing import Optional

import numpy as np

MAXL = 8
_REPO = pathlib.Path(__file__).resolve().parent.parent.parent
_SRC = _REPO / "native" / "dkg_native.cpp"
_LIB = _REPO / "build" / "libdkg_native.so"

_lib: Optional[ctypes.CDLL] = None
_build_failed = False


class FieldCtxStruct(ctypes.Structure):
    _fields_ = [
        ("nlimbs", ctypes.c_uint64),
        ("p", ctypes.c_uint64 * (MAXL + 1)),
        ("mu", ctypes.c_uint64 * (MAXL + 2)),
    ]


class EdCtxStruct(ctypes.Structure):
    _fields_ = [("f", FieldCtxStruct), ("d2", ctypes.c_uint64 * MAXL)]


class WsCtxStruct(ctypes.Structure):
    _fields_ = [("f", FieldCtxStruct), ("b3", ctypes.c_uint64 * MAXL)]


def _build() -> bool:
    _LIB.parent.mkdir(parents=True, exist_ok=True)
    # build beside the target and rename: another process (an xdist
    # worker) must never find a half-written library at _LIB
    tmp = _LIB.with_name(f"{_LIB.name}.{os.getpid()}.tmp")
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        str(_SRC), "-o", str(tmp),
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, _LIB)
        return True
    except Exception:
        tmp.unlink(missing_ok=True)
        return False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    if _lib is not None:
        return _lib
    if _build_failed:
        return None
    if not _LIB.exists() or _LIB.stat().st_mtime < _SRC.stat().st_mtime:
        if not _build():
            _build_failed = True
            return None
    try:
        return _bind(ctypes.CDLL(str(_LIB)))
    except AttributeError:
        # stale library missing newly required symbols despite a fresh
        # mtime (same-second checkouts, archive extraction): rebuild
        # once from source before giving up.
        try:
            _LIB.unlink()
        except OSError:
            pass
        if _build():
            try:
                return _bind(ctypes.CDLL(str(_LIB)))
            except (OSError, AttributeError):
                pass
        _build_failed = True
        return None
    except OSError:
        # builds-but-won't-load (e.g. a MinGW DLL whose runtime deps are
        # not on the DLL search path): cache the failure so available()
        # gates every use, as promised — never raise out of the optional
        # runtime.
        _build_failed = True
        return None


def _bind(lib: ctypes.CDLL) -> ctypes.CDLL:
    global _lib
    u64p = ctypes.POINTER(ctypes.c_uint64)
    for name, argtypes in {
        "f_add_batch": [ctypes.c_void_p, u64p, u64p, u64p, ctypes.c_size_t],
        "f_sub_batch": [ctypes.c_void_p, u64p, u64p, u64p, ctypes.c_size_t],
        "f_mul_batch": [ctypes.c_void_p, u64p, u64p, u64p, ctypes.c_size_t],
        "f_pow": [ctypes.c_void_p, u64p, u64p, ctypes.c_uint64, u64p],
        "ed_add_batch": [ctypes.c_void_p, u64p, u64p, u64p, ctypes.c_size_t],
        "ed_scalar_mul_batch": [
            ctypes.c_void_p, u64p, ctypes.c_uint64, u64p, u64p, ctypes.c_size_t
        ],
        "ed_scalar_mul_ct_batch": [
            ctypes.c_void_p, u64p, ctypes.c_uint64, ctypes.c_uint64,
            u64p, u64p, ctypes.c_size_t,
        ],
        "ws_add_batch": [ctypes.c_void_p, u64p, u64p, u64p, ctypes.c_size_t],
        "ws_scalar_mul_batch": [
            ctypes.c_void_p, u64p, ctypes.c_uint64, u64p, u64p, ctypes.c_size_t
        ],
        "ws_scalar_mul_ct_batch": [
            ctypes.c_void_p, u64p, ctypes.c_uint64, ctypes.c_uint64,
            u64p, u64p, ctypes.c_size_t,
        ],
        "chacha20_xor": [
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint32,
            ctypes.c_char_p, ctypes.c_char_p, ctypes.c_size_t,
        ],
    }.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = None
    _lib = lib
    return lib


def available() -> bool:
    return _load() is not None


# ---------------------------------------------------------------------------
# int <-> 64-bit limb conversion
# ---------------------------------------------------------------------------


def limbs64(x: int, n: int) -> np.ndarray:
    out = np.zeros(n, np.uint64)
    for i in range(n):
        out[i] = x & 0xFFFFFFFFFFFFFFFF
        x >>= 64
    if x:
        raise ValueError("does not fit")
    return out


def from_limbs64(a) -> int:
    acc = 0
    for i, v in enumerate(np.asarray(a, np.uint64).tolist()):
        acc |= int(v) << (64 * i)
    return acc


def nlimbs64(modulus: int) -> int:
    return (modulus.bit_length() + 63) // 64


class NativeField:
    """Batched field ops over a fixed prime (64-bit-limb Barrett)."""

    def __init__(self, modulus: int):
        self.modulus = modulus
        self.n = nlimbs64(modulus)
        if self.n > MAXL:
            raise ValueError("modulus too wide for native runtime")
        ctx = FieldCtxStruct()
        ctx.nlimbs = self.n
        for i, v in enumerate(limbs64(modulus, self.n + 1)):
            ctx.p[i] = int(v)
        mu = (1 << (128 * self.n)) // modulus
        for i, v in enumerate(limbs64(mu, self.n + 2)):
            ctx.mu[i] = int(v)
        self._ctx = ctx

    def _ptr(self):
        return ctypes.byref(self._ctx)

    def encode(self, vals) -> np.ndarray:
        vals = np.atleast_1d(np.asarray(vals, dtype=object))
        out = np.zeros((len(vals), self.n), np.uint64)
        for i, v in enumerate(vals):
            out[i] = limbs64(int(v) % self.modulus, self.n)
        return out

    def decode(self, arr) -> list[int]:
        arr = np.asarray(arr, np.uint64).reshape(-1, self.n)
        return [from_limbs64(row) for row in arr]

    def _binop(self, name, a, b):
        lib = _load()
        a = np.ascontiguousarray(a, np.uint64)
        b = np.ascontiguousarray(b, np.uint64)
        out = np.empty_like(a)
        count = a.size // self.n
        u64p = ctypes.POINTER(ctypes.c_uint64)
        getattr(lib, name)(
            self._ptr(),
            a.ctypes.data_as(u64p),
            b.ctypes.data_as(u64p),
            out.ctypes.data_as(u64p),
            count,
        )
        return out

    def add(self, a, b):
        return self._binop("f_add_batch", a, b)

    def sub(self, a, b):
        return self._binop("f_sub_batch", a, b)

    def mul(self, a, b):
        return self._binop("f_mul_batch", a, b)

    def pow(self, a, e: int):
        lib = _load()
        a = np.ascontiguousarray(a, np.uint64).reshape(self.n)
        el = np.ascontiguousarray(limbs64(e, (e.bit_length() + 63) // 64 or 1))
        out = np.empty(self.n, np.uint64)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        lib.f_pow(
            self._ptr(), a.ctypes.data_as(u64p), el.ctypes.data_as(u64p),
            len(el), out.ctypes.data_as(u64p),
        )
        return out

    def inv(self, a):
        return self.pow(a, self.modulus - 2)


class NativeCurve:
    """Batched point ops (edwards: 4 coords; weierstrass_a0: 3 coords)."""

    def __init__(self, kind: str, modulus: int, const: int):
        self.kind = kind
        self.field = NativeField(modulus)
        n = self.field.n
        if kind == "edwards":
            ctx = EdCtxStruct()
            tgt = ctx.d2
        elif kind == "weierstrass_a0":
            ctx = WsCtxStruct()
            tgt = ctx.b3
        else:
            raise ValueError(kind)
        ctx.f = self.field._ctx
        for i, v in enumerate(limbs64(const % modulus, n)):
            tgt[i] = int(v)
        self._ctx = ctx
        self.ncoords = 4 if kind == "edwards" else 3

    def encode_points(self, pts) -> np.ndarray:
        out = np.zeros((len(pts), self.ncoords, self.field.n), np.uint64)
        for i, p in enumerate(pts):
            for c in range(self.ncoords):
                out[i, c] = limbs64(int(p[c]) % self.field.modulus, self.field.n)
        return out

    def decode_points(self, arr) -> list[tuple]:
        arr = np.asarray(arr, np.uint64).reshape(-1, self.ncoords, self.field.n)
        return [tuple(from_limbs64(row[c]) for c in range(self.ncoords)) for row in arr]

    def add(self, p, q):
        lib = _load()
        p = np.ascontiguousarray(p, np.uint64)
        q = np.ascontiguousarray(q, np.uint64)
        out = np.empty_like(p)
        count = p.size // (self.ncoords * self.field.n)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        name = "ed_add_batch" if self.kind == "edwards" else "ws_add_batch"
        getattr(lib, name)(
            ctypes.byref(self._ctx), p.ctypes.data_as(u64p),
            q.ctypes.data_as(u64p), out.ctypes.data_as(u64p), count,
        )
        return out

    def _scalar_mul_impl(self, suffix, scalars, points, scalar_modulus, extra):
        """Shared marshalling for the vartime and constant-time ladders:
        scalar limb encoding, point layout, and the kind-based dispatch
        differ only by function-name suffix and the extra mid arguments."""
        lib = _load()
        sl = nlimbs64(scalar_modulus)
        ss = np.zeros((len(scalars), sl), np.uint64)
        for i, s in enumerate(scalars):
            ss[i] = limbs64(int(s) % scalar_modulus, sl)
        points = np.ascontiguousarray(points, np.uint64)
        out = np.empty_like(points)
        u64p = ctypes.POINTER(ctypes.c_uint64)
        prefix = "ed" if self.kind == "edwards" else "ws"
        getattr(lib, f"{prefix}_scalar_mul{suffix}")(
            ctypes.byref(self._ctx),
            ss.ctypes.data_as(u64p),
            sl,
            *extra,
            points.ctypes.data_as(u64p),
            out.ctypes.data_as(u64p),
            len(scalars),
        )
        return out

    def scalar_mul(self, scalars, points, scalar_modulus: int):
        """Variable-time ladder; PUBLIC scalars only."""
        return self._scalar_mul_impl("_batch", scalars, points, scalar_modulus, ())

    def scalar_mul_ct(self, scalars, points, scalar_modulus: int):
        """Constant-structure ladder over the full scalar-field bit
        length — the secret-scalar path (wire-path KEM / dealing).
        Limb-exact match of HostGroup.scalar_mul's Python ladder."""
        return self._scalar_mul_impl(
            "_ct_batch", scalars, points, scalar_modulus,
            (scalar_modulus.bit_length(),),
        )


def chacha20_xor(key: bytes, nonce: bytes, data: bytes, counter: int = 0) -> bytes:
    lib = _load()
    if lib is None:
        raise RuntimeError("native runtime unavailable")
    out = ctypes.create_string_buffer(len(data))
    lib.chacha20_xor(key, nonce, counter, data, out, len(data))
    return out.raw
