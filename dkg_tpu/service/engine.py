"""Warm execution lane: pad-and-mask ceremonies over shared runtime state.

One process serves MANY ceremonies, so everything shape- or
curve-dependent is shared and warm:

* fixed-base tables come from :mod:`dkg_tpu.groups.precompute` (one
  process-wide cache, persisted to disk) via :class:`WarmRuntime`, which
  additionally caches the per-``shared_string`` Pedersen commitment key
  and its ``h`` table;
* every request's ``(n, t)`` is padded to its :func:`~dkg_tpu.service.
  buckets.bucket_for` bucket, so all requests in a bucket reuse ONE set
  of jitted executables (the compile cache is keyed by static shape);
* same-bucket requests stack on a leading *ceremony axis* and run
  through vmapped twins of the round kernels (``_deal_stack`` etc.) —
  the kernels in dkg.ceremony are already array-shaped, so stacking is
  a natural lift that amortizes per-dispatch overhead across the convoy
  (the dominant cost for small committees on CPU/single-core hosts).

Bit-exactness: phantom lanes are zero-coefficient dealers (zero shares,
identity commitments) and every round-1 kernel is elementwise along the
dealer/ceremony axes, so a real lane's outputs — wire bytes included —
are bit-identical whether it runs unpadded, padded, or stacked
(tests/test_service.py oracle tests, both curves).  The Fiat-Shamir
randomizers ``rho`` DO differ between the padded and unpadded legs (the
transcript digest binds the padded tensors); that changes only which
random linear combination checks the same set of pair equations, never
the dealt values, the qualified set on honest runs, or the master key.

The start/finish split (:func:`start_convoy` / :func:`finish_convoy`)
generalizes ``hybrid_batch.seal_shares_pipeline``'s overlap trick to
whole ceremonies: ``start`` only *dispatches* device work (JAX dispatch
is asynchronous), so a scheduler worker can start convoy k+1 before
doing convoy k's host-side transcript/DEM work under the device's
dispatch shadow.

Every stage of a convoy runs inside ``tracing.phase_span(fl.trace,
"convoy.<stage>")`` (:data:`CONVOY_STAGES`; docs/observability.md has
the table): a host event ``dkg/convoy.<stage>`` on the profiler's clock,
a ``dkg_phase_seconds{phase="convoy.<stage>"}`` observation and an entry
of the convoy's :class:`~dkg_tpu.utils.tracing.CeremonyTrace`.  A device
step is two stages, ``*_dispatch`` (host work up to the asynchronous
dispatch's return) and ``*_wait`` (blocked until the step's results are
there: ``np.asarray`` of what the host needs, and for deal, whose
tensors stay on the device, ``block_until_ready``), so host time and
waiting never share a number.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import random
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..crypto.commitment import CommitmentKey
from ..dkg import ceremony as ce
from ..fields import host as fh
from ..groups import device as gd
from ..groups import host as gh
from ..groups import precompute as gp
from ..parallel import mesh as pm
from ..utils import tracing
from ..utils.metrics import REGISTRY
from . import aot, buckets
from .errors import PoisonedRequest

#: Default domain-separation string for service ceremonies (requests may
#: override; the commitment key h derives from it).
DEFAULT_SHARED_STRING = b"dkg-tpu-service"


@dataclasses.dataclass(frozen=True)
class CeremonyRequest:
    """One ceremony-as-a-service request.

    ``seed`` pins the coefficient stream (``random.Random(seed)``, drawn
    in exactly :class:`~dkg_tpu.dkg.ceremony.BatchedCeremony`'s order:
    :func:`draw_coeffs` and it share :func:`~dkg_tpu.fields.host.draw_limbs`) so
    results are reproducible and WAL replay after a crash re-deals
    byte-identical polynomials; ``None`` uses ``random.SystemRandom``
    (non-durable requests only).  ``deadline_s`` is a relative budget
    from admission; a ceremony past its deadline is EXPIRED rather than
    started (and rather than *finished*, if it expires mid-flight).
    """

    curve: str
    n: int
    t: int
    shared_string: bytes = DEFAULT_SHARED_STRING
    seed: int | None = None
    rho_bits: int = 128
    deadline_s: float | None = None
    durable: bool = False
    tag: str = ""

    def bucket(self) -> buckets.Bucket:
        return buckets.bucket_for(self.n, self.t)

    def convoy_key(self) -> tuple:
        """Requests sharing this key may stack into one convoy: same
        curve, bucket, randomizer width and commitment key."""
        b = self.bucket()
        return (self.curve, b.n, b.t, self.rho_bits, self.shared_string)


def request_id(req: CeremonyRequest, seq: int = 0) -> str:
    """Deterministic short ceremony id: request identity + admission
    sequence number (submitting the same request twice is two
    ceremonies).  Mirrors obslog.ceremony_id_for's blake2b-48 shape."""
    h = hashlib.blake2b(digest_size=6)
    h.update(
        f"{req.curve}|{req.n}|{req.t}|{req.seed}|{req.rho_bits}|{seq}|".encode()
    )
    h.update(req.shared_string)
    return h.hexdigest()


@dataclasses.dataclass
class CeremonyOutcome:
    """Public result of one ceremony.  ``master`` is the canonical
    encoded master public key; ``final_shares`` (secret!) stays in
    process memory only — the durability journal persists everything
    here EXCEPT it (dkg_tpu.service.durable)."""

    ceremony_id: str
    status: str  # "done" | "failed"
    curve: str = ""
    n: int = 0
    t: int = 0
    bucket_n: int = 0
    bucket_t: int = 0
    master: bytes = b""
    qualified: tuple = ()
    complaints: tuple = ()
    error: str = ""
    #: engine wall-clock attributed to this ceremony: its convoy's
    #: runtime divided by the convoy width
    seconds: float = 0.0
    #: time.monotonic() stamp set by the scheduler when the outcome was
    #: recorded — lets clients compute queue-to-completion latency
    completed_at: float = 0.0
    #: width of the convoy this ceremony ran in, and the seconds it sat
    #: queued (admission to the pop that took it into that convoy); set
    #: by the scheduler, 0 for outcomes that never rode a convoy
    convoy_width: int = 0
    queue_seconds: float = 0.0
    #: epoch counter of the held sharing: 0 at the ceremony, +1 per
    #: completed refresh/reshare against this outcome (the scheduler's
    #: epoch methods CAS on it).  ``master`` never changes with it.
    epoch: int = 0
    final_shares: np.ndarray | None = dataclasses.field(
        default=None, repr=False, compare=False
    )


#: host bytes of coefficient tensors a runtime keeps while no convoy
#: has them: two pairs at the sharded shape (4096, 1366), 716 MB each —
#: a two-deep worker's rotation — and every pair of the smaller buckets
STAGING_KEEP_BYTES = 2 << 30


@dataclasses.dataclass
class StagedCoeffs:
    """A pair of padded coefficient tensors ``(n_pad, t_pad+1, L)`` that
    outlives the request that filled it, and ``real``, the ``(n, t+1)``
    lanes that request wrote: everything outside them is zero."""

    a: np.ndarray
    b: np.ndarray
    real: tuple = (0, 0)

    def lanes(self, n: int, tc: int) -> tuple[np.ndarray, np.ndarray]:
        """The ``[:n, :tc]`` views a draw writes, after zeroing what the
        request before wrote outside them (another real ``(n, t)`` of the
        bucket): the pad lanes are zero whoever had the tensors."""
        pn, ptc = self.real
        for x in (self.a, self.b):
            x[n:pn, :ptc] = 0
            x[:n, tc:ptc] = 0
        self.real = (n, tc)
        return self.a[:n, :tc], self.b[:n, :tc]


class CoeffStaging:
    """The coefficient tensors a runtime keeps from request to request,
    so that a large draw writes mapped pages instead of 0.1-0.7 GB of
    fresh ones (glibc hands allocations of that size back to the kernel
    when they are freed; the first touch was the larger half of the
    sharded request's ``draw``: PERF.md section 6, PR 45).

    A pair is lent to one convoy (:meth:`lend`) and comes back
    (:meth:`give_back`) once the outputs of the deal that read it are
    ready — until then a backend may still read the host memory (a
    transfer in flight; the CPU backend may alias it outright), so a
    pair is never rewritten under a live deal and **two convoys in
    flight never share one**.  A two-deep worker so turns two pairs a
    bucket, as many as it has convoys, and waits for nothing it did not
    wait for before.  Idle pairs are bounded by
    :data:`STAGING_KEEP_BYTES` (what comes back beyond it is dropped); a
    pair whose convoy failed before its finish is simply not returned.
    Books ``coeff_staging_total{event="alloc"|"reuse"}``."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._idle: dict[tuple, list[StagedCoeffs]] = {}
        self._idle_bytes = 0

    def lend(self, shape: tuple) -> StagedCoeffs:
        with self._lock:
            idle = self._idle.get(shape)
            pair = idle.pop() if idle else None
            if pair is not None:
                self._idle_bytes -= pair.a.nbytes + pair.b.nbytes
        REGISTRY.inc("coeff_staging_total", event="alloc" if pair is None else "reuse")
        if pair is None:
            pair = StagedCoeffs(np.zeros(shape, np.uint32), np.zeros(shape, np.uint32))
        return pair

    def give_back(self, pair: StagedCoeffs) -> None:
        nbytes = pair.a.nbytes + pair.b.nbytes
        with self._lock:
            if self._idle_bytes + nbytes <= STAGING_KEEP_BYTES:
                self._idle.setdefault(pair.a.shape, []).append(pair)
                self._idle_bytes += nbytes


class WarmRuntime:
    """Shared warm state for all ceremonies in a process: fixed-base
    tables (via groups.precompute's process+disk cache) and per
    ``(curve, shared_string)`` commitment keys, and the coefficient
    tensors kept for large draws (:class:`CoeffStaging`, released with
    the runtime).  Thread-safe; every scheduler worker holds one
    reference."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._ck: dict = {}
        self._mesh: dict = {}
        self.staging = CoeffStaging()

    def commitment(self, curve: str, shared_string: bytes):
        """(CommitmentKey, g_table, h_table) for a ceremony environment,
        cached.  The g table is shared curve-wide; h derives from the
        shared string."""
        key = (curve, shared_string)
        with self._lock:
            hit = self._ck.get(key)
        if hit is not None:
            return hit
        cs = gd.ALL_CURVES[curve]
        group = gh.ALL_GROUPS[curve]
        ck = CommitmentKey.generate(group, shared_string)
        # precompute has its own build-once lock; taking self._lock over
        # these (multi-second, possibly-compiling) builds would serialize
        # unrelated curves behind one warmer
        g_table = gp.generator_table(cs)
        h_table = gp.base_table(cs, ck.h)
        entry = (ck, g_table, h_table)
        with self._lock:
            self._ck.setdefault(key, entry)
        # which tier of the point kernels this process serves the curve
        # on: the trace-time counters stay zero where programs are loaded
        REGISTRY.set_gauge("point_kernel_tier", 1, curve=curve, **gd.point_kernel_tier())
        return entry

    def mesh_route(self, curve: str, shared_string: bytes, b: buckets.Bucket, n_dev: int):
        """(mesh, g_table, h_table) of a sharded bucket's route
        (``buckets.shard_devices``): the party mesh over the first
        ``n_dev`` local devices and the two fixed-base tables replicated
        on it — placed once a process, so a request moves only its own
        coefficients to the devices.  Books the gauge
        ``mesh_route{bucket,devices}`` the first time a bucket takes the
        route."""
        key = (curve, shared_string, n_dev)
        with self._lock:
            hit = self._mesh.get(key)
        if hit is None:
            _, g_table, h_table = self.commitment(curve, shared_string)
            mesh = pm.make_mesh(n_dev)
            hit = (
                mesh,
                pm.place_replicated(mesh, g_table),
                pm.place_replicated(mesh, h_table),
            )
            with self._lock:
                hit = self._mesh.setdefault(key, hit)
        REGISTRY.set_gauge("mesh_route", 1, bucket=b.label, devices=str(n_dev))
        return hit

    def warmup(self, req: CeremonyRequest, widths: tuple = (1,)) -> None:
        """Compile the request's bucket programs ahead of traffic by
        running one throwaway convoy per width (results discarded).

        With the AOT store enabled (``DKG_TPU_AOT_DIR``), prebaked
        executables deserialize into the process instead: the largest
        requested width — the steady convoy shape — gets its
        deal/verify pair preloaded eagerly, and any width whose deal
        program is on disk skips its throwaway convoy entirely, leaving
        the long tail (finalise, straggler widths, sign rungs) to lazy
        dispatch-time loads.  Loads are seconds, compiles are minutes:
        on a one-core host the store deserializes at ~5 MB/s, so eager
        preloading everything would itself blow the warmup budget.  A
        width missing from the store still runs its convoy (and, via
        the dispatch seams, persists its executables for the next
        process)."""
        b = req.bucket()
        if aot.enabled():
            # tables + commitment key first: convoy-free warmup must
            # leave the runtime as ready as the compiling path does
            self.commitment(req.curve, req.shared_string)
            w_hot = max(widths)
            aot.preload_prefixes(
                [
                    ("deal", req.curve, b.n, b.t, w_hot),
                    ("verify", req.curve, b.n, b.t, w_hot),
                ]
            )
        for w in widths:
            if aot.enabled() and aot.disk_has_prefix(
                ("deal", req.curve, b.n, b.t, w)
            ):
                continue
            reqs = [
                dataclasses.replace(req, seed=(req.seed or 0) + i)
                for i in range(w)
            ]
            finish_convoy(self, start_convoy(self, reqs))


# ---------------------------------------------------------------------------
# AOT executable dispatch
# ---------------------------------------------------------------------------


def _specs(args: tuple) -> tuple:
    return tuple(
        jax.tree_util.tree_map(
            lambda leaf: jax.ShapeDtypeStruct(np.shape(leaf), leaf.dtype), a
        )
        for a in args
    )


def _aot_dispatch(key_prefix: tuple, args: tuple, trace, fallback):
    """Serve one program dispatch from the AOT executable store when
    it is enabled, else the ordinary jitted twin.  ``trace`` maps a
    tuple of ShapeDtypeStruct specs to a ``jax.stages.Traced`` (statics
    baked in); the store lowers and compiles it, books each stage's
    seconds (``aot_build_stage_seconds{kind=,stage=}``) and persists the
    result for every later process.
    A store failure degrades to ``fallback`` — a request must never die
    on a cache problem — but is counted and logged (aot.note_error)."""
    if not aot.enabled():
        return fallback()
    try:
        key = key_prefix + (aot.spec_sig(args),)
        fn = aot.get_or_build(key, lambda: trace(_specs(args)))
        return fn(*args)
    except Exception as exc:
        aot.note_error(exc, f"dispatch {key_prefix[0]}")
        return fallback()


def stored_mesh_program(kind: str, cfg: ce.CeremonyConfig, mesh, rho_bits: int, prog, args):
    """The ``run`` that the sharded route hands ``parallel/mesh.py``'s
    phase functions: one ``shard_map`` program through the same seam as
    the one-device programs (:func:`_aot_dispatch`: its stored executable
    where the store is on, the jitted ``prog`` otherwise and after a
    store failure, counted).

    The key binds what such a program is compiled for beside the
    one-device programs' fields: the mesh's shape, its devices' kind and
    ids (an executable runs on the devices it was compiled for).
    Operands are placed under the program's ``pm.IN_SPECS`` first (no
    copy where they already lie so): a stored executable takes its
    arguments only in the layout it was compiled for."""
    if not aot.enabled():
        return prog(*args)
    args = tuple(pm.place_sharded(mesh, x, spec) for x, spec in zip(args, pm.IN_SPECS[kind]))
    devs = list(mesh.devices.flat)
    return _aot_dispatch(
        (
            kind, cfg.curve, cfg.n, cfg.t, rho_bits, tuple(mesh.devices.shape),
            devs[0].device_kind, tuple(d.id for d in devs),
        ),
        args,
        lambda _: prog.trace(
            *(jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding) for x in args)
        ),
        lambda: prog(*args),
    )


def aot_sign_folded(curve: str, sigma_limbs: np.ndarray, h_dev):
    """AOT twin of :func:`dkg_tpu.sign.partial.sign_folded`: same
    broadcast semantics, same raw device result (pure uint32 limb math,
    so the serialized ladder is bit-identical to the jit path), but the
    rung executable comes from the store — a fresh worker's first sign
    flush skips the ladder compile."""
    from .. import sign as signing

    if not aot.enabled():
        return signing.sign_folded(curve, sigma_limbs, h_dev)
    cs = gd.ALL_CURVES[curve]
    hh = jnp.asarray(h_dev)
    kk = jnp.asarray(sigma_limbs)
    if kk.ndim == 1:
        kk = jnp.broadcast_to(kk[None, :], (hh.shape[0], kk.shape[-1]))
    args = (kk, hh)
    return _aot_dispatch(
        ("sign_folded", curve, int(hh.shape[0])),
        args,
        lambda sp: _sign_ladder.trace(cs, *sp),
        lambda: signing.sign_folded(curve, sigma_limbs, h_dev),
    )


@functools.partial(jax.jit, static_argnums=0)
def _sign_ladder(cs, kk, hh):
    """Traced twin of the steady lane's folded ladder (scalar_mul's
    eager entry inlines its core under trace; rung batches are already
    power-of-two so the eager pad is a no-op)."""
    return gd.scalar_mul(cs, kk, hh)


# ---------------------------------------------------------------------------
# stacked (ceremony-axis) twins of the round kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def _deal_stack(cfg, coeffs_a, coeffs_b, g_table, h_table):
    """(k, n, t+1, L) coefficient stacks -> stacked round-1 tensors."""

    def one(ca, cb):
        return ce.deal(cfg, ca, cb, g_table, h_table)

    return jax.vmap(one)(coeffs_a, coeffs_b)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _verify_stack(cfg, e_comm, shares, hidings, rho, rho_bits, g_table, h_table):
    """(k, n, ...) stacks -> (k, n) bool.  Not a ``vmap``: ``verify_batch``
    takes the ceremony axis itself and packs the convoy's lanes jointly
    onto the point kernels' blocks; a map would pad each ceremony's
    lanes to blocks of its own.  A program of this name so that the
    store's key and the device trace (``jit__verify_stack``) stay."""
    return ce.verify_batch(cfg, e_comm, shares, hidings, rho, rho_bits, g_table, h_table)


@functools.partial(jax.jit, static_argnums=0)
def _finalise_stack(cfg, a_comm, shares, qualified):
    def one(a1, s1, q1):
        return (
            ce.aggregate_shares(cfg, s1, q1),
            ce.master_key_from_bare(cfg, a1, q1),
        )

    return jax.vmap(one)(a_comm, shares, qualified)


# ---------------------------------------------------------------------------
# coefficient drawing + padding
# ---------------------------------------------------------------------------


def draw_coeffs(
    cfg: ce.CeremonyConfig, rng, out: tuple = (None, None)
) -> tuple[np.ndarray, np.ndarray]:
    """The REAL coefficient tensors, drawn in exactly
    :class:`~dkg_tpu.dkg.ceremony.BatchedCeremony`'s order (both call
    :func:`~dkg_tpu.fields.host.draw_limbs`, ``a`` wholly before ``b``)
    so a seeded service ceremony and a fresh single-ceremony run of the
    same seed deal byte-identical polynomials.  Written into the pair
    ``out`` where given (the real lanes of kept, padded tensors)."""
    fs = cfg.cs.scalar
    shape = (cfg.n, cfg.t + 1)
    a = fh.draw_limbs(fs, rng, shape, out=out[0])
    b = fh.draw_limbs(fs, rng, shape, out=out[1])
    return a, b


def pad_coeffs(coeffs: np.ndarray, n_pad: int, t_pad: int) -> np.ndarray:
    """Zero-pad a real ``(n, t+1, L)`` coefficient tensor to the bucket
    shape ``(n_pad, t_pad+1, L)``: phantom dealers are all-zero
    polynomials, real dealers gain zero high-order coefficients — both
    inert under the pad-and-mask contract."""
    n, tc, limbs = coeffs.shape
    if (n, tc) == (n_pad, t_pad + 1):
        return coeffs
    out = np.zeros((n_pad, t_pad + 1, limbs), np.uint32)
    out[:n, :tc] = coeffs
    return out


def rng_for(req: CeremonyRequest):
    if req.seed is None:
        return random.SystemRandom()
    return random.Random(req.seed)


#: A convoy's stages in the order they run.  ``hold`` is the scheduler's
#: (service/scheduler.py): dispatched, waiting for its worker to come
#: back from the previous convoy's finish.
CONVOY_STAGES = (
    "draw", "deal_dispatch", "hold", "deal_wait", "digest_dispatch",
    "digest_wait", "rho_fold", "verify_dispatch", "verify_wait", "blame",
    "finalise_dispatch", "finalise_wait", "encode",
)

_CONVOY_SEQ = itertools.count()


def _stage(trace, stage: str):
    return tracing.phase_span(trace, f"convoy.{stage}")


def derive_rho_convoy(
    cfg: ce.CeremonyConfig, a, e, s, r, rho_bits: int, trace=None
) -> np.ndarray:
    """Per-ceremony Fiat-Shamir randomizers for a whole convoy, (k, n,
    L) — bit-identical to calling :func:`dkg_tpu.dkg.ceremony.
    derive_rho` on each ceremony's slice.

    The transcript row digests are per-dealer and row-independent, so
    the convoy's (k, n, ...) tensors fold into ONE (k*n, ...) row-digest
    pass — one dispatch per tensor family instead of 3*k — and only the
    outer fold stays per ceremony (``rho_fold``): three small arrays
    through one blake2b, then :func:`~dkg_tpu.dkg.ceremony.
    fiat_shamir_rho`'s n lanes through ``hashlib``.  With the lanes in
    numpy the stage held the interpreter lock long enough to keep the
    other workers off the chip (the figures: PERF.md section 6, PR 37).
    This is the digest's share of the dispatch amortization that makes
    the stacked lane pay: per-ceremony digest calls were ~40% of a small
    convoy's wall clock.

    The tensors are taken as they are, device arrays or numpy, with
    their ceremony axis.  ``digest_dispatch`` is
    :func:`dkg_tpu.dkg.ceremony._dealer_rows_device` up to its return:
    on the device leg five asynchronous dispatches on the arrays where
    deal left them (nothing is fetched, no eager operation between the
    programs), on the host leg the whole computation, the tensors' fetch
    included (``round1_host_bytes_total``).  ``digest_wait`` fetches the
    three (k * n, 8) row-digest arrays, 96 bytes a dealer, the only part
    of the transcript the device leg brings to the host: the three
    copies are started together and waited for once.  A width-1 convoy
    takes the same path and its stages carry the same names.
    """
    k = s.shape[0]
    with _stage(trace, "digest_dispatch"):
        rows = ce._dealer_rows_device(cfg, a, e, s, r)
    with _stage(trace, "digest_wait"):
        rows_a, rows_e, rows_sr = (
            np.asarray(x).reshape(k, cfg.n, -1) for x in jax.device_get(rows)
        )
    with _stage(trace, "rho_fold"):
        return np.stack(
            [
                ce.fiat_shamir_rho(
                    cfg,
                    ce._fold_digest_device(cfg, rows_a[i], rows_e[i], rows_sr[i]),
                    rho_bits,
                )
                for i in range(k)
            ]
        )


# ---------------------------------------------------------------------------
# convoy execution: start (device dispatch) / finish (host + device tail)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class InFlight:
    """A dispatched convoy.  The four round-1 tensors are deal's outputs
    and **live on the device from deal to finalise**: the digest leg,
    verify and finalise read them there, and nothing on the served path
    copies them to the host (``wire_broadcasts`` does, for the wire
    format's bytes)."""

    reqs: list
    ids: list
    cfg_pad: ce.CeremonyConfig
    g_table: jax.Array
    h_table: jax.Array
    a: jax.Array  # (k, n_pad, t_pad+1, C, L)
    e: jax.Array
    s: jax.Array  # (k, n_pad, n_pad, L)
    r: jax.Array
    #: the convoy's stage seconds (``convoy.<stage>``); ``meta`` names
    #: the convoy: sequence number, width, bucket, ceremony ids, and the
    #: worker slot once a scheduler has added it
    trace: tracing.CeremonyTrace = dataclasses.field(
        default_factory=tracing.CeremonyTrace
    )
    #: the sharded route only (``buckets.shard_devices``): the party mesh
    #: the four tensors are dealer-sharded over — they then carry no
    #: ceremony axis, the convoy is one request — and when deal was
    #: dispatched (``time.perf_counter``)
    mesh: object | None = None
    dispatched_at: float = 0.0
    #: the kept coefficient tensors deal reads (a width-1 convoy whose
    #: draw is large: :class:`CoeffStaging`), the runtime's again once
    #: ``deal_wait`` is over
    staged: StagedCoeffs | None = None


def start_convoy(
    runtime: WarmRuntime, reqs: list, ids: list | None = None
) -> InFlight:
    """Draw + pad coefficients for a same-key convoy and *dispatch* the
    stacked deal.  Returns without blocking on device work (width-1
    convoys reuse the plain :func:`dkg_tpu.dkg.ceremony.deal`
    executable; wider convoys use the vmapped twin)."""
    key = reqs[0].convoy_key()
    if any(r.convoy_key() != key for r in reqs):
        raise ValueError("start_convoy: mixed convoy keys")
    req0 = reqs[0]
    b = req0.bucket()
    k = len(reqs)
    cfg_pad = ce.CeremonyConfig(req0.curve, req0.n, req0.t).padded(b.n, b.t)
    _, g_table, h_table = runtime.commitment(req0.curve, req0.shared_string)
    if ids is None:
        ids = [request_id(req, i) for i, req in enumerate(reqs)]
    trace = tracing.CeremonyTrace(
        meta={
            "convoy": next(_CONVOY_SEQ),
            "width": k,
            "bucket": f"{b.n}x{b.t}",
            "ceremonies": list(ids),
        }
    )
    staged = None
    with _stage(trace, "draw"):
        if k == 1 and req0.n * (req0.t + 1) >= fh.BLOCK_MIN_SCALARS:
            # a large draw goes straight into the padded tensors, and
            # those are kept ones: no copy, no fresh pages
            staged = runtime.staging.lend((b.n, b.t + 1, cfg_pad.cs.scalar.limbs))
            draw_coeffs(
                ce.CeremonyConfig(req0.curve, req0.n, req0.t),
                rng_for(req0),
                out=staged.lanes(req0.n, req0.t + 1),
            )
            ca_h, cb_h = staged.a, staged.b
        else:
            ca, cb = [], []
            for req in reqs:
                cfg_real = ce.CeremonyConfig(req.curve, req.n, req.t)
                a_real, b_real = draw_coeffs(cfg_real, rng_for(req))
                ca.append(pad_coeffs(a_real, b.n, b.t))
                cb.append(pad_coeffs(b_real, b.n, b.t))
            ca_h, cb_h = (ca[0], cb[0]) if k == 1 else (np.stack(ca), np.stack(cb))
    n_dev = buckets.shard_devices(b) if k == 1 else 0
    if n_dev:
        # the sharded route: the same draw and pad, then the mesh's deal
        with _stage(trace, "deal_dispatch"):
            mesh, g_mesh, h_mesh = runtime.mesh_route(
                req0.curve, req0.shared_string, b, n_dev
            )
            ca_d, cb_d = pm.place_coeffs(mesh, ca_h, cb_h)
            t_deal = time.perf_counter()
            a, e, s, r = pm.sharded_deal(
                cfg_pad, mesh, ca_d, cb_d, g_mesh, h_mesh, stored_mesh_program
            )
        REGISTRY.inc("mesh_requests_total", devices=str(n_dev))
        return InFlight(
            list(reqs), list(ids), cfg_pad, g_mesh, h_mesh, a, e, s, r, trace,
            mesh=mesh, dispatched_at=t_deal, staged=staged,
        )
    with _stage(trace, "deal_dispatch"):
        args = (jnp.asarray(ca_h), jnp.asarray(cb_h), g_table, h_table)
        if k == 1:
            a, e, s, r = _aot_dispatch(
                ("deal", req0.curve, b.n, b.t, 1, 0),
                args,
                lambda sp: ce.deal.trace(cfg_pad, *sp),
                lambda: ce.deal(cfg_pad, *args),
            )
            a, e, s, r = a[None], e[None], s[None], r[None]
        else:
            a, e, s, r = _aot_dispatch(
                ("deal", req0.curve, b.n, b.t, k, 0),
                args,
                lambda sp: _deal_stack.trace(cfg_pad, *sp),
                lambda: _deal_stack(cfg_pad, *args),
            )
    return InFlight(
        list(reqs), list(ids), cfg_pad, g_table, h_table, a, e, s, r, trace,
        staged=staged,
    )


def finish_convoy(runtime: WarmRuntime, fl: InFlight) -> list[CeremonyOutcome]:
    """Transcript digest + stacked verify/finalise for a dispatched
    convoy.

    ``convoy.deal_wait`` only waits for deal's outputs to exist
    (``block_until_ready``: it copies nothing, and after a hold it
    returns at once) — everything before this call overlaps deal.
    :func:`derive_rho_convoy` then digests ``fl.a``, ``fl.e``, ``fl.s``,
    ``fl.r`` as the device arrays they are; verify and finalise read
    them on the device too."""
    if fl.mesh is not None:
        return _finish_sharded(runtime, fl)
    cfg_pad = fl.cfg_pad
    trace = fl.trace
    k = len(fl.reqs)
    n_pad = cfg_pad.n
    rho_bits = fl.reqs[0].rho_bits
    with _stage(trace, "deal_wait"):
        jax.block_until_ready((fl.a, fl.e, fl.s, fl.r))
    _release_staged(runtime, fl)
    rho = derive_rho_convoy(cfg_pad, fl.a, fl.e, fl.s, fl.r, rho_bits, trace)
    curve = fl.reqs[0].curve
    with _stage(trace, "verify_dispatch"):
        if k == 1:
            args = (
                fl.e[0], fl.s[0], fl.r[0], jnp.asarray(rho[0]),
                fl.g_table, fl.h_table,
            )
            ok = _aot_dispatch(
                ("verify", curve, n_pad, cfg_pad.t, 1, rho_bits),
                args,
                lambda sp: ce.verify_batch.trace(
                    cfg_pad, sp[0], sp[1], sp[2], sp[3], rho_bits, sp[4], sp[5]
                ),
                lambda: ce.verify_batch(
                    cfg_pad, args[0], args[1], args[2], args[3], rho_bits,
                    args[4], args[5],
                ),
            )[None]
        else:
            args = (fl.e, fl.s, fl.r, jnp.asarray(rho), fl.g_table, fl.h_table)
            ok = _aot_dispatch(
                ("verify", curve, n_pad, cfg_pad.t, k, rho_bits),
                args,
                lambda sp: _verify_stack.trace(
                    cfg_pad, sp[0], sp[1], sp[2], sp[3], rho_bits, sp[4], sp[5]
                ),
                lambda: _verify_stack(
                    cfg_pad, args[0], args[1], args[2], args[3], rho_bits,
                    args[4], args[5],
                ),
            )
    with _stage(trace, "verify_wait"):
        ok_h = np.asarray(ok)

    qualified = np.zeros((k, n_pad), bool)
    complaints: list[list[tuple[int, int]]] = [[] for _ in range(k)]
    errors: list[str] = [""] * k
    for i, req in enumerate(fl.reqs):
        qualified[i, : req.n] = True
        if not ok_h[i, : req.n].all():
            # rare blame path, per ceremony: the engine holds the
            # plaintext share matrix, so re-checking IS adjudication
            # (mirrors BatchedCeremony.run)
            with _stage(trace, "blame"):
                pw = np.asarray(
                    ce.verify_pairwise(
                        cfg_pad, fl.e[i], fl.s[i], fl.r[i], fl.g_table, fl.h_table
                    )
                )[: req.n, : req.n]
            guilty = ~pw.all(axis=1)
            complaints[i] = [
                (int(rcp) + 1, int(dlr) + 1) for dlr, rcp in zip(*np.nonzero(~pw))
            ]
            qualified[i, : req.n] = ~guilty
            if int(guilty.sum()) > req.t:
                errors[i] = "MISBEHAVIOUR_HIGHER_THRESHOLD"

    with _stage(trace, "finalise_dispatch"):
        if k == 1:
            # width-1 lanes reuse the plain executables (shared with
            # BatchedCeremony and the rest of the suite's compile cache)
            q0 = jnp.asarray(qualified[0])
            final_shares = _aot_dispatch(
                ("aggregate", curve, n_pad, cfg_pad.t, 1, 0),
                (fl.s[0], q0),
                lambda sp: ce.aggregate_shares.trace(cfg_pad, *sp),
                lambda: ce.aggregate_shares(cfg_pad, fl.s[0], q0),
            )[None]
            master = _aot_dispatch(
                ("master", curve, n_pad, cfg_pad.t, 1, 0),
                (fl.a[0], q0),
                lambda sp: ce.master_key_from_bare.trace(cfg_pad, *sp),
                lambda: ce.master_key_from_bare(cfg_pad, fl.a[0], q0),
            )[None]
        else:
            qd = jnp.asarray(qualified)
            final_shares, master = _aot_dispatch(
                ("finalise", curve, n_pad, cfg_pad.t, k, 0),
                (fl.a, fl.s, qd),
                lambda sp: _finalise_stack.trace(cfg_pad, *sp),
                lambda: _finalise_stack(cfg_pad, fl.a, fl.s, qd),
            )
    with _stage(trace, "finalise_wait"):
        shares_h = np.asarray(final_shares)
        master_h = np.asarray(master)

    with _stage(trace, "encode"):
        master_enc = gd.encode_batch(cfg_pad.cs, master_h)
        out = []
        for i, req in enumerate(fl.reqs):
            failed = bool(errors[i])
            out.append(
                CeremonyOutcome(
                    ceremony_id=fl.ids[i],
                    status="failed" if failed else "done",
                    curve=req.curve,
                    n=req.n,
                    t=req.t,
                    bucket_n=cfg_pad.n,
                    bucket_t=cfg_pad.t,
                    master=b"" if failed else master_enc[i].tobytes(),
                    qualified=tuple(bool(q) for q in qualified[i, : req.n]),
                    complaints=tuple(complaints[i]),
                    error=errors[i],
                    final_shares=None if failed else shares_h[i, : req.n],
                )
            )
    return out


def _release_staged(runtime: WarmRuntime, fl: InFlight) -> None:
    """Deal has run: nothing reads the convoy's kept coefficient tensors
    any more, and the next draw may overwrite them."""
    if fl.staged is not None:
        runtime.staging.give_back(fl.staged)
        fl.staged = None


def _finish_sharded(runtime: WarmRuntime, fl: InFlight) -> list[CeremonyOutcome]:
    """:func:`finish_convoy` for a request on the sharded route: the
    phases of ``parallel.mesh.run_sharded_ceremony`` (its own functions,
    not copies) under the convoy's stage spans, and an outcome laid out
    as the one-device route's, a failed batch check's included.

    Stage by stage: ``deal_wait`` waits for the two deal programs
    (commitments, then shares; ``mesh_collective_seconds{op}`` as
    ``run_sharded_ceremony`` books it, here from deal's dispatch, so
    after a hold it includes the hold); ``digest_dispatch`` is
    ``mesh_digest_rows``'s dispatch (on the host leg the whole numpy
    digest, shard by shard), ``digest_wait`` the fetch of its
    three (n, 8) row arrays, ``rho_fold`` the fold and the n lanes of
    rho; ``verify_dispatch`` takes the bare first columns, places rho
    and dispatches ``mesh_verify_finalise``, which verifies AND
    aggregates, so ``finalise_dispatch`` has nothing left to dispatch
    and ``finalise_wait`` fetches the final shares and the master key.
    ``blame`` is ``parallel.mesh.adjudicate`` over the request's own
    (n, t): the complaints and the guilty dealers are reported whether
    or not enough dealers are left to finalise."""
    (req,) = fl.reqs
    cfg, mesh, trace = fl.cfg_pad, fl.mesh, fl.trace
    with _stage(trace, "deal_wait"):
        jax.block_until_ready((fl.a, fl.e))
        t_e = time.perf_counter()
        jax.block_until_ready((fl.s, fl.r))
        t_s = time.perf_counter()
    _release_staged(runtime, fl)
    pm.book_phase("deal_commitments", t_e - fl.dispatched_at)
    pm.book_phase("deal_shares", t_s - t_e)
    with _stage(trace, "digest_dispatch"):
        rows = pm.transcript_rows(cfg, mesh, fl.a, fl.e, fl.s, fl.r, stored_mesh_program)
    with _stage(trace, "digest_wait"):
        rows = jax.device_get(list(rows))
    with _stage(trace, "rho_fold"):
        rho = pm.rho_from_rows(cfg, rows, req.rho_bits)
    t_rho = time.perf_counter()
    pm.book_phase("transcript_digest", t_rho - t_s)
    with _stage(trace, "verify_dispatch"):
        # only the bare first columns survive the digest
        a0 = fl.a[:, 0]
        fl.a = None
        ok, final_shares, master = pm.sharded_verify_finalise(
            cfg, mesh, a0, fl.e, fl.s, fl.r, fl.g_table, fl.h_table,
            pm.place_replicated(mesh, rho), req.rho_bits, stored_mesh_program,
        )
    with _stage(trace, "verify_wait"):
        ok_h = np.asarray(ok)
    pm.book_phase("verify_finalise", time.perf_counter() - t_rho)
    qualified = np.ones((req.n,), bool)
    complaints: list[tuple[int, int]] = []
    if not ok_h[: req.n].all():
        with _stage(trace, "blame"):
            t_blame = time.perf_counter()
            pw, qual_h, final_shares, master = pm.adjudicate(
                cfg, mesh, a0, fl.e, fl.s, fl.r, fl.g_table, fl.h_table,
                real=(req.n, req.t), run=stored_mesh_program,
            )
            complaints = [
                (int(rcp) + 1, int(dlr) + 1)
                for dlr, rcp in zip(*np.nonzero(~pw[: req.n, : req.n]))
            ]
            qualified = qual_h[: req.n]
            pm.book_phase("blame", time.perf_counter() - t_blame)
    failed = final_shares is None  # more than t dealers out: nothing was finalised
    with _stage(trace, "finalise_dispatch"):
        pass  # the mesh's verify program (or blame's re-finalise) aggregated already
    with _stage(trace, "finalise_wait"):
        if not failed:
            shares_h = np.asarray(final_shares)
            master_h = np.asarray(master)
    with _stage(trace, "encode"):
        return [
            CeremonyOutcome(
                ceremony_id=fl.ids[0],
                status="failed" if failed else "done",
                curve=req.curve,
                n=req.n,
                t=req.t,
                bucket_n=cfg.n,
                bucket_t=cfg.t,
                master=b"" if failed else gd.encode_batch(cfg.cs, master_h[None])[0].tobytes(),
                qualified=tuple(bool(q) for q in qualified),
                complaints=tuple(complaints),
                error="MISBEHAVIOUR_HIGHER_THRESHOLD" if failed else "",
                final_shares=None if failed else shares_h[: req.n],
            )
        ]


def run_convoy(runtime: WarmRuntime, reqs: list) -> list[CeremonyOutcome]:
    """start + finish in one call (the unpipelined entry point)."""
    return finish_convoy(runtime, start_convoy(runtime, reqs))


def run_single_reference(req: CeremonyRequest) -> bytes:
    """A FRESH unpadded single-ceremony run of ``req`` (the oracle the
    service legs are compared against): BatchedCeremony with the same
    seeded rng, master key canonically encoded."""
    c = ce.BatchedCeremony(
        req.curve, req.n, req.t, req.shared_string, rng_for(req)
    )
    out = c.run(rho_bits=req.rho_bits)
    if "master" not in out:
        raise PoisonedRequest(f"reference ceremony failed: {out.get('error')}")
    cs = c.cfg.cs
    return gd.encode_batch(cs, np.asarray(out["master"])[None])[0].tobytes()


# ---------------------------------------------------------------------------
# wire-format leg (padded KEM/DEM, real-lane slice)
# ---------------------------------------------------------------------------


def wire_broadcasts(
    runtime: WarmRuntime,
    req: CeremonyRequest,
    fl: InFlight,
    lane: int,
    pks: list,
    rng_enc,
) -> list[bytes]:
    """Wire-format ``BroadcastPhase1`` bytes for one convoy lane, sealed
    to the ``req.n`` recipient communication keys ``pks``.

    The KEM runs at the BUCKET shape so it shares executables with every
    other ceremony in the bucket: encryption randomness is drawn for the
    real ``(n, n)`` block (same draw order as the unpadded leg) and
    padded with ones, phantom recipient keys with the generator — then
    the real sub-block of the sealed output is packaged.  Byte-identical
    to the unpadded ``seal_shares_pipeline`` leg (oracle test)."""
    from ..dkg.hybrid_batch import broadcasts_from_batch, seal_shares_pipeline
    from ..utils import serde

    cfg_pad = fl.cfg_pad
    cs = cfg_pad.cs
    fs = cs.scalar
    group = gh.ALL_GROUPS[req.curve]
    n, n_pad = req.n, cfg_pad.n
    r_real = fh.draw_limbs(fs, rng_enc, (n, n))
    r_pad = np.zeros((n_pad, n_pad, fs.limbs), np.uint32)
    r_pad[..., 0] = 1  # phantom lanes: r=1 (a zero KEM scalar has no inverse)
    r_pad[:n, :n] = r_real
    pks_dev = gd.from_host(cs, list(pks) + [group.generator()] * (n_pad - n))
    sealed = seal_shares_pipeline(
        group, cfg_pad, np.asarray(fl.s[lane]), np.asarray(fl.r[lane]),
        pks_dev, jnp.asarray(r_pad), fl.g_table,
    )
    real_rows = [row[:n] for row in sealed[:n]]
    # slice the coefficient axis too: a real dealer's padded high
    # coefficients are commitments to zero (identity points) that the
    # unpadded wire message does not carry
    bcasts = broadcasts_from_batch(
        group, cfg_pad, np.asarray(fl.e[lane])[:n, : req.t + 1], real_rows
    )
    return [serde.encode_phase1(group, b) for b in bcasts]
