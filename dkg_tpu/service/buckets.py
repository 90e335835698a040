"""Shape-bucketing policy: pad requested ``(n, t)`` into a small ladder.

Every distinct ``(n, t)`` jitted at its exact shape is one more program
set in the compile cache — and a compile on this workload costs minutes,
not milliseconds (a cold n=16 secp256k1 ceremony compiles for ~2 min on
a laptop-class CPU while the warm run takes half a second).  A service
facing arbitrary committee sizes therefore cannot jit per request: it
pads every request up to a canonical *bucket* so thousands of distinct
shapes share a handful of executables.

Policy (deliberately tiny, so the whole ladder stays warm):

* ``n`` rounds up to the next power of two, floored at
  :data:`MIN_BUCKET_N` — committee sizes 9..16 share one program set,
  17..32 the next, and so on.
* ``t`` rounds up to the smallest rung of ``n_pad/4``, ``n_pad/3``,
  ``(n_pad-1)/2`` — the three threshold regimes real deployments use
  (light, standard ~n/3, maximal honest-majority).  A ``t`` beyond the
  maximal rung (degenerate, but legal in the engine) escalates to the
  next ``n`` bucket.
* convoy widths (how many same-bucket ceremonies stack on the ceremony
  axis) come from the fixed ladder :data:`WIDTHS`; ragged convoys are
  split greedily (k=7 -> 4+2+1) instead of padded with phantom
  ceremonies, so batching never wastes compute — only compiles from the
  ladder exist.

Correctness of padding is the engine's pad-and-mask contract
(:meth:`dkg_tpu.dkg.ceremony.CeremonyConfig.padded`): phantom lanes are
zero-coefficient dealers whose shares are zero and whose commitments
are the identity; the real lanes' outputs are bit-identical to the
unpadded run (oracle tests in tests/test_service.py).
"""

from __future__ import annotations

import dataclasses

#: Smallest n bucket: ceremonies below this pad up to it.  Eight lanes
#: is already enough to keep the batched kernels' vector shapes sane.
MIN_BUCKET_N = 8

#: Largest n bucket the policy will emit.  Requests beyond this are the
#: north-star single-ceremony regime (sharded engine), not service
#: traffic.
MAX_BUCKET_N = 4096

#: Stacked-lane width ladder (descending).  Only these convoy widths
#: ever compile; see :func:`split_widths`.
WIDTHS = (8, 4, 2, 1)

#: Stacking crossover: buckets at or above this ``n`` run width-1
#: convoys.  Stacking pays while per-dispatch overhead is a meaningful
#: fraction of one ceremony's compute; fleet calibration (single-core
#: CPU, secp256k1, width 8) measured 1.65x at the (16,5) bucket, 1.27x
#: at (32,8), and a 0.95x LOSS at (64,16), where compute dominates and
#: the vmapped lane only adds overhead.  Capping also halves the warm
#: compile set for the heavy buckets (no stacked programs to build).
WIDTH_CAP_N = 64


#: Sharding crossover: a bucket at or above this ``n`` is served by the
#: ``shard_map`` programs of :mod:`dkg_tpu.parallel.mesh` over every
#: local device, where the process has at least
#: :data:`SHARD_MIN_DEVICES` and their count divides ``n``
#: (:func:`shard_devices`).  Below it one chip serves a request in
#: seconds ((1024,341): 2.1 s) and sharding it would buy little; at
#: (4096,1365) one chip's programs reckon to over 30 s a request and the
#: round-1 tensors to 4.3 GB (PERF.md section 4).
SHARD_MIN_N = 2048

#: Fewest local devices worth a mesh (one v5e host has four).
SHARD_MIN_DEVICES = 4


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One canonical padded shape.  Hashable — used as a compile/convoy
    key together with the curve."""

    n: int
    t: int

    @property
    def label(self) -> str:
        """``"{n}x{t}"``: the ``bucket`` label of the scheduler's series
        (docs/observability.md), which the benchmark's readers match."""
        return f"{self.n}x{self.t}"


def _next_pow2(v: int) -> int:
    return 1 << max(v - 1, 1).bit_length()


def t_rungs(n_pad: int) -> tuple[int, ...]:
    """The threshold rungs available at an ``n`` bucket, ascending."""
    return tuple(sorted({n_pad // 4, n_pad // 3, (n_pad - 1) // 2}))


def bucket_for(n: int, t: int) -> Bucket:
    """The canonical bucket dominating ``(n, t)``.

    Raises ValueError for shapes no bucket dominates (n out of range, or
    t >= n which no DKG admits).
    """
    if n < 2 or n > MAX_BUCKET_N:
        raise ValueError(f"bucket_for: n={n} outside [2, {MAX_BUCKET_N}]")
    if t < 1 or t >= n:
        raise ValueError(f"bucket_for: t={t} outside [1, n-1] for n={n}")
    n_pad = max(MIN_BUCKET_N, _next_pow2(n))
    while n_pad <= MAX_BUCKET_N:
        for rung in t_rungs(n_pad):
            if rung >= t:
                return Bucket(n_pad, rung)
        n_pad *= 2
    raise ValueError(f"bucket_for: no bucket dominates (n={n}, t={t})")


def width_cap(b: Bucket) -> int:
    """Largest convoy width worth stacking for ``b`` (a ladder value).

    The scheduler takes ``min(batch_max, width_cap(bucket))`` when it
    pops a convoy, so operators tune ``batch_max`` downward only —
    the cap already excludes the shapes where stacking is a measured
    loss (see :data:`WIDTH_CAP_N`).
    """
    return 1 if b.n >= WIDTH_CAP_N else WIDTHS[0]


def _local_device_count() -> int:
    import jax

    return jax.local_device_count()


def shard_devices(b: Bucket) -> int:
    """How many local devices bucket ``b`` is sharded over; 0 for the
    one-device route.  A constant and a function of the bucket and the
    process's devices, nothing else: no switch, no scheduler argument.
    A bucket under :data:`SHARD_MIN_N` never asks for the devices, so
    what its process can see does not touch it."""
    if b.n < SHARD_MIN_N:
        return 0
    devices = _local_device_count()
    return devices if devices >= SHARD_MIN_DEVICES and b.n % devices == 0 else 0


#: Message-count rungs for the sign lane (descending).  Only these
#: batch shapes ever enter the ladder/MSM executables, so mixed sign
#: traffic from any ceremony shares one warm program per (curve, rung).
#: The ladder deliberately includes the small rungs (2, 1): existing
#: callers with tiny batches keep their exact compiled shapes — a
#: convoy of 2 runs as [2], not [1, 1] — and tail slices of big convoys
#: reuse them instead of padding with phantom messages (a phantom
#: message costs a full ladder lane; an extra warm narrow dispatch is
#: microseconds).
SIGN_RUNGS = (256, 64, 16, 4, 2, 1)


def sign_rung_slices(total: int, batch_max: int = SIGN_RUNGS[0]) -> list[tuple[int, int]]:
    """Greedy ``(start, stop)`` decomposition of ``total`` queued sign
    messages into :data:`SIGN_RUNGS` shapes, each at most ``batch_max``
    (total=21 -> [(0, 16), (16, 20), (20, 21)]).  The sign-lane analogue
    of :func:`split_widths`, over the message axis instead of the
    ceremony axis."""
    if total < 0:
        raise ValueError(f"sign_rung_slices: total={total} < 0")
    out: list[tuple[int, int]] = []
    at = 0
    for w in SIGN_RUNGS:
        if w > batch_max:
            continue
        while total - at >= w:
            out.append((at, at + w))
            at += w
    return out


def split_widths(k: int, batch_max: int = WIDTHS[0]) -> list[int]:
    """Greedy decomposition of a convoy of ``k`` ceremonies into ladder
    widths, each at most ``batch_max`` (k=7 -> [4, 2, 1]).  Splitting
    instead of padding: a phantom ceremony costs a full ceremony's
    compute, while one extra (already-compiled) narrower program costs
    only its dispatch."""
    if k < 0:
        raise ValueError(f"split_widths: k={k} < 0")
    out: list[int] = []
    for w in WIDTHS:
        if w > batch_max:
            continue
        while k >= w:
            out.append(w)
            k -= w
    return out
