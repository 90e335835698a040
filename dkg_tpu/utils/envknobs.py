"""Validated environment-knob parsing shared across modules, and the
one list of the knobs that shape a traced program.

Every DKG_TPU_* knob that silently mis-parsing could turn into a wrong
(possibly OOM or wrong-kernel) compiled program goes through here, so
the validate-and-raise behavior cannot drift between copies.  The
knobs read under a tracer are :data:`PROGRAM_SHAPING`, each with its
reader beside it; the others: DKG_TPU_DEM (scalar|batch host DEM leg)
via dkg.hybrid_batch, DKG_TPU_TABLE_CACHE via
groups.precompute, DKG_TPU_NET_* transport knobs via net.channel,
DKG_TPU_SIGN_BATCH (device message-chunk size) and
DKG_TPU_SIGN_DISPATCH (device|host partial-signature leg) via
sign.partial — lint rule DKG009 bans raw environment access and
per-message scalar-mul loops in dkg_tpu/sign/ hot paths,
DKG_TPU_CHECKPOINT_DIR via net.checkpoint,
DKG_TPU_OBSLOG flight-recorder log directory via utils.obslog,
DKG_TPU_SERVICE_CONCURRENCY / DKG_TPU_SERVICE_QUEUE_DEPTH /
DKG_TPU_SERVICE_BATCH_MAX / DKG_TPU_SERVICE_DEADLINE_S /
DKG_TPU_SERVICE_WAL_DIR / DKG_TPU_SERVICE_RETRIES (transient-fault
convoy retries, 0 disables) / DKG_TPU_SERVICE_RETRY_BACKOFF_S (first
backoff, doubling) / DKG_TPU_SERVICE_MAX_REPLAYS (journal crash-loop
guard) scheduler knobs via service.scheduler — lint rule DKG007 bans
any other environment access in dkg_tpu/service/,
DKG_TPU_RUNTIMEOBS (on|off — JAX compile/cache/memory introspection)
via utils.runtimeobs,
DKG_TPU_SERVICE_HTTP_PORT (observability HTTP port; 0 binds an
ephemeral port, unset keeps the scrape surface off) via
service.httpobs,
DKG_TPU_SLO_WINDOW_S / DKG_TPU_SLO_ERROR_BUDGET /
DKG_TPU_SLO_CEREMONY_P99_S / DKG_TPU_SLO_SIGN_P99_S (rolling SLO
window, allowed failure ratio, and latency objectives) via
service.slo,
DKG_TPU_SIGN_RLC_DISPATCH (host|device RLC combine leg) via
sign.verify,
DKG_TPU_SIGN_MESH (0|1|force — shard the steady lane's folded sign
ladder over the device mesh; 1 engages only where shards run
concurrently (accelerator backend or a multi-core host), force on any
>=2-device mesh; the Mesh handle and shard_map live in
parallel.signmesh, per lint rule DKG015) via parallel.signmesh,
DKG_TPU_EPOCH_MAX_CHURN (leave+join budget a reshare accepts; 0
refuses any membership change) and DKG_TPU_EPOCH_DEADLINE_S
(per-epoch-round fetch timeout) via dkg_tpu.epoch.manager — lint
rule DKG008 likewise bans raw environment access in dkg_tpu/epoch/,
DKG_TPU_AOT_DIR (AOT-serialized executable store directory; unset
keeps the store off) via service.aot,
DKG_TPU_AOT_TOPOLOGY (chip-less topology scripts/aot_lab.py compiles
against, default v5e:2x2),
DKG_TPU_FLEET_PROCS (initial worker-process count) /
DKG_TPU_FLEET_MIN / DKG_TPU_FLEET_MAX (autoscale floor/ceiling) /
DKG_TPU_FLEET_CONTROL_S (control-loop period; unset disables the
loop) / DKG_TPU_FLEET_HTTP_PORT (front-door port; 0 binds an
ephemeral port, unset keeps the fleet python-API only) via
service.fleet,
DKG_TPU_FLEET_WAL_DIR (per-slot fleet journal root: slot NNN's workers
journal into <root>/slotNNN and a replacement worker recovers from it;
unset disables worker failover — reaped workers' placements are
evicted) / DKG_TPU_FLEET_RESPAWN_BACKOFF_S (backoff before a slot's
SECOND respawn, doubling per further death, capped; the first respawn
is immediate; default 0.5) / DKG_TPU_FLEET_RESPAWN_MAX (deaths within
the window before a slot is quarantined instead of respawned, default
3 — the fleet mirror of DKG_TPU_SERVICE_MAX_REPLAYS) /
DKG_TPU_FLEET_RESPAWN_WINDOW_S (rolling crash-loop window, default
60) / DKG_TPU_FLEET_SUBMIT_RETRY_S (pause before submit's one retry
against the replacement or ring-next worker, default 0.05) via
service.fleet).

An EMPTY value is everywhere treated as unset: ``DKG_TPU_X= cmd`` is
the shell idiom for clearing a knob on one invocation, and must select
the default path, not raise.
"""

from __future__ import annotations

import os

#: Every knob a traced function reads: its value is baked into the
#: compiled program at fixed shapes.  Whatever keeps a program past the
#: trace that made it — the memoized sharded builders of parallel/mesh
#: and parallel/signmesh, the AOT executable store's digest header
#: (service/aot) — keys on :func:`program_shape`, so a knob flipped
#: between calls, or between two processes sharing a store, retraces
#: instead of being served the other side's program.  A new knob read
#: under a tracer is added HERE and nowhere else
#: (tests/test_program_shape.py scans the traced sources for names
#: missing from this tuple).
PROGRAM_SHAPING = (
    "DKG_TPU_ASSUME_BACKEND",  # fields.device._on_tpu: every backend-matched default
    "DKG_TPU_PALLAS",  # fields.device.fused_kernels_active
    "DKG_TPU_MUL",  # fields.device.mul_dispatch_mode, ops.pallas_field
    "DKG_TPU_MXU",  # fields.matmul.mxu_matmul_active
    "DKG_TPU_MSM",  # groups.device: MSM algorithm
    "DKG_TPU_RLC",  # dkg.ceremony._point_rlc schedule
    "DKG_TPU_RLC_CHUNK",  # dkg.ceremony._point_rlc column chunk
    "DKG_TPU_DIGEST",  # crypto.device_hash.digest_dispatch
)


def program_shape() -> tuple:
    """``(name, value)`` of every SET knob of :data:`PROGRAM_SHAPING`,
    in its order (empty is unset, as everywhere here).  Raw values: a
    key has to tell two programs apart, not validate — the reader
    raises on a typo when it traces."""
    return tuple((name, v) for name in PROGRAM_SHAPING if (v := os.environ.get(name)))


def choice(name: str, options: tuple, what: str) -> str | None:
    """None when ``name`` is unset (or empty), else its value validated
    against ``options`` (a tuple of accepted strings).

    Raises ValueError on anything else — enum knobs select compiled
    kernel paths (MSM algorithm, RLC schedule, fused dispatch), where a
    typo must fail loudly rather than silently run the default path.
    """
    env = os.environ.get(name)
    if not env:
        return None
    if env not in options:
        raise ValueError(
            f"{name}={env!r}: expected one of "
            f"{', '.join(repr(o) for o in options)} ({what})"
        )
    return env


def nonneg_int(name: str, what: str) -> int | None:
    """None when ``name`` is unset, else its value as an int >= 0.

    Raises ValueError on anything else — a typo must fail loudly, never
    silently select a default.  ``what`` explains the zero semantics in
    the error message (e.g. "0 disables chunking").
    """
    env = os.environ.get(name)
    if not env:
        return None
    try:
        v = int(env)
    except ValueError:
        v = -1
    if v < 0:
        raise ValueError(
            f"{name}={env!r}: expected a non-negative integer ({what})"
        )
    return v


def pos_int(name: str, what: str) -> int | None:
    """None when ``name`` is unset, else its value as an int >= 1."""
    env = os.environ.get(name)
    if not env:
        return None
    try:
        v = int(env)
    except ValueError:
        v = 0
    if v < 1:
        raise ValueError(f"{name}={env!r}: expected a positive integer ({what})")
    return v


def pos_float(name: str, what: str) -> float | None:
    """None when ``name`` is unset, else its value as a finite float > 0."""
    env = os.environ.get(name)
    if not env:
        return None
    try:
        v = float(env)
    except ValueError:
        v = -1.0
    if not v > 0 or v != v or v == float("inf"):
        raise ValueError(f"{name}={env!r}: expected a positive finite number ({what})")
    return v


def string(name: str, what: str) -> str | None:
    """None when ``name`` is unset or empty, else its raw value.

    For free-form knobs (paths, labels) where any non-empty string is
    valid; exists so every DKG_TPU_* parse shares the one empty-is-unset
    convention instead of re-implementing ``if env:`` truthiness.
    ``what`` documents the knob for grep (e.g. "table cache directory").
    """
    del what  # documentation-only, kept for signature parity
    return os.environ.get(name) or None


def nonneg_float(name: str, what: str) -> float | None:
    """None when ``name`` is unset, else its value as a finite float >= 0."""
    env = os.environ.get(name)
    if not env:
        return None
    try:
        v = float(env)
    except ValueError:
        v = -1.0
    if not v >= 0 or v == float("inf"):
        raise ValueError(
            f"{name}={env!r}: expected a non-negative finite number ({what})"
        )
    return v
