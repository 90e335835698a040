"""Device-sharded steady sign lane: the folded sigma*H(m) ladder over a
mesh batch axis.

The scheduler's fast leg signs every unproved ticket's messages with
ONE ladder dispatch per rung (sign.partial.sign_folded) — the ladder is
batch-elementwise, so a rung-512/1024 shape shards embarrassingly over
the device axis.  This module owns the mesh handle and the shard_map
(lint rule DKG015 confines ``Mesh``/``PartitionSpec``/``shard_map``
construction to dkg_tpu/parallel/ — call sites take a mesh handle),
gated behind ``DKG_TPU_SIGN_MESH`` (``1`` = engage where sharding can
win, ``force`` = engage on any >=2-device mesh; validated via
utils.envknobs — the scheduler never reads the environment itself, per
DKG007).

Bit-exactness: sharding a batch-elementwise ladder changes nothing but
the device each row runs on, so the sharded rung is limb-identical to
the single-device rung — byte-checked against the host ``secret*H(m)``
oracle every ``scripts/sign_bench.py --steady`` run.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..groups import device as gd
from ..utils import envknobs
from . import mesh as pm


def sign_mesh() -> "pm.Mesh | None":
    """The sign lane's device mesh, or None when the lane should stay
    single-device: knob off/unset, fewer than two devices visible, or
    (``1``, the auto setting) no parallel capacity behind the devices.

    The folded ladder is DEPTH-dominated — every shard pays the full
    rung-iteration chain while the batch rows ride the vector lanes
    nearly free — so sharding only wins where shards actually run
    concurrently.  On a real accelerator mesh they do; on a
    host-count-forced CPU mesh the virtual devices share the box's
    cores, and with a single core the 8 shard programs serialise into
    ~3x the single-device wall clock (measured: 1.0 s vs 0.38 s per
    width-64 rung).  ``1`` therefore engages only when the backend is
    an accelerator or the host has at least two cores; ``force``
    engages on any >=2-device mesh regardless — the setting
    byte-exactness checks and real-mesh runs use.

    Cheap enough to resolve per convoy (jax caches the device list), so
    the scheduler holds no stale handle across a hostmesh re-force.
    """
    knob = envknobs.choice(
        "DKG_TPU_SIGN_MESH",
        ("0", "1", "force"),
        "device-sharded folded sign ladder",
    )
    if knob not in ("1", "force"):
        return None
    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    if knob == "1" and jax.default_backend() == "cpu" and (
        os.cpu_count() or 1
    ) < 2:
        return None
    return pm.make_mesh(n_dev)


def sign_folded_sharded(curve: str, sigma_limbs, h_dev, mesh: pm.Mesh):
    """sign.partial.sign_folded over ``mesh``'s device axis.

    Pads the batch up to a multiple of the mesh size with zero rows
    (zero scalar bits leave the ladder accumulator at the identity; the
    phantom rows are sliced off before return), shards the (B, L)
    sigma rows and (B, C, L) H(m) points on the batch axis, and runs
    the ladder shard-locally — no collectives, pure map.  Returns the
    RAW device (B, C, L) result exactly like ``sign_folded``, so the
    scheduler's rung pipeline (``folded_collect`` after every rung is
    in flight) works unchanged.
    """
    cs = gd.ALL_CURVES[curve]
    hh = jnp.asarray(h_dev)
    kk = jnp.asarray(sigma_limbs)
    if kk.ndim == 1:
        kk = jnp.broadcast_to(kk[None, :], (hh.shape[0], kk.shape[-1]))
    b = hh.shape[0]
    n_dev = int(mesh.devices.size)
    pad = (-b) % n_dev
    if pad:
        kk = jnp.concatenate(
            [kk, jnp.zeros((pad,) + kk.shape[1:], kk.dtype)], axis=0
        )
        hh = jnp.concatenate(
            [hh, jnp.zeros((pad,) + hh.shape[1:], hh.dtype)], axis=0
        )

    out = _ladder_prog(curve, mesh, envknobs.program_shape())(kk, hh)
    return out[:b] if pad else out


@functools.lru_cache(maxsize=None)
def _ladder_prog(curve: str, mesh: "pm.Mesh", knobs: tuple):
    """Memoized, jitted sharded ladder — the steady lane dispatches one
    rung per call, so a per-call shard_map closure would retrace every
    rung (``knobs`` is cache key only, same discipline as mesh.py's
    program builders; jit's own cache covers varying rung widths)."""
    del knobs
    cs = gd.ALL_CURVES[curve]

    @jax.jit
    @functools.partial(
        pm._shard_map_nocheck,
        mesh=mesh,
        in_specs=(pm.P(pm.PARTY_AXIS), pm.P(pm.PARTY_AXIS)),
        out_specs=pm.P(pm.PARTY_AXIS),
    )
    def step(k, h):
        return gd.scalar_mul(cs, k, h)

    return step
