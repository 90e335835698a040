"""Multi-chip DKG: participants sharded over a device mesh.

The reference leaves the broadcast channel abstract — callers shuttle
`Option<BroadcastPhaseN>` arrays between parties (reference:
committee.rs:825-871, lib.rs:91-92).  On a TPU pod slice that seam maps
onto XLA collectives over ICI (SURVEY §2 table, §5):

* round-1 "publish commitments, everyone fetches" -> ``all_gather`` of
  the commitment limb tensors across the party-sharded mesh axis;
* per-recipient encrypted-share delivery -> ``all_to_all`` of the
  (dealer, recipient) share matrix (dealer-sharded -> recipient-sharded);
* master-key assembly -> every shard reduces the gathered bare
  commitments (or a ``psum``-style tree on point limbs).

Multi-host ceremonies ride the same code: a global mesh over all hosts'
devices puts DCN under the same collectives, with the external
blockchain boundary staying host-side exactly like the reference leaves
it to the caller.
"""

from __future__ import annotations

import functools
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map as _shard_map


def _shard_map_nocheck(f, *, mesh, in_specs, out_specs):
    """shard_map with the VMA (replication) check disabled (named so a
    future call site wanting jax's checked semantics doesn't silently
    get this wrapper)."""
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )

from ..dkg import ceremony as ce
from ..groups import device as gd
from ..utils import envknobs
from jax import lax

PARTY_AXIS = "parties"

#: The mesh programs can be served from the executable store: every
#: phase function below takes ``run``, how its program is run, and the
#: served route passes the store's seam (``service/engine.py``
#: ``stored_mesh_program``), so a process that finds the programs there
#: traces and compiles nothing and a sharded bucket can be prepared
#: inside a serving process's set-up.  The benchmark's sharded cell asks
#: for this attribute before it builds anything.
SERVED_FROM_STORE = True

_LOG = logging.getLogger(__name__)

_SHARDED, _REPLICATED = P(PARTY_AXIS), P()

#: Each mesh program's operand layout, by the name its XLA module and its
#: store key carry: what its ``shard_map`` is given as ``in_specs``, and
#: what a stored executable's operands have to be placed under.
IN_SPECS = {
    "mesh_deal_commitments": (_SHARDED, _SHARDED, _REPLICATED, _REPLICATED),
    "mesh_deal_shares": (_SHARDED, _SHARDED),
    "mesh_digest_rows": (_SHARDED,) * 4,
    "mesh_verify_finalise": (_SHARDED,) * 4 + (_REPLICATED,) * 3,
    "mesh_finalise": (_SHARDED, _SHARDED, _REPLICATED),
    "mesh_blame": (_SHARDED,) * 3 + (_REPLICATED,) * 2,
}

# The memoized program builders below put envknobs.program_shape() —
# the knobs read at TRACE time and baked into the compiled sharded
# programs — into their cache key, so flipping a knob between calls
# retraces (the semantics per-call eager tracing always had) while a
# steady-state rerun at stable knobs reuses the jitted executable
# instead of recompiling the whole sharded program set: before this
# cache the north-star warm run cost the same as the cold one
# (NORTHSTAR r01 measured warm 135.6 s vs cold 126.0 s at (16, 5) on
# the CPU mesh — pure retrace).


def _verify_chunk_default(cfg: ce.CeremonyConfig, block: int) -> int:
    """Recipient-axis chunk width for the sharded verify/finalise body.

    The round-2 share delivery moves the (n, block, L) u32 share matrix
    through an ``all_to_all`` whose send AND recv buffers are live
    temps, and the same tensor is then copied into ``aggregate_shares``
    and padded by the MXU matmul digitizer — at BLS n=16384/8 devices
    each of those is ~2 GB, and the TPU buffer assigner fragmented them
    into a 48.62 G program (MEMPROOF_TPU round 4, vs 15.75 G HBM).
    Chunking the recipient axis bounds every one of those temps at once:
    per chunk the a2a moves (n, w, L), the aggregate carries (w, L),
    and the digitizer pads (w, n, L)-shaped operands.

    Budget: recv buffer n * w * L * 4 B <= 128 MiB, floored to a power
    of two so full chunks share one program, clamped to [1, block].

    At (4096,1365) over four devices (block 1024, the sharded cell's
    shape) that is 512: two turns of the loop, two ``all_to_all`` of
    134 MB each a turn.  Lowered for ``v5e:2x2`` the program takes
    1.36 GB of temps so, 1.25 GB unchunked: at this size the rule bounds
    nothing — it is the n=16384 shapes it was made for.  The rule is the
    one width a program is traced at: there is no switch beside it
    (``DKG_TPU_VERIFY_CHUNK`` went at PR 44; a test that wants another
    width patches this function).  >= block means unchunked.
    """
    fs = cfg.cs.scalar
    per_recipient = cfg.n * fs.limbs * 4
    w = max(1, (128 << 20) // per_recipient)
    w = 1 << max(0, w.bit_length() - 1)
    return min(w, block)


def _run_program(run, kind: str, cfg: ce.CeremonyConfig, mesh: Mesh, rho_bits: int, prog, args):
    """One mesh program on ``args``: the jitted ``prog`` itself, or, where
    the caller passed ``run``, through it —
    ``run(kind, cfg, mesh, rho_bits, prog, args)``, the served route's
    seam to the executable store (``service/engine.py``
    ``stored_mesh_program``).  This module knows no store."""
    return prog(*args) if run is None else run(kind, cfg, mesh, rho_bits, prog, args)


def book_phase(op: str, seconds: float, registry=None) -> None:
    """``mesh_collective_seconds{op}``: one sharded phase's wall clock,
    the same series from :func:`run_sharded_ceremony` and from the served
    route (``service/engine.py``)."""
    if registry is None:
        from ..utils import metrics as _metrics

        registry = _metrics.REGISTRY
    registry.observe("mesh_collective_seconds", seconds, op=op)


def make_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over the party axis (v5e-8: 8 shards, 512 parties/shard
    at n=4096 — SURVEY §2 table row 4).

    Raises rather than truncating when fewer than ``n_devices`` devices
    exist (e.g. the backend initialised before hostmesh forcing took
    effect) — a silently smaller mesh would make sharding "tests" pass
    without exercising the collectives.
    """
    devs = jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise RuntimeError(
                f"requested a {n_devices}-device mesh but only {len(devs)} "
                "devices exist (was the jax backend initialised before "
                "hostmesh.force_cpu_mesh?)"
            )
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (PARTY_AXIS,))


def sharded_deal(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,  # (n, t+1, L) global, sharded on axis 0
    coeffs_b: jax.Array,
    g_table: jax.Array,  # replicated
    h_table: jax.Array,
    run=None,
):
    """Round 1 over the mesh: local dealing, EVERYTHING dealer-sharded.

    Returns (a, e, s, r) all sharded on the dealer axis.  The round-1
    "broadcast" is deliberately NOT an allgather: replicating the
    commitment tensor is what caps committee size (at n=16384, t=5461
    the E tensor alone is ~17 GB — more than a v5e chip's HBM).  What
    verification actually consumes is (a) the rho-combined commitment
    columns, exchanged later as ndev partial point-RLCs of (t+1, C, L)
    each (sharded_verify_finalise), and (b) the transcript digest,
    exchanged as 32-byte per-dealer row digests
    (ce.sharded_transcript_digest) — both O(t + n), not O(n*t).
    """
    a, e = sharded_deal_commitments(cfg, mesh, coeffs_a, coeffs_b, g_table, h_table, run)
    s, r = sharded_deal_shares(cfg, mesh, coeffs_a, coeffs_b, run)
    return a, e, s, r


def sharded_deal_commitments(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    run=None,
):
    """Round-1 commitment program: (A, E), dealer-sharded.

    Dealing runs as TWO sequential programs (this one, then
    :func:`sharded_deal_shares`) so the fixed-base scan's chunk carry
    is freed before the Horner share evaluation allocates its temps —
    the monolithic chunked deal keeps a ~6.5 G temp floor alive next
    to 12.2 G of its own inputs+outputs at BLS n=16384 over 8 devices
    (MEMPROOF_TPU round 5), which no chunk width can fit into a 16 GB
    v5e.  Callers wanting the memory bound must NOT wrap both halves
    in one outer jit — that fuses them back into one program.
    """
    _check_mesh(cfg, mesh)
    return _run_program(
        run, "mesh_deal_commitments", cfg, mesh, 0,
        _deal_commitments_prog(cfg, mesh, envknobs.program_shape()),
        (coeffs_a, coeffs_b, g_table, h_table),
    )


@functools.lru_cache(maxsize=None)
def _deal_commitments_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    """Memoized, jitted round-1 commitment program (``knobs`` is cache
    key only — the trace below re-reads the environment)."""
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_deal_commitments"],
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS)),
    )
    def mesh_deal_commitments(ca, cb, gt, ht):
        # chunked in-trace (lax.map) so the fixed-base scan's padded
        # carry stays bounded per shard — the AOT TPU compile of the
        # one-shot body at BLS n=16384/8 devices was rejected at 21.3 GB
        return ce.deal_commitments_traced_chunked(cfg, ca, cb, gt, ht)

    return mesh_deal_commitments


def sharded_deal_shares(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    run=None,
):
    """Round-1 share program: (s, r), dealer-sharded (second of the two
    sequential deal programs; see :func:`sharded_deal_commitments`)."""
    _check_mesh(cfg, mesh)
    return _run_program(
        run, "mesh_deal_shares", cfg, mesh, 0,
        _deal_shares_prog(cfg, mesh, envknobs.program_shape()),
        (coeffs_a, coeffs_b),
    )


@functools.lru_cache(maxsize=None)
def _deal_shares_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_deal_shares"],
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS)),
    )
    def mesh_deal_shares(ca, cb):
        return ce.deal_shares_traced_chunked(cfg, ca, cb)

    return mesh_deal_shares


def sharded_verify_finalise(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    a0: jax.Array,  # (n, C, L) dealer-sharded BARE first columns A_{j,0}
    e: jax.Array,  # (n, t+1, C, L) dealer-sharded randomized commitments
    s: jax.Array,  # (n, n, L) dealer-sharded share matrix
    r: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    rho: jax.Array,  # (n, L) replicated Fiat-Shamir randomizers
    rho_bits: int,
    run=None,
):
    """Round 2 + finalise over the mesh, commitments never replicated.

    Collectives per shard — O(ndev * t) for the gathered RLC partials
    and O(n * n/ndev) for the share all_to_all; crucially nothing is
    O(n * t), so the layout scales to the n=16384 BASELINE config where
    a replicated E tensor (~17 GB) would not fit in HBM:

    * share delivery dealer-sharded -> recipient-sharded: ``all_to_all``
      of the share/hiding matrices;
    * the rho-combined commitment columns D_l = sum_j rho_j E_{j,l}:
      each shard point-RLCs its OWN dealers with its slice of rho, then
      one ``all_gather`` of the ndev partial (t+1, C, L) column tensors
      + a local tree-add;
    * the master key: local tree-add of the shard's bare A_{j,0} +
      ``all_gather`` of ndev partial points.

    Takes only the BARE FIRST COLUMNS a0 = a[:, 0] (the master key's
    sole input, committee.rs:791-796) rather than the full (n, t+1)
    bare tensor: at BLS n=16384 that keeps a 3.22 G argument out of the
    round-2 program's working set, and lets the engine FREE the full
    bare tensor right after the transcript digest — the happy path
    never reads the other columns.

    Returns (ok, final_shares, master): ok/final_shares
    recipient-sharded, master replicated.
    """
    _check_mesh(cfg, mesh)
    return _run_program(
        run, "mesh_verify_finalise", cfg, mesh, rho_bits,
        _verify_finalise_prog(cfg, mesh, rho_bits, envknobs.program_shape()),
        (a0, e, s, r, g_table, h_table, rho),
    )


@functools.lru_cache(maxsize=None)
def _verify_finalise_prog(
    cfg: ce.CeremonyConfig, mesh: Mesh, rho_bits: int, knobs: tuple
):
    del knobs
    n_dev = mesh.devices.size
    cs = cfg.cs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_verify_finalise"],
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P()),
    )
    def mesh_verify_finalise(a0_sh, e_sh, s_sh, r_sh, gt, ht, rho_all):
        shard = lax.axis_index(PARTY_AXIS)
        block = cfg.n // n_dev
        first = shard * block + 1
        # --- combined commitment columns: partial RLC over local dealers,
        # then gather + tree-add the ndev partials (point sum, NOT psum:
        # limbs don't add elementwise)
        rho_local = lax.dynamic_slice_in_dim(rho_all, shard * block, block, 0)
        d_part = ce._point_rlc(cs, rho_local, e_sh, rho_bits)  # (t+1, C, L)
        d_all = lax.all_gather(d_part, PARTY_AXIS)  # (ndev, t+1, C, L)
        d_comm = gd._tree_reduce(cs, jnp.moveaxis(d_all, 0, -3), n_dev)
        # --- round 2 + aggregation, recipient-chunked: share delivery
        # (all_to_all), RLC batch verification, and the qualified-sum all
        # ride one bounded-width loop so no (n, block, L) temp ever
        # materialises (the round-4 MEMPROOF_TPU 48.6 G blow-up)
        qual = jnp.ones((cfg.n,), bool)  # blame re-finalises separately
        ok, finals = _verify_aggregate_chunked(
            cfg, n_dev, d_comm, s_sh, r_sh, rho_all, rho_bits, gt, ht,
            qual, first, block,
        )
        master = _master_shardlocal(cfg, n_dev, a0_sh, qual, shard, block)
        return ok, finals, master

    return mesh_verify_finalise


def _master_shardlocal(cfg, n_dev, a0_sh, qual, shard, block):
    """Master key inside a shard_map body; a0_sh (block, C, L) are the
    shard's bare A_{j,0} columns.

    Masks them by the shard's slice of the qualified set before
    reducing — same semantics as the single-device
    master_key_from_bare, so the master key and the aggregated shares
    always cover the same dealer set.
    """
    cs = cfg.cs
    q_local = lax.dynamic_slice_in_dim(qual, shard * block, block, 0)
    a0 = gd.select(q_local, a0_sh, gd.identity(cs, (block,)))
    m_part = gd._tree_reduce(cs, a0, block)  # (C, L)
    m_all = lax.all_gather(m_part, PARTY_AXIS)  # (ndev, C, L)
    return gd._tree_reduce(cs, m_all, n_dev)


def _chunked_recipient_loop(n_dev, block: int, chunk: int, run, tensors):
    """Drive ``run(off, w, *slices)`` over recipient-axis chunks.

    ``tensors`` are dealer-sharded (block_d, n, L) arrays whose global
    recipient axis 1 is viewed as (n_dev, block); each chunk passes the
    [off, off+w) slice of EVERY destination's local block, reshaped to
    (block_d, n_dev*w, L) — exactly what a tiled ``all_to_all`` on axis
    1 expects.  The sequential-map/ragged-tail skeleton (and its
    never-unroll invariant) lives in utils.scanchunk.map_chunked;
    outputs are concatenated on the leading (recipient) axis.
    """
    from ..utils.scanchunk import map_chunked

    views = []
    for x in tensors:
        bd = x.shape[0]
        views.append(x.reshape((bd, n_dev, block) + tuple(x.shape[2:])))

    def call(off, w):
        sl = []
        for v in views:
            bd = v.shape[0]
            c = lax.dynamic_slice_in_dim(v, off, w, axis=2)
            sl.append(c.reshape((bd, n_dev * w) + tuple(v.shape[3:])))
        return run(off, w, *sl)

    return map_chunked(block, chunk, call)


def _verify_aggregate_chunked(
    cfg, n_dev, d_comm, s_sh, r_sh, rho, rho_bits, gt, ht, qual, first, block
):
    """Share delivery + RLC batch verify + qualified aggregation, in
    recipient chunks inside a shard_map body.

    One all_to_all per chunk delivers (n, w, L) share/hiding rows; the
    chunk is verified (same equations as ce.verify_batch, shard-local
    recipient indices) and aggregated immediately, so peak live temps
    scale with w, not block.  Bit-identical to the one-shot body: each
    recipient's check and final share read only that recipient's column.
    """
    cs = cfg.cs
    fs = cs.scalar

    def run(off, w, sc, rc):
        s_recv = lax.all_to_all(sc, PARTY_AXIS, split_axis=1, concat_axis=0, tiled=True)
        r_recv = lax.all_to_all(rc, PARTY_AXIS, split_axis=1, concat_axis=0, tiled=True)
        s_rlc = ce._field_dot(fs, rho, s_recv)  # (w, L)
        r_rlc = ce._field_dot(fs, rho, r_recv)
        xs = (first + off + jnp.arange(w, dtype=jnp.uint32)).astype(jnp.uint32)
        rhs = gd.eval_point_poly(cs, d_comm, xs, cfg.index_bits)
        lhs = gd.add(
            cs,
            gd.fixed_base_mul(cs, gt, s_rlc),
            gd.fixed_base_mul(cs, ht, r_rlc),
        )
        return gd.eq(cs, lhs, rhs), ce.aggregate_shares(cfg, s_recv, qual)

    chunk = _verify_chunk_default(cfg, block)
    return _chunked_recipient_loop(n_dev, block, chunk, run, (s_sh, r_sh))


def _aggregate_chunked(cfg, n_dev, s_sh, qual, block):
    """Chunked share delivery + qualified aggregation only (the blame
    re-finalise path: verification already adjudicated)."""

    def run(off, w, sc):
        s_recv = lax.all_to_all(sc, PARTY_AXIS, split_axis=1, concat_axis=0, tiled=True)
        return (ce.aggregate_shares(cfg, s_recv, qual),)

    chunk = _verify_chunk_default(cfg, block)
    (finals,) = _chunked_recipient_loop(n_dev, block, chunk, run, (s_sh,))
    return finals


def sharded_finalise(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    a0: jax.Array,  # (n, C, L) dealer-sharded bare first columns
    s: jax.Array,  # (n, n, L) dealer-sharded
    qualified: jax.Array,  # (n,) replicated dealer mask
    run=None,
):
    """Aggregation + master key only, over an adjudicated qualified set
    (the blame path re-finalise: no verification work — the pairwise
    checks already determined exactly which dealers are out)."""
    _check_mesh(cfg, mesh)
    return _run_program(
        run, "mesh_finalise", cfg, mesh, 0,
        _finalise_prog(cfg, mesh, envknobs.program_shape()),
        (a0, s, qualified),
    )


@functools.lru_cache(maxsize=None)
def _finalise_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs
    n_dev = mesh.devices.size

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_finalise"],
        out_specs=(P(PARTY_AXIS), P()),
    )
    def mesh_finalise(a0_sh, s_sh, qual):
        shard = lax.axis_index(PARTY_AXIS)
        block = cfg.n // n_dev
        finals = _aggregate_chunked(cfg, n_dev, s_sh, qual, block)
        master = _master_shardlocal(cfg, n_dev, a0_sh, qual, shard, block)
        return finals, master

    return mesh_finalise


def sharded_blame(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    e: jax.Array,  # (n, t+1, C, L) dealer-sharded
    s: jax.Array,  # (n, n, L) dealer-sharded
    r: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    run=None,
):
    """Pairwise blame assignment on the mesh -> replicated (n, n) bools.

    The per-pair check g*s_ji + h*s'_ji == sum_l x_i^l E_{j,l} reads
    ONLY dealer-local data (each shard holds its dealers' commitments
    AND the share rows they dealt), so blame needs zero share movement:
    every shard re-checks its own dealers against all n recipients and
    one bool allgather assembles the verdict matrix (the mesh twin of
    ceremony.verify_pairwise / the reference complaint trigger,
    committee.rs:305-317).  Rare-path cost: O(n * n/ndev) fixed-base
    mults per shard.
    """
    _check_mesh(cfg, mesh)
    return _run_program(
        run, "mesh_blame", cfg, mesh, 0,
        _blame_prog(cfg, mesh, envknobs.program_shape()),
        (e, s, r, g_table, h_table),
    )


@functools.lru_cache(maxsize=None)
def _blame_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_blame"],
        out_specs=P(),
    )
    def mesh_blame(e_sh, s_sh, r_sh, gt, ht):
        pw = ce.verify_pairwise(cfg, e_sh, s_sh, r_sh, gt, ht)  # (block, n)
        return lax.all_gather(pw, PARTY_AXIS, tiled=True)  # (n, n)

    return mesh_blame


def transcript_rows(cfg: ce.CeremonyConfig, mesh: Mesh, a, e, s, r, run=None):
    """Phase 2, dispatched: the per-dealer row digests of the four
    dealer-sharded round-1 tensors, three (n, 8) uint32 arrays.

    On the device leg (``crypto.device_hash.digest_dispatch``: a TPU, or
    ``DKG_TPU_DIGEST=device``) ONE ``shard_map`` program,
    ``mesh_digest_rows``, run like the others: every
    shard canonicalises and tree-hashes its own dealers where deal left
    them (``ce.dealer_rows_traced``, PR 43's leg a shard each) and the
    rows come back dealer-sharded — no round-1 tensor crosses to the
    host, ``round1_host_bytes_total`` stands still, and what
    :func:`rho_from_rows` fetches is 96 bytes a dealer.  Returns at
    dispatch.

    Two callers need the other leg, ``ce.sharded_dealer_rows`` (numpy on
    the host, shard by shard), and so it stays: a backend whose digest
    leg is the host's (every CPU run: XLA:CPU's tree hash was the
    slowest phase of a ceremony there, the reason ``digest_dispatch``
    exists), and a mesh that spans processes, where a process can fetch
    only its own shards' rows and the loop's ``process_allgather`` brings
    the others'.  Either way the same rows bit for bit
    (``tests/test_sharded_route.py`` holds the two legs to each other),
    so the same digest and rho as the one-device engine's.
    """
    from ..crypto import device_hash as dh

    if dh.digest_dispatch() == "host" or jax.process_count() > 1:
        return ce.sharded_dealer_rows(cfg, a, e, s, r)
    return _run_program(
        run, "mesh_digest_rows", cfg, mesh, 0,
        _digest_rows_prog(cfg, mesh, envknobs.program_shape()),
        (a, e, s, r),
    )


@functools.lru_cache(maxsize=None)
def _digest_rows_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=IN_SPECS["mesh_digest_rows"],
        out_specs=(P(PARTY_AXIS),) * 3,
    )
    def mesh_digest_rows(a_sh, e_sh, s_sh, r_sh):
        from ..utils.scanchunk import map_chunked

        def call(off, w):
            part = [lax.dynamic_slice_in_dim(x, off, w, 0) for x in (a_sh, e_sh, s_sh, r_sh)]
            return ce.dealer_rows_traced(cfg, *part)

        block = int(a_sh.shape[0])
        return map_chunked(block, _digest_chunk_default(cfg, block), call)

    return mesh_digest_rows


#: Commitment lanes one pass of the digest canonicalises at once: what
#: the one-device leg passes at (1024,341), 350,208, measured there
#: (PERF.md section 5), rounded up to a power of two.
_DIGEST_CHUNK_LANES = 1 << 19


def _digest_chunk_default(cfg: ce.CeremonyConfig, block: int) -> int:
    """Dealer-axis chunk of a shard's digest: rows are per dealer, so a
    chunk changes no bit, and the canonicalisation's temps (a lane's
    (C, L) words tile-padded, the Montgomery rows) go with the lanes it
    passes at once — lowered for v5e:2x2 at (4096,1365) over four devices
    the unchunked body took 7.2 GB of temps beside 1.1 GB of arguments
    (PERF.md section 6, PR 44).  A power of two of dealers whose
    (t + 1) lanes each stay within :data:`_DIGEST_CHUNK_LANES`."""
    w = max(1, _DIGEST_CHUNK_LANES // (cfg.t + 1))
    return min(1 << (w.bit_length() - 1), block)


def rho_from_rows(cfg: ce.CeremonyConfig, rows, rho_bits: int) -> np.ndarray:
    """Phase 2, collected: :func:`transcript_rows`'s arrays fetched (one
    wait for the three), folded as ``transcript_digest_device`` folds
    them and expanded into the (n, L) Fiat-Shamir randomizers."""
    rows = [np.asarray(x) for x in jax.device_get(list(rows))]
    return ce.fiat_shamir_rho(cfg, ce._fold_digest_device(cfg, *rows), rho_bits)


def adjudicate(
    cfg: ce.CeremonyConfig, mesh: Mesh, a0, e, s, r, g_table, h_table, real=None, run=None
):
    """The failed batch check's path: ``sharded_blame``, the guilty
    dealers out, ``sharded_finalise`` over the rest (aggregation + master
    key only — the pairwise checks already adjudicated, so no
    verification is repeated), mirroring BatchedCeremony.run's flow.

    ``real`` is the ceremony's own (n, t) where ``cfg`` is a padded
    bucket's (the served route): guilt and the threshold are the real
    dealers', and a phantom dealer is never qualified, as on the
    one-device route.

    Returns (pw, qualified, finals, master): the replicated (n, n)
    pairwise verdicts and the dealer mask, both numpy.  ``finals`` and
    ``master`` are None when more than t dealers are out
    (committee.rs:340-347: proceeding would yield a key backed by fewer
    than t+1 honest dealers): nothing is finalised then, and the caller
    still has the verdicts to report (:func:`higher_threshold` is the
    error the tuple APIs raise)."""
    n, t = real if real is not None else (cfg.n, cfg.t)
    # pw is replicated (out_specs P()), so plain asarray is
    # multihost-safe: every process holds a full copy
    pw = np.asarray(sharded_blame(cfg, mesh, e, s, r, g_table, h_table, run))
    qualified = np.zeros((cfg.n,), bool)
    qualified[:n] = pw[:n, :n].all(axis=1)
    if n - int(qualified[:n].sum()) > t:
        return pw, qualified, None, None
    finals, master = sharded_finalise(cfg, mesh, a0, s, jnp.asarray(qualified), run)
    return pw, qualified, finals, master


def higher_threshold(qualified: np.ndarray):
    """``DkgError(MISBEHAVIOUR_HIGHER_THRESHOLD)`` naming the dealers
    :func:`adjudicate` found guilty."""
    from ..dkg.errors import DkgError, DkgErrorKind

    return DkgError(
        DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD,
        detail="guilty dealers (1-based): "
        + ", ".join(str(j + 1) for j in np.nonzero(~qualified)[0]),
    )


def sharded_ceremony(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    rho_bits: int = 128,
    tamper=None,
    run=None,
):
    """Full ceremony, parties sharded over the mesh — blame included.

    Two device phases with a host Fiat-Shamir boundary between them —
    rho is derived from the digest of the COMPLETE round-1 transcript
    (commitments + delivered shares), never from a fixed string, so the
    batch check is sound against an adaptive dealer and publicly
    recomputable.  If the batch check fails anywhere, the engine drops
    to :func:`adjudicate`.

    Returns (ok, finals, master, qualified): ``ok`` is the
    PRE-adjudication per-recipient batch check (failures show which
    recipients received bad shares); ``qualified`` the final dealer
    mask.  Raises ``DkgError(MISBEHAVIOUR_HIGHER_THRESHOLD)`` when more
    than t dealers are disqualified (the tuple API has no error slot).
    ``tamper(a, e, s, r) -> same`` is the fault-injection hook (arrays
    must keep their shardings); jit-compiled over the mesh; the driver's
    ``dryrun_multichip`` runs this on a virtual CPU mesh.
    """
    a, e, s, r = sharded_deal(cfg, mesh, coeffs_a, coeffs_b, g_table, h_table, run)
    if tamper is not None:
        a, e, s, r = tamper(a, e, s, r)
    jax.block_until_ready(e)
    # multihost-safe: only 32-byte row digests cross process boundaries
    rho = jnp.asarray(rho_from_rows(cfg, transcript_rows(cfg, mesh, a, e, s, r, run), rho_bits))
    # After the digest only the BARE FIRST COLUMNS are ever read (the
    # master key); dropping the full bare tensor here returns its HBM
    # (3.22 G at BLS n=16384) before the round-2 program runs.
    a0 = a[:, 0]
    del a
    ok, finals, master = sharded_verify_finalise(
        cfg, mesh, a0, e, s, r, g_table, h_table, rho, rho_bits, run
    )
    qualified = jnp.ones((cfg.n,), bool)
    if not bool(_host_global(ok).all()):
        _, qual_h, finals, master = adjudicate(cfg, mesh, a0, e, s, r, g_table, h_table, run=run)
        if finals is None:
            raise higher_threshold(qual_h)
        qualified = jnp.asarray(qual_h)
    return ok, finals, master, qualified


def place_sharded(mesh: Mesh, x, spec: P | None = None) -> jax.Array:
    """Place an array onto ``mesh`` under an EXPLICIT PartitionSpec
    (default: sharded on the party axis; pass ``P()`` for replicated
    operands like the fixed-base tables).

    ``jax.device_put`` with a NamedSharding is the one sanctioned way
    host buffers enter the sharded ceremony: committing the layout here
    (instead of letting the first shard_map infer-and-reshard) means
    the deal program's inputs are already dealer-blocked, so round 1
    starts with zero cross-device movement.  No-op when ``x`` already
    has that sharding.
    """
    from jax.sharding import NamedSharding

    return jax.device_put(
        x, NamedSharding(mesh, spec if spec is not None else P(PARTY_AXIS))
    )


def place_replicated(mesh: Mesh, x) -> jax.Array:
    """:func:`place_sharded` under ``P()``: a whole copy on every device
    of the mesh (the fixed-base tables, rho)."""
    return place_sharded(mesh, x, P())


def place_coeffs(mesh: Mesh, coeffs_a, coeffs_b, registry=None):
    """A request's two coefficient tensors from the host onto the mesh,
    dealer-sharded, and waited for: what a sharded request moves to the
    devices.  Books ``mesh_place_seconds`` and ``mesh_place_bytes_total``
    (nothing moves, and nothing is booked as moved, for operands that
    already lie so)."""
    if registry is None:
        from ..utils import metrics as _metrics

        registry = _metrics.REGISTRY
    moved = sum(int(x.nbytes) for x in (coeffs_a, coeffs_b) if not isinstance(x, jax.Array))
    t0 = time.perf_counter()
    placed = jax.block_until_ready(
        (place_sharded(mesh, coeffs_a), place_sharded(mesh, coeffs_b))
    )
    registry.observe("mesh_place_seconds", time.perf_counter() - t0)
    registry.inc("mesh_place_bytes_total", moved)
    return placed


def run_sharded_ceremony(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a,
    coeffs_b,
    g_table,
    h_table,
    rho_bits: int = 128,
    tamper=None,
    seal=None,
    ceremony_id: str = "sharded",
    registry=None,
    run=None,
):
    """BatchedCeremony.run's mesh twin: the full instrumented ceremony,
    inputs placed with explicit PartitionSpecs, every phase timed and
    attributed per shard.

    The device flow is exactly :func:`sharded_ceremony`'s (bit-identical
    results — pinned by tests/test_parallel.py's subprocess oracle), and
    its phases are the functions the served route drives too
    (``service/engine.py``: :func:`place_coeffs`,
    :func:`sharded_deal_commitments`, :func:`sharded_deal_shares`,
    :func:`transcript_rows`, :func:`rho_from_rows`,
    :func:`sharded_verify_finalise`, :func:`adjudicate`), each program
    run through ``run`` where given (the served route's store seam;
    ``chip_smoke.py --mesh`` passes it too); what this
    driver adds is the operational envelope the north-star run
    publishes:

    * input placement via :func:`place_sharded` (coefficients
      dealer-sharded, tables replicated) so phase 0 starts aligned;
    * per-phase wall clocks -> ``phases_s`` and the
      ``mesh_collective_seconds{op}`` histogram;
    * **a line of the log as each phase is entered** (logger
      ``dkg_tpu.parallel.mesh``, INFO), so a run that does not return
      names the phase it is in;
    * per-shard readiness events in obslog's ``round_head`` /
      ``publish`` / ``round_tail`` schema (party = shard index), so
      ``obslog.critical_path`` decomposes a sharded barrier exactly the
      way it decomposes a networked one — the straggler it names is the
      last shard to produce its block.  Shards are blocked in mesh
      order, so a shard's publish timestamp includes any wait on the
      ones before it; the LAST publish (the straggler) is exact.
    * optionally, host-side DEM/transport overlapped per shard:
      ``seal=(group, pks_dev, r_enc)`` routes the dealt share matrix
      through ``dkg.hybrid_batch.seal_shares_mesh`` (the
      seal_shares_pipeline chunk overlap lifted to mesh shards), whose
      sealed broadcasts land in the result's ``broadcasts`` slot.

    Phases (the obslog round numbers): 0 deal-commitments,
    1 deal-shares, 2 transcript digest + Fiat-Shamir, 3 verify+finalise,
    4 blame/re-finalise (failed batch check only).

    Returns a BatchedCeremony.run-style dict: ``ok`` (pre-adjudication
    per-recipient batch check, recipient-sharded), ``final_shares``,
    ``master``, ``qualified``, ``rho``, plus ``phases_s``, ``events``,
    ``mesh_shape``/``n_devices``, and ``broadcasts`` (None unless
    ``seal`` was given).  Raises
    ``DkgError(MISBEHAVIOUR_HIGHER_THRESHOLD)`` past t disqualified
    dealers, like the tuple API.
    """
    from ..utils import metrics as _metrics
    from ..utils import obslog

    reg = registry if registry is not None else _metrics.REGISTRY
    n_dev = _check_mesh(cfg, mesh)
    reg.inc("mesh_shards_total", n_dev)
    events: list[dict] = []
    phases: dict[str, float] = {}

    def _head(rd: int, op: str) -> float:
        _LOG.info(
            "sharded ceremony %s (%s n=%d t=%d, %d devices): entering phase %d %s",
            ceremony_id, cfg.curve, cfg.n, cfg.t, n_dev, rd, op,
        )
        now = time.time()
        events.append(
            {"kind": "round_head", "ceremony_id": ceremony_id, "round": rd, "ts": now}
        )
        obslog.emit_current("round_head", round=rd, ceremony_id=ceremony_id)
        return now

    def _publish_shards(rd: int, out) -> None:
        # host-observed per-shard readiness, blocked in mesh order: an
        # early shard's timestamp may include waiting on the scan, but
        # the last (the straggler critical_path names) is exact
        per = list(getattr(out, "addressable_shards", ()) or ())
        if len(per) == n_dev:
            per.sort(key=lambda sh: sh.index[0].start or 0)
            blocks = [sh.data for sh in per]
        else:  # replicated output, host array, or single-device run
            blocks = [out] * n_dev
        for i, blk in enumerate(blocks):
            jax.block_until_ready(blk)
            events.append(
                {
                    "kind": "publish",
                    "ceremony_id": ceremony_id,
                    "round": rd,
                    "party": i,
                    "ts": time.time(),
                }
            )
            obslog.emit_current(
                "publish", round=rd, party=i, ceremony_id=ceremony_id
            )

    def _tail(rd: int, op: str, t_open: float) -> None:
        now = time.time()
        events.append(
            {
                "kind": "round_tail",
                "ceremony_id": ceremony_id,
                "round": rd,
                "ts": now,
                "timed_out": False,
                "present": n_dev,
                "party": n_dev - 1,
            }
        )
        obslog.emit_current(
            "round_tail",
            round=rd,
            ceremony_id=ceremony_id,
            timed_out=False,
            present=n_dev,
        )
        phases[op] = phases.get(op, 0.0) + (now - t_open)
        book_phase(op, now - t_open, reg)

    _LOG.info("sharded ceremony %s: placing inputs on %d devices", ceremony_id, n_dev)
    ca, cb = place_coeffs(mesh, coeffs_a, coeffs_b, reg)
    gt = place_sharded(mesh, g_table, P())
    ht = place_sharded(mesh, h_table, P())

    t0 = _head(0, "deal_commitments")
    a, e = sharded_deal_commitments(cfg, mesh, ca, cb, gt, ht, run)
    _publish_shards(0, e)
    _tail(0, "deal_commitments", t0)

    t0 = _head(1, "deal_shares")
    s, r = sharded_deal_shares(cfg, mesh, ca, cb, run)
    _publish_shards(1, s)
    _tail(1, "deal_shares", t0)

    if tamper is not None:
        a, e, s, r = tamper(a, e, s, r)

    broadcasts = None
    if seal is not None:
        from ..dkg import hybrid_batch as hb

        group, pks_dev, r_enc = seal
        _LOG.info("sharded ceremony %s: entering seal_transport", ceremony_id)
        t0 = time.time()
        broadcasts = hb.seal_shares_mesh(
            group, cfg, mesh, s, r, pks_dev, r_enc, gt
        )
        phases["seal_transport"] = time.time() - t0
        book_phase("seal_transport", phases["seal_transport"], reg)

    t0 = _head(2, "transcript_digest")
    rho = jnp.asarray(rho_from_rows(cfg, transcript_rows(cfg, mesh, a, e, s, r, run), rho_bits))
    _publish_shards(2, rho)
    _tail(2, "transcript_digest", t0)

    # only the bare FIRST columns survive the digest (the master key's
    # sole input); dropping the full bare tensor returns its HBM before
    # the round-2 program runs (3.22 G at BLS n=16384)
    a0 = a[:, 0]
    del a

    t0 = _head(3, "verify_finalise")
    ok, finals, master = sharded_verify_finalise(
        cfg, mesh, a0, e, s, r, g_table=gt, h_table=ht, rho=rho, rho_bits=rho_bits, run=run
    )
    _publish_shards(3, finals)
    _tail(3, "verify_finalise", t0)

    qualified = jnp.ones((cfg.n,), bool)
    if not bool(_host_global(ok).all()):
        t0 = _head(4, "blame")
        _, qual_h, finals, master = adjudicate(cfg, mesh, a0, e, s, r, gt, ht, run=run)
        if finals is None:
            _tail(4, "blame", t0)
            raise higher_threshold(qual_h)
        qualified = jnp.asarray(qual_h)
        _publish_shards(4, finals)
        _tail(4, "blame", t0)
    _LOG.info("sharded ceremony %s: done, phases_s %s", ceremony_id, phases)

    return {
        "ok": ok,
        "final_shares": finals,
        "master": master,
        "qualified": qualified,
        "rho": rho,
        "broadcasts": broadcasts,
        "phases_s": phases,
        "events": events,
        "mesh_shape": tuple(mesh.devices.shape),
        "n_devices": n_dev,
    }


def _host_global(x: jax.Array) -> np.ndarray:
    """Global host value of a possibly mesh-sharded array; on multi-host
    meshes the shards are gathered across processes first (a direct
    np.asarray would fail: the array spans non-addressable devices)."""
    if jax.process_count() > 1:  # pragma: no cover — single-process CI
        from jax.experimental import multihost_utils as mhu

        return np.asarray(mhu.process_allgather(x, tiled=True))
    return np.asarray(x)


def _check_mesh(cfg: ce.CeremonyConfig, mesh: Mesh) -> int:
    n_dev = mesh.devices.size
    if cfg.n % n_dev != 0:
        raise ValueError("committee size must divide evenly over the mesh")
    return n_dev
