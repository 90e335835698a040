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

# The memoized program builders below put envknobs.program_shape() —
# the knobs read at TRACE time and baked into the compiled sharded
# programs — into their cache key, so flipping a knob between calls
# retraces (the semantics per-call eager tracing always had) while a
# steady-state rerun at stable knobs reuses the jitted executable
# instead of recompiling the whole sharded program set: before this
# cache the north-star warm run cost the same as the cold one
# (NORTHSTAR r01 measured warm 135.6 s vs cold 126.0 s at (16, 5) on
# the CPU mesh — pure retrace).


def _verify_env_chunk() -> int | None:
    """DKG_TPU_VERIFY_CHUNK (0 disables), validated by the shared knob
    parser in ceremony."""
    return ce._env_chunk("DKG_TPU_VERIFY_CHUNK")


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
    """
    fs = cfg.cs.scalar
    per_recipient = cfg.n * fs.limbs * 4
    w = max(1, (128 << 20) // per_recipient)
    w = 1 << max(0, w.bit_length() - 1)
    return min(w, block)


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
    a, e = sharded_deal_commitments(cfg, mesh, coeffs_a, coeffs_b, g_table, h_table)
    s, r = sharded_deal_shares(cfg, mesh, coeffs_a, coeffs_b)
    return a, e, s, r


def sharded_deal_commitments(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
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
    step = _deal_commitments_prog(cfg, mesh, envknobs.program_shape())
    return step(coeffs_a, coeffs_b, g_table, h_table)


@functools.lru_cache(maxsize=None)
def _deal_commitments_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    """Memoized, jitted round-1 commitment program (``knobs`` is cache
    key only — the trace below re-reads the environment)."""
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P(), P()),
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS)),
    )
    def step(ca, cb, gt, ht):
        # chunked in-trace (lax.map) so the fixed-base scan's padded
        # carry stays bounded per shard — the AOT TPU compile of the
        # one-shot body at BLS n=16384/8 devices was rejected at 21.3 GB
        return ce.deal_commitments_traced_chunked(cfg, ca, cb, gt, ht)

    return step


def sharded_deal_shares(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
):
    """Round-1 share program: (s, r), dealer-sharded (second of the two
    sequential deal programs; see :func:`sharded_deal_commitments`)."""
    _check_mesh(cfg, mesh)
    return _deal_shares_prog(cfg, mesh, envknobs.program_shape())(coeffs_a, coeffs_b)


@functools.lru_cache(maxsize=None)
def _deal_shares_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=(P(PARTY_AXIS), P(PARTY_AXIS)),
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS)),
    )
    def step(ca, cb):
        return ce.deal_shares_traced_chunked(cfg, ca, cb)

    return step


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
    step = _verify_finalise_prog(cfg, mesh, rho_bits, envknobs.program_shape())
    return step(a0, e, s, r, g_table, h_table, rho)


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
        in_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P(PARTY_AXIS), P(PARTY_AXIS), P(), P(), P()),
        out_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P()),
    )
    def step(a0_sh, e_sh, s_sh, r_sh, gt, ht, rho_all):
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

    return step


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


def _recipient_chunk(cfg, block: int) -> int:
    """Resolved recipient-chunk width: env override else budget default;
    0 / >= block means unchunked."""
    chunk = _verify_env_chunk()
    if chunk is None:
        chunk = _verify_chunk_default(cfg, block)
    return chunk


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

    chunk = _recipient_chunk(cfg, block)
    return _chunked_recipient_loop(n_dev, block, chunk, run, (s_sh, r_sh))


def _aggregate_chunked(cfg, n_dev, s_sh, qual, block):
    """Chunked share delivery + qualified aggregation only (the blame
    re-finalise path: verification already adjudicated)."""

    def run(off, w, sc):
        s_recv = lax.all_to_all(sc, PARTY_AXIS, split_axis=1, concat_axis=0, tiled=True)
        return (ce.aggregate_shares(cfg, s_recv, qual),)

    chunk = _recipient_chunk(cfg, block)
    (finals,) = _chunked_recipient_loop(n_dev, block, chunk, run, (s_sh,))
    return finals


def sharded_finalise(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    a0: jax.Array,  # (n, C, L) dealer-sharded bare first columns
    s: jax.Array,  # (n, n, L) dealer-sharded
    qualified: jax.Array,  # (n,) replicated dealer mask
):
    """Aggregation + master key only, over an adjudicated qualified set
    (the blame path re-finalise: no verification work — the pairwise
    checks already determined exactly which dealers are out)."""
    _check_mesh(cfg, mesh)
    return _finalise_prog(cfg, mesh, envknobs.program_shape())(a0, s, qualified)


@functools.lru_cache(maxsize=None)
def _finalise_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs
    n_dev = mesh.devices.size

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P()),
        out_specs=(P(PARTY_AXIS), P()),
    )
    def step(a0_sh, s_sh, qual):
        shard = lax.axis_index(PARTY_AXIS)
        block = cfg.n // n_dev
        finals = _aggregate_chunked(cfg, n_dev, s_sh, qual, block)
        master = _master_shardlocal(cfg, n_dev, a0_sh, qual, shard, block)
        return finals, master

    return step


def sharded_blame(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    e: jax.Array,  # (n, t+1, C, L) dealer-sharded
    s: jax.Array,  # (n, n, L) dealer-sharded
    r: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
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
    return _blame_prog(cfg, mesh, envknobs.program_shape())(e, s, r, g_table, h_table)


@functools.lru_cache(maxsize=None)
def _blame_prog(cfg: ce.CeremonyConfig, mesh: Mesh, knobs: tuple):
    del knobs

    @jax.jit
    @functools.partial(
        _shard_map_nocheck,
        mesh=mesh,
        in_specs=(P(PARTY_AXIS), P(PARTY_AXIS), P(PARTY_AXIS), P(), P()),
        out_specs=P(),
    )
    def step(e_sh, s_sh, r_sh, gt, ht):
        pw = ce.verify_pairwise(cfg, e_sh, s_sh, r_sh, gt, ht)  # (block, n)
        return lax.all_gather(pw, PARTY_AXIS, tiled=True)  # (n, n)

    return step


def sharded_ceremony(
    cfg: ce.CeremonyConfig,
    mesh: Mesh,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    rho_bits: int = 128,
    tamper=None,
):
    """Full ceremony, parties sharded over the mesh — blame included.

    Two device phases with a host Fiat-Shamir boundary between them —
    rho is derived from the digest of the COMPLETE round-1 transcript
    (commitments + delivered shares), never from a fixed string, so the
    batch check is sound against an adaptive dealer and publicly
    recomputable.  If the batch check fails anywhere, the engine drops
    to ``sharded_blame``, disqualifies guilty dealers, and re-finalises
    over the qualified set with ``sharded_finalise`` (aggregation +
    master key only — the pairwise checks already adjudicated, so no
    verification is repeated), mirroring BatchedCeremony.run's flow.

    Returns (ok, finals, master, qualified): ``ok`` is the
    PRE-adjudication per-recipient batch check (failures show which
    recipients received bad shares); ``qualified`` the final dealer
    mask.  Raises ``DkgError(MISBEHAVIOUR_HIGHER_THRESHOLD)`` when more
    than t dealers are disqualified (committee.rs:340-347 — the tuple
    API has no error slot, and proceeding would yield a key backed by
    fewer than t+1 honest dealers).  ``tamper(a, e, s, r) -> same`` is
    the fault-injection hook (arrays must keep their shardings);
    jit-compiled over the mesh; the driver's ``dryrun_multichip`` runs
    this on a virtual CPU mesh.
    """
    from ..dkg.errors import DkgError, DkgErrorKind

    a, e, s, r = sharded_deal(cfg, mesh, coeffs_a, coeffs_b, g_table, h_table)
    if tamper is not None:
        a, e, s, r = tamper(a, e, s, r)
    jax.block_until_ready(e)
    # multihost-safe: only 32-byte row digests cross process boundaries
    digest = ce.sharded_transcript_digest(cfg, a, e, s, r)
    rho = jnp.asarray(ce.fiat_shamir_rho(cfg, digest, rho_bits))
    # After the digest only the BARE FIRST COLUMNS are ever read (the
    # master key); dropping the full bare tensor here returns its HBM
    # (3.22 G at BLS n=16384) before the round-2 program runs.
    a0 = a[:, 0]
    del a
    ok, finals, master = sharded_verify_finalise(
        cfg, mesh, a0, e, s, r, g_table, h_table, rho, rho_bits
    )
    qualified = jnp.ones((cfg.n,), bool)
    if not bool(_host_global(ok).all()):
        # pw is replicated (out_specs P()), so plain asarray is
        # multihost-safe: every process holds a full copy
        pw = np.asarray(sharded_blame(cfg, mesh, e, s, r, g_table, h_table))
        guilty = ~pw.all(axis=1)
        if int(guilty.sum()) > cfg.t:
            raise DkgError(
                DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD,
                detail="guilty dealers (1-based): "
                + ", ".join(str(j + 1) for j in np.nonzero(guilty)[0]),
            )
        qualified = jnp.asarray(~guilty)
        finals, master = sharded_finalise(cfg, mesh, a0, s, qualified)
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
):
    """BatchedCeremony.run's mesh twin: the full instrumented ceremony,
    inputs placed with explicit PartitionSpecs, every phase timed and
    attributed per shard.

    The device flow is exactly :func:`sharded_ceremony`'s (bit-identical
    results — pinned by tests/test_parallel.py's subprocess oracle);
    what this driver adds is the operational envelope the north-star
    run publishes:

    * input placement via :func:`place_sharded` (coefficients
      dealer-sharded, tables replicated) so phase 0 starts aligned;
    * per-phase wall clocks -> ``phases_s`` and the
      ``mesh_collective_seconds{op}`` histogram;
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
    from ..dkg.errors import DkgError, DkgErrorKind
    from ..utils import metrics as _metrics
    from ..utils import obslog

    reg = registry if registry is not None else _metrics.REGISTRY
    n_dev = _check_mesh(cfg, mesh)
    reg.inc("mesh_shards_total", n_dev)
    events: list[dict] = []
    phases: dict[str, float] = {}

    def _head(rd: int) -> float:
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
        reg.observe("mesh_collective_seconds", now - t_open, op=op)

    ca = place_sharded(mesh, coeffs_a)
    cb = place_sharded(mesh, coeffs_b)
    gt = place_sharded(mesh, g_table, P())
    ht = place_sharded(mesh, h_table, P())

    t0 = _head(0)
    a, e = sharded_deal_commitments(cfg, mesh, ca, cb, gt, ht)
    _publish_shards(0, e)
    _tail(0, "deal_commitments", t0)

    t0 = _head(1)
    s, r = sharded_deal_shares(cfg, mesh, ca, cb)
    _publish_shards(1, s)
    _tail(1, "deal_shares", t0)

    if tamper is not None:
        a, e, s, r = tamper(a, e, s, r)

    broadcasts = None
    if seal is not None:
        from ..dkg import hybrid_batch as hb

        group, pks_dev, r_enc = seal
        t0 = time.time()
        broadcasts = hb.seal_shares_mesh(
            group, cfg, mesh, s, r, pks_dev, r_enc, gt
        )
        phases["seal_transport"] = time.time() - t0
        reg.observe(
            "mesh_collective_seconds", phases["seal_transport"], op="seal_transport"
        )

    t0 = _head(2)
    digest = ce.sharded_transcript_digest(cfg, a, e, s, r)
    rho = jnp.asarray(ce.fiat_shamir_rho(cfg, digest, rho_bits))
    _publish_shards(2, rho)
    _tail(2, "transcript_digest", t0)

    # only the bare FIRST columns survive the digest (the master key's
    # sole input); dropping the full bare tensor returns its HBM before
    # the round-2 program runs (3.22 G at BLS n=16384)
    a0 = a[:, 0]
    del a

    t0 = _head(3)
    ok, finals, master = sharded_verify_finalise(
        cfg, mesh, a0, e, s, r, g_table=gt, h_table=ht, rho=rho, rho_bits=rho_bits
    )
    _publish_shards(3, finals)
    _tail(3, "verify_finalise", t0)

    qualified = jnp.ones((cfg.n,), bool)
    if not bool(_host_global(ok).all()):
        t0 = _head(4)
        pw = np.asarray(sharded_blame(cfg, mesh, e, s, r, gt, ht))
        guilty = ~pw.all(axis=1)
        if int(guilty.sum()) > cfg.t:
            _tail(4, "blame", t0)
            raise DkgError(
                DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD,
                detail="guilty dealers (1-based): "
                + ", ".join(str(j + 1) for j in np.nonzero(guilty)[0]),
            )
        qualified = jnp.asarray(~guilty)
        finals, master = sharded_finalise(cfg, mesh, a0, s, qualified)
        _publish_shards(4, finals)
        _tail(4, "blame", t0)

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
