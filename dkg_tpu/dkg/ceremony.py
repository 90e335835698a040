"""Batched device ceremony engine: arrays-of-parties as the primitive.

The reference drives one party at a time through the phases and spends
~all cycles in per-pair scalar ops (SURVEY §3).  This engine inverts the
shape TPU-first: the ceremony state is struct-of-arrays limb tensors for
*all parties at once*, and each round is one jitted batched kernel:

* ``deal``   — coefficient commitments A/E for all n dealers' t+1
  coefficients via fixed-base window tables (reference hot loop #1,
  committee.rs:151-159), and the full n×n share matrix via one batched
  Horner scan (hot loop #2, committee.rs:163-186).
* ``verify_batch`` — random-linear-combination batch verification: with
  Fiat-Shamir randomizers rho_j, each recipient checks

      g·(sum_j rho_j s_ji) + h·(sum_j rho_j s'_ji)
          == sum_l x_i^l · (sum_j rho_j E_jl)

  One n-sized point-RLC + one point-Horner per recipient replaces the
  n·(n-1) individual (t+1)-MSMs of the reference (committee.rs:292-296)
  — ~100x fewer point-ops at n=4096 — while ``verify_pairwise`` remains
  for blame assignment when the batch check fails (soundness: a cheating
  dealer passes the batch check w.p. 2^-rho_bits).
* ``verify_pairwise`` — the direct per-(recipient, dealer) check, used
  on the rare failure path and as the parity oracle.

Secrets discipline: coefficients/shares live on device as scalar limb
arrays; randomness is generated host-side (CSPRNG) and uploaded — the
device path is branchless/batched so secret-dependent control flow never
arises (SURVEY §6 hard part d).
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..crypto.commitment import CommitmentKey
from ..fields import device as fd
from ..fields import host as fh
from ..groups import device as gd
from ..groups import host as gh
from ..poly import device as pdev
from ..utils.metrics import REGISTRY


@dataclasses.dataclass(frozen=True)
class CeremonyConfig:
    """Static ceremony shape: hashable, jit-static."""

    curve: str  # name in gd.ALL_CURVES
    n: int  # committee size
    t: int  # threshold (polynomial degree)

    @property
    def cs(self) -> gd.CurveSpec:
        return gd.ALL_CURVES[self.curve]

    @property
    def index_bits(self) -> int:
        """Bit width of party indices 1..n."""
        return max(int(self.n).bit_length(), 1)

    def padded(self, n_pad: int, t_pad: int) -> "CeremonyConfig":
        """The shape-bucketed twin of this config: same curve, lanes
        padded to ``(n_pad, t_pad)`` so many ceremonies of nearby shapes
        share ONE set of jitted executables (dkg_tpu.service).

        Pad-and-mask contract: the caller zero-pads the coefficient
        tensors (phantom dealers are all-zero polynomials; real dealers
        gain zero high-order coefficients).  Zero coefficients deal zero
        shares and identity commitments, and every round-1 kernel is
        lane-elementwise along the dealer axis, so the REAL lanes of the
        padded run are bit-identical to the unpadded run — proven by the
        padded-vs-unpadded oracle tests (tests/test_service.py) on both
        curves.  Phantom dealers must be masked out of ``qualified``
        before aggregation/master-key (adding their zero shares is a
        no-op, but they are not protocol participants).
        """
        if n_pad < self.n or t_pad < self.t:
            raise ValueError(
                f"padded({n_pad}, {t_pad}): bucket must dominate the real "
                f"shape (n={self.n}, t={self.t})"
            )
        return CeremonyConfig(self.curve, n_pad, t_pad)


# ---------------------------------------------------------------------------
# round-1 dealing kernels
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=0)
def deal(
    cfg: CeremonyConfig,
    coeffs_a: jax.Array,  # (n, t+1, L) sharing-poly coefficients (secret)
    coeffs_b: jax.Array,  # (n, t+1, L) hiding-poly coefficients (secret)
    g_table: jax.Array,  # (NW, 16, C, L) fixed-base table for g
    h_table: jax.Array,  # (NW, 16, C, L) fixed-base table for h
):
    """All dealers' round-1 outputs in one shot.

    Returns (A, E, s, r):
      A (n, t+1, C, L) bare commitments g·a_l      (committee.rs:151-159)
      E (n, t+1, C, L) randomized A + h·b_l
      s (n, n, L)  share matrix s[j, i] = f_j(i+1)  (committee.rs:163-186)
      r (n, n, L)  hiding shares f'_j(i+1)
    """
    a_pub, e_comm = deal_commitments(cfg, coeffs_a, coeffs_b, g_table, h_table)
    shares, hidings = deal_shares(cfg, coeffs_a, coeffs_b)
    return a_pub, e_comm, shares, hidings


def deal_commitments(cfg, coeffs_a, coeffs_b, g_table, h_table):
    """Commitment half of dealing: (A, E) only (committee.rs:151-159)."""
    cs = cfg.cs
    a_pub = gd.fixed_base_mul(cs, g_table, coeffs_a)  # (m, t+1, C, L)
    b_hid = gd.fixed_base_mul(cs, h_table, coeffs_b)
    return a_pub, gd.add(cs, a_pub, b_hid)


def deal_shares(cfg, coeffs_a, coeffs_b):
    """Share half of dealing: the full share/hiding matrices
    (committee.rs:163-186)."""
    fs = cfg.cs.scalar
    xs = jnp.arange(1, cfg.n + 1, dtype=jnp.uint32)
    xs_limbs = jnp.zeros((cfg.n, fs.limbs), jnp.uint32).at[:, 0].set(xs)
    shares = pdev.eval_many(fs, coeffs_a, xs_limbs)  # (m, n, L)
    hidings = pdev.eval_many(fs, coeffs_b, xs_limbs)
    return shares, hidings


def _deal_chunk_default(cfg: CeremonyConfig, m: int | None = None) -> int:
    """Dealer-axis chunk size that keeps deal()'s TPU peak in budget.

    The fixed-base scan carries an (n_chunk, t+1, C, L) accumulator
    whose minor (C, L) dims are tile-padded to (8, 128) by the TPU
    layout (AOT compile at n=4096 t=1365: "Unpadded (3.39G) Padded
    (15.51G)", an HBM OOM on a 16 GB v5e).  Temps scale with the
    dealer chunk, and at RUNTIME they must coexist with the phase's own
    inputs (coefficients) and outputs (a, e, s, r for the ``m`` rows
    being dealt) — at BLS n=16384 over 8 devices those are 12.2 GB by
    themselves, so a fixed temp budget cannot be right for every shape.
    The budget is therefore what remains of a 15 GiB usable device
    after inputs + outputs (floored at 1 GiB so tiny devices still
    make progress, capped at 6.25 GiB — the AOT-measured sweet spot at
    the north-star shape: chunk=1024, peak 8.18 GB, ~2x headroom under
    the verify phase that follows).

    chunk = budget / ((t+1) * 8 * 128 * 4 B) padded-carry bytes per
    dealer, floored to a power of two so all full chunks share one
    compiled program (a ragged last chunk compiles once more).

    Since PR 35 the fused path gathers a window's entries as C·L-word
    rows (512 B a lane) and carries lane blocks, so the 4 KiB a lane
    this rule budgets for is no longer written there.  Re-derived at
    PR 44 for the shape a cell now runs, (4096,1365) over four devices
    (m = 1024 dealers a shard): the rule gives 1024, so the shard deals
    in one piece, and lowered for ``v5e:2x2`` that piece takes 1.07 GB
    of temps beside 0.58 GB of arguments and 0.54 GB of outputs — a
    third of what 4 KiB a lane reckons (5.7 GB), and far inside the
    device.  The rule therefore errs on the safe side wherever it
    bites (m > 1024 at this t); its numbers stay until a shape that
    chunks is measured on the chip (PERF.md section 6, PR 44).
    """
    if m is None:
        m = cfg.n
    cs = cfg.cs
    pt_bytes = cs.ncoords * cs.field.limbs * 4
    sc_bytes = cs.scalar.limbs * 4
    io_bytes = (
        2 * m * (cfg.t + 1) * sc_bytes  # coeffs_a + coeffs_b in
        + 2 * m * (cfg.t + 1) * pt_bytes  # a + e out
        + 2 * m * cfg.n * sc_bytes  # shares + hidings out
    )
    budget = min(25 << 28, max(1 << 30, (15 << 30) - io_bytes))
    per_dealer = (cfg.t + 1) * 8 * 128 * 4
    chunk = max(1, budget // per_dealer)
    return 1 << max(0, chunk.bit_length() - 1)


def _env_chunk(name: str) -> int | None:
    """A validated chunk-size env knob: None when unset, else an int >= 0
    (0 disables chunking): DKG_TPU_RLC_CHUNK's reader."""
    from ..utils import envknobs

    return envknobs.nonneg_int(name, "0 disables chunking")


def deal_chunked(
    cfg: CeremonyConfig,
    coeffs_a: jax.Array,
    coeffs_b: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
    chunk: int | None = None,
):
    """``deal`` in dealer-axis chunks (host loop of identical jit calls).

    Outputs are concatenated on the dealer axis and bit-identical to a
    one-shot ``deal`` (each dealer's row is independent).  Chunking
    exists purely to bound the TPU scan-carry padding described in
    :func:`_deal_chunk_default`, which is what an unpinned ``chunk``
    takes on TPU (elsewhere: no chunking; 0 disables it anywhere).
    """
    if chunk is None:
        chunk = _deal_chunk_default(cfg, coeffs_a.shape[0]) if fd._on_tpu() else 0
    # chunk over the rows actually supplied — callers may deal for a
    # LOCAL subset of dealers (committee_batch: m <= n rows)
    n_rows = coeffs_a.shape[0]
    if not chunk or chunk >= n_rows:
        return deal(cfg, coeffs_a, coeffs_b, g_table, h_table)
    outs = [
        deal(cfg, coeffs_a[c0 : c0 + chunk], coeffs_b[c0 : c0 + chunk], g_table, h_table)
        for c0 in range(0, n_rows, chunk)
    ]
    return tuple(jnp.concatenate(parts, axis=0) for parts in zip(*outs))


def _shares_chunk_default(cfg: CeremonyConfig, m: int) -> int:
    """Dealer-axis chunk for the STANDALONE shares program
    (:func:`deal_shares_traced_chunked`).

    Its Horner carry is (w, n, L) u32 with the minor (n, L) dims
    tile-padded — per dealer ~n * 128 * 4 B per matrix, two matrices.
    The budget is what remains of 15 GiB after the program's arguments
    (coefficients), its outputs (both share matrices), AND the
    commitment tensors left RESIDENT by the first deal program — the
    whole point of the two-program split is that the commitment scan's
    temps are freed by then, so only real state is charged.

    At (4096,1365) over four devices (m = 1024) the rule gives 1024: no
    chunk, and lowered for ``v5e:2x2`` the program takes 3.55 GB of
    temps for 0.54 GB of outputs — the sharded cell's fullest moment on
    a chip (PERF.md section 6, PR 44), a quarter of the device.
    """
    cs = cfg.cs
    pt_bytes = cs.ncoords * cs.field.limbs * 4
    sc_bytes = cs.scalar.limbs * 4
    io_bytes = (
        2 * m * (cfg.t + 1) * sc_bytes  # coeffs in
        + 2 * m * (cfg.t + 1) * pt_bytes  # resident a + e
        + 2 * m * cfg.n * sc_bytes  # shares + hidings out
    )
    budget = min(25 << 28, max(1 << 30, (15 << 30) - io_bytes))
    per_dealer = 2 * cfg.n * 128 * 4
    chunk = max(1, budget // per_dealer)
    return 1 << max(0, chunk.bit_length() - 1)


def deal_commitments_traced_chunked(cfg, coeffs_a, coeffs_b, g_table, h_table):
    """In-trace dealer-chunked commitment half (A, E) for sharded
    bodies — the first of the two sequential deal programs (the split
    lets XLA free this program's fixed-base scan carry before the
    shares program allocates its Horner temps; the MONOLITHIC chunked
    deal has a ~6.5 G temp floor that cannot coexist with its own
    12.2 G of inputs+outputs at BLS n=16384 over 8 devices)."""
    from ..utils.scanchunk import map_chunked

    m = int(coeffs_a.shape[0])
    chunk = _deal_chunk_default(cfg, m)

    def call(off, w):
        ca = lax.dynamic_slice_in_dim(coeffs_a, off, w, 0)
        cb = lax.dynamic_slice_in_dim(coeffs_b, off, w, 0)
        return deal_commitments(cfg, ca, cb, g_table, h_table)

    return map_chunked(m, chunk, call)


def deal_shares_traced_chunked(cfg, coeffs_a, coeffs_b):
    """In-trace dealer-chunked share half (s, r) — the second deal
    program; see :func:`deal_commitments_traced_chunked`."""
    from ..utils.scanchunk import map_chunked

    m = int(coeffs_a.shape[0])
    chunk = _shares_chunk_default(cfg, m)

    def call(off, w):
        ca = lax.dynamic_slice_in_dim(coeffs_a, off, w, 0)
        cb = lax.dynamic_slice_in_dim(coeffs_b, off, w, 0)
        return deal_shares(cfg, ca, cb)

    return map_chunked(m, chunk, call)


# ---------------------------------------------------------------------------
# verification kernels
# ---------------------------------------------------------------------------


def _field_dot(fs, weights: jax.Array, values: jax.Array) -> jax.Array:
    """sum_j weights[j] * values[j, ...] over axis 0, mod p.

    weights (m, L), values (m, ..., L) -> (..., L).
    """
    from ..fields import matmul as fmm

    if (fmm.mxu_matmul_active() and values.ndim == 3
            and weights.shape[0] <= fmm.MAX_K):
        # one-row modular matmul on the MXU (contraction over dealers)
        return fmm.matmul_mod(fs, weights[None], jnp.swapaxes(values, 0, 1))[0]
    prods = fd.mul(fs, weights.reshape((weights.shape[0],) + (1,) * (values.ndim - 2) + (weights.shape[-1],)), values)

    def step(acc, v):
        return fd.add(fs, acc, v), None

    acc, _ = lax.scan(step, fd.zeros(fs, values.shape[1:-1]), prods)
    return acc


def _straus_tiles(cs, weights: jax.Array, points: jax.Array, nbits: int) -> jax.Array:
    """The Straus schedule of :func:`_point_rlc` in the point kernels'
    lane-block form (``ops.pallas_point.to_tiles``): ``points`` is
    converted once, (dealer, column) row-major onto lanes, the table's
    entries 2P..15P, the tree over the dealers (``gd._tree_tiles``) and
    the accumulator stay (nb, C·L, BLOCK) blocks, and the accumulator is
    converted once at the end.  A lane's entry is a 16-way select on
    its dealer's digit, one fusion over the table's entries: no gather.  The
    packing follows from the shape: at (1024, 64) every level of the
    tree but the last halves whole blocks; a (16, 6) ceremony is one
    block, its levels lane slices of it.

    A convoy's stack (weights (k, m, L), points (k, m, cols, C, L)) is
    the same schedule with the ceremony axis joined to the columns:
    lanes ordered (dealer, ceremony, column), which is the order the
    tree halves on, and a lane's digit its (ceremony, dealer)'s.  Eight
    (16, 6) ceremonies are then 768 lanes in 6 blocks and 48 accumulator
    lanes in 1, where one padded block a ceremony made 8 and 8; the body
    books what it packed (``point_rlc_lanes_traced_total``).
    """
    from ..ops import pallas_point as pp

    stack = weights.shape[:-2]  # () or a convoy's (k,)
    if stack:
        points = jnp.moveaxis(points, len(stack), 0)  # (m, k, cols, C, L)
    m, window = points.shape[0], gd.WINDOW
    nd = -(-nbits // window)  # windows that can be non-zero
    p_t, _, lanes = pp.to_tiles(cs, points)
    nb, cols = p_t.shape[0], lanes // m  # cols: a dealer's lanes, every ceremony's columns
    nb_acc = -(-cols // pp.BLOCK)
    width = str(stack[0] if stack else 1)
    for part, live, blocks in (("points", lanes, nb), ("acc", cols, nb_acc)):
        REGISTRY.inc("point_rlc_lanes_traced_total", live, part=part, kind="live", stack=width)
        REGISTRY.inc(
            "point_rlc_lanes_traced_total", blocks * pp.BLOCK, part=part, kind="block", stack=width
        )
    ident = pp.identity_tiles(cs)

    def entry(prev, _):
        nxt = pp.add_tiles(cs, prev, p_t)
        return nxt, nxt

    _, rest = lax.scan(entry, p_t, None, length=14)  # 2P..15P: (14, nb, C·L, BLOCK)
    digits = gd.scalar_windows(cs, weights, window)[..., :nd]  # (m, nd), a stack's (k, m, nd)
    if stack:
        digits = jnp.reshape(jnp.moveaxis(digits, -2, 0), (-1, nd))  # (m·k, nd), dealer-major as the lanes
    lane_digits = jnp.repeat(
        jnp.moveaxis(digits, -1, 0)[::-1], lanes // digits.shape[0], axis=1
    )  # (nd, lanes) MSB first
    lane_digits = jnp.pad(lane_digits, ((0, 0), (0, nb * pp.BLOCK - lanes)))

    def step(acc, dig):
        dig = dig.reshape(nb, 1, pp.BLOCK)
        contribs = jnp.where(dig == 1, p_t, ident)
        for k in range(14):
            contribs = jnp.where(dig == k + 2, rest[k], contribs)
        total = gd._tree_tiles(cs, contribs, m, cols)
        return pp.window_step_tiles(cs, acc, total, window), None

    acc, _ = lax.scan(step, pp.identity_tiles(cs, nb_acc), lane_digits)
    return pp.from_tiles(cs, acc, points.shape[1:-2], cols)


def _point_rlc(cs, weights: jax.Array, points: jax.Array, nbits: int) -> jax.Array:
    """sum_j weights[j]·P[j, ...] for nbits-wide public weights.

    weights (m, L) limb arrays with only the low nbits set;
    points (m, ..., C, L) -> (..., C, L).  A convoy's stack carries
    its ceremony axis in front of both, each ceremony with weights of
    its own: weights (k, m, L), points (k, m, ..., C, L) ->
    (k, ..., C, L).  The block form packs the stack's lanes jointly
    (:func:`_straus_tiles`); the tensor forms have no lanes to pack
    and map the ceremonies.

    Three schedules, same sum:

    * **Bucket Pippenger** (:func:`groups.device.msm_pippenger`) — no
      per-point tables; points scatter into 2**c buckets per window,
      c chosen from the batch shape.  Default off-TPU: it avoids the
      per-lane Straus table build + gathers that dominate the CPU
      lowering, and its three scan bodies keep compiles light.
    * **Windowed Straus (w = 4)** — per-point 16-entry tables, then
      ceil(nbits/4) rounds of (gather + tree-add + one 4-double window
      step), ~2.8x fewer point-adds than bit-at-a-time.  Default on
      TPU; the window step is the fused Pallas kernel when those are
      active, a plain XLA 4-double+add otherwise — so the conservative
      (no-Pallas) TPU configuration still gets the cheaper schedule.
      With the fused kernels active the schedule runs in their lane-block
      form (:func:`_straus_tiles`): one conversion in, one out.
    * **Bit-at-a-time ladder** — the compile-cheapest schedule, kept as
      the cross-platform parity leg (bench parity_check).

    ``DKG_TPU_RLC=straus|bits|pippenger`` (validated via envknobs)
    forces a schedule on any backend (the cross-schedule parity tests
    use this).  Like every feature flag here, it is read at TRACE time:
    a jitted caller (verify_batch) caches its executable per static
    shape, so flipping the env var after a same-shape call reuses the
    already-traced schedule — set flags before the first call of a
    process (the bench's child-per-rung design exists exactly for this).

    The schedules, and the two forms of Straus, add the dealers in
    different orders: the result is the same group element, its
    projective coordinates are NOT canonical.  Compare it with
    ``gd.eq`` (as :func:`verify_batch` does); nothing reads its limbs.
    Each traced schedule body books ``point_rlc_traced_total{schedule,
    form, stack}`` (a chunked call traces two: the map's body and the
    tail; ``stack`` is the ceremonies the body holds, 1 without the axis).
    """
    from ..utils import envknobs

    stack = weights.shape[:-2]  # () or a convoy's (k,)
    m = points.shape[len(stack)]
    mode = envknobs.choice(
        "DKG_TPU_RLC",
        ("straus", "bits", "pippenger"),
        "a typo would silently measure the wrong schedule",
    )
    fused = gd.fused_kernels_active()
    if mode is None:
        mode = (
            "straus"
            if fused or fd._on_tpu()
            else "pippenger"
        )
    tiles = mode == "straus" and fused
    if stack and not tiles:
        return jax.vmap(lambda w, p: _point_rlc(cs, w, p, nbits))(weights, points)
    col_axis = len(stack) + 1
    if mode != "bits" and points.ndim > col_axis + 2:
        # Chunk the first trailing batch axis so the per-chunk temps
        # (per-point Straus tables / Pippenger buckets) stay under
        # ~256 MB regardless of (m, t); any FURTHER batch axes multiply
        # the per-chunk size too.  The chunks MUST run through a
        # sequential lax.map: the round-4 unrolled concatenate loop let
        # the TPU buffer assigner overlap ~196 live 252 MB chunk tables
        # at BLS n=16384 (MEMPROOF_TPU: 26.5 G fragmentation on 6 G of
        # real temps).  DKG_TPU_RLC_CHUNK overrides the budget
        # (tests force tiny chunks; 0 disables chunking).
        if mode == "straus":
            per_col = m * 16 * cs.ncoords * cs.field.limbs * 4
        else:
            pwin = gd.pippenger_window(m, cs.name)
            nw = -(-nbits // pwin)
            per_col = nw * (1 << pwin) * cs.ncoords * cs.field.limbs * 4
        for extra in stack + points.shape[col_axis + 1 : -2]:  # a column of every ceremony
            per_col *= extra
        chunk = _env_chunk("DKG_TPU_RLC_CHUNK")
        if chunk is None:
            chunk = max(1, (256 << 20) // per_col)
            if tiles:  # a power of two: the dealers' halves then fall on block edges
                chunk = 1 << (chunk.bit_length() - 1)
        ncols = points.shape[col_axis]
        if chunk and ncols > chunk:
            from ..utils.scanchunk import map_chunked

            def col_chunk(off, w):
                cols = lax.dynamic_slice_in_dim(points, off, w, axis=col_axis)
                out = _point_rlc(cs, weights, cols, nbits)
                return jnp.moveaxis(out, len(stack), 0) if stack else out  # the map joins on axis 0

            out = map_chunked(ncols, chunk, col_chunk)
            return jnp.moveaxis(out, 0, len(stack)) if stack else out

    REGISTRY.inc(
        "point_rlc_traced_total",
        schedule=mode,
        form="blocks" if tiles else "tensor",
        stack=str(stack[0] if stack else 1),
    )
    if tiles:
        return _straus_tiles(cs, weights, points, nbits)

    if mode == "pippenger":
        # weights broadcast over the column axes; the m axis moves last
        # to match the MSM kernel's (..., m, C, L) convention
        return gd.msm_pippenger(
            cs, weights, jnp.moveaxis(points, 0, -3), nbits=nbits
        )

    if mode == "straus":
        window = gd.WINDOW
        nd = -(-nbits // window)  # windows that can be non-zero
        table = gd._build_table(cs, points)  # (m, ..., 16, C, L)
        digits = gd.scalar_windows(cs, weights, window)[:, :nd]  # (m, nd)
        digits_rev = jnp.moveaxis(digits, -1, 0)[::-1]  # (nd, m) MSB first

        def step(acc, dig):
            shape = (m,) + (1,) * (points.ndim - 3)
            contribs = gd._gather_table(
                table, jnp.broadcast_to(dig.reshape(shape), points.shape[:-2])
            )  # (m, ..., C, L)
            total = gd._tree_reduce(cs, jnp.moveaxis(contribs, 0, -3), m)
            return gd.window_step(cs, acc, total, window, fused), None

        init = gd.identity(cs, points.shape[1:-2])
        acc, _ = lax.scan(step, init, digits_rev)
        return acc

    # bits (m, nbits) from the 16-bit limbs, then MSB-first rows
    idx = jnp.arange(nbits)
    limbs = weights[:, idx // 16]  # (m, nbits)
    bits = (limbs >> (idx % 16).astype(jnp.uint32)) & 1
    bits_rev = jnp.moveaxis(bits, -1, 0)[::-1]

    def step_bin(acc, bit_row):
        acc = gd._double_xla(cs, acc)
        shape = (m,) + (1,) * (points.ndim - 3)
        sel = gd.select(
            (bit_row.reshape(shape) != 0) | jnp.zeros(points.shape[:-2], bool),
            points,
            gd.identity(cs, points.shape[:-2]),
        )
        total = gd._tree_reduce(cs, jnp.moveaxis(sel, 0, -3), m)
        return gd._add_xla(cs, acc, total), None

    init = gd.identity(cs, points.shape[1:-2])
    acc, _ = lax.scan(step_bin, init, bits_rev)
    return acc


@functools.partial(jax.jit, static_argnums=(0, 5))
def verify_batch(
    cfg: CeremonyConfig,
    e_comm: jax.Array,  # (n, t+1, C, L) all dealers' randomized commitments
    shares: jax.Array,  # (n, n, L) s[j, i] as received by recipient i
    hidings: jax.Array,  # (n, n, L)
    rho: jax.Array,  # (n, L) Fiat-Shamir randomizers (low rho_bits bits)
    rho_bits: int,
    g_table: jax.Array,
    h_table: jax.Array,
) -> jax.Array:
    """RLC batch share-verification; returns (n,) bool per recipient.

    Sound up to 2^-rho_bits per cheating dealer; on False the caller
    falls back to ``verify_pairwise`` rows for blame assignment
    (mirrors the complaint path, committee.rs:305-317).

    A convoy's stack (``service.engine._verify_stack``) carries its
    ceremony axis in front of the four tensors, ``rho`` (k, n, L) a
    ceremony's own, and gets (k, n) bool: the same k·n equations, the
    axis a batch axis of the three kernel users below, so that their
    lane blocks fill with the convoy's lanes and not one ceremony's.
    """
    cs = cfg.cs
    fs = cs.scalar
    stack = rho.shape[:-2]  # () or a convoy's (k,)

    # per-recipient scalar RLCs over dealers:  (n_recipients, L)
    dot = functools.partial(_field_dot, fs)
    if stack:  # scalar field, no lane blocks to fill: mapped
        dot = jax.vmap(dot)
    s_rlc = dot(rho, shares)  # sum_j rho_j s_{j,i}
    r_rlc = dot(rho, hidings)

    # combined commitment columns D_l = sum_j rho_j E_{j,l}: (t+1, C, L)
    # (the fused path chunks the column axis internally to bound its
    # Straus-table memory)
    d_comm = _point_rlc(cs, rho, e_comm, rho_bits)

    # RHS_i = sum_l x_i^l D_l via small-x point Horner: (n, C, L)
    xs = jnp.arange(1, cfg.n + 1, dtype=jnp.uint32)
    if stack:
        d_comm = d_comm[..., None, :, :, :]  # a ceremony's columns, for each of its recipients
    rhs = gd.eval_point_poly(cs, d_comm, xs, cfg.index_bits)

    # LHS_i = g·s_rlc + h·r_rlc
    lhs = gd.add(
        cs,
        gd.fixed_base_mul(cs, g_table, s_rlc),
        gd.fixed_base_mul(cs, h_table, r_rlc),
    )
    return gd.eq(cs, lhs, rhs)


@functools.partial(jax.jit, static_argnums=0)
def verify_pairwise(
    cfg: CeremonyConfig,
    e_comm: jax.Array,  # (n_dealers, t+1, C, L)
    shares: jax.Array,  # (n_dealers, n_recipients, L)
    hidings: jax.Array,
    g_table: jax.Array,
    h_table: jax.Array,
) -> jax.Array:
    """Direct per-(dealer, recipient) checks -> (n_dealers, n_recipients)
    bool.  The reference's equation exactly (committee.rs:292-296), as
    one wide batched op; used for blame assignment + as parity oracle.
    """
    cs = cfg.cs
    lhs = gd.add(
        cs,
        gd.fixed_base_mul(cs, g_table, shares),
        gd.fixed_base_mul(cs, h_table, hidings),
    )  # (n_d, n_r, C, L)
    xs = jnp.arange(1, shares.shape[1] + 1, dtype=jnp.uint32)[None, :]
    rhs = gd.eval_point_poly(
        cs, e_comm[:, None], jnp.broadcast_to(xs, shares.shape[:2]), cfg.index_bits
    )
    return gd.eq(cs, lhs, rhs)


@functools.partial(jax.jit, static_argnums=0)
def aggregate_shares(cfg: CeremonyConfig, shares: jax.Array, qualified: jax.Array):
    """Final share per recipient: sum of qualified dealers' shares
    (committee.rs:453-462).  shares (n_dealers, n_recip, L),
    qualified (n_dealers,) bool -> (n_recip, L)."""
    fs = cfg.cs.scalar
    masked = fd.select(
        jnp.broadcast_to(qualified[:, None], shares.shape[:-1]),
        shares,
        fd.zeros(fs, shares.shape[:-1]),
    )

    def step(acc, row):
        return fd.add(fs, acc, row), None

    acc, _ = lax.scan(step, fd.zeros(fs, shares.shape[1:-1]), masked)
    return acc


@functools.partial(jax.jit, static_argnums=0)
def master_key_from_bare(cfg: CeremonyConfig, a_comm: jax.Array, qualified: jax.Array):
    """Master public key = sum over qualified dealers of A_{j,0}
    (committee.rs:791-796).  a_comm (n, t+1, C, L) -> (C, L)."""
    cs = cfg.cs
    a0 = a_comm[:, 0]  # (n, C, L)
    masked = gd.select(
        jnp.broadcast_to(qualified, a0.shape[:-2]), a0, gd.identity(cs, a0.shape[:-2])
    )
    return gd._tree_reduce(cs, masked, masked.shape[0])


# ---------------------------------------------------------------------------
# host-facing orchestration
# ---------------------------------------------------------------------------


def _dealer_row_digests(shares_rows: np.ndarray, hidings_rows: np.ndarray) -> np.ndarray:
    """Per-dealer digests of the delivered share/hiding rows.

    (k, n, L) x2 -> (k, 32) uint8.  Dealer position is bound by the
    order in which the caller folds these into the outer digest."""
    out = np.zeros((len(shares_rows), 32), np.uint8)
    for i in range(len(shares_rows)):
        h = hashlib.blake2b(digest_size=32, person=b"dkgtpu-row")
        h.update(np.ascontiguousarray(shares_rows[i]))
        h.update(np.ascontiguousarray(hidings_rows[i]))
        out[i] = np.frombuffer(h.digest(), np.uint8)
    return out


def _fold_digest(cfg: CeremonyConfig, a_np: np.ndarray, e_np: np.ndarray,
                 row_digests: np.ndarray) -> bytes:
    h = hashlib.blake2b(digest_size=32, person=b"dkgtpu-tr")
    h.update(f"{cfg.curve}|{cfg.n}|{cfg.t}|".encode())
    for arr in (a_np, e_np):
        a = np.ascontiguousarray(arr)
        h.update(str(a.shape).encode() + str(a.dtype).encode())
        h.update(a)  # streamed: no bytes() copy of ~100 MB tensors
    h.update(np.ascontiguousarray(row_digests))
    return h.digest()


def _fold_digest_device(cfg: CeremonyConfig, rows_a, rows_e, rows_sr) -> bytes:
    """Outer fold shared by the flat and sharded device digests: binds
    the three per-dealer row-digest arrays in dealer order."""
    h = hashlib.blake2b(digest_size=32, person=b"dkgtpu-trd")
    h.update(f"{cfg.curve}|{cfg.n}|{cfg.t}|".encode())
    for rows in (rows_a, rows_e, rows_sr):
        h.update(np.ascontiguousarray(np.asarray(rows, np.uint32)))
    return h.digest()


#: (curve, "<dealers>x<t+1>") shapes whose device digest leg this process
#: has already traced (``digest_leg_first_call_seconds``).
_DIGEST_LEG_SEEN: set = set()


def _dealer_rows_device(cfg: CeremonyConfig, a_comm, e_comm, shares, hidings,
                        dispatch: str | None = None):
    """Per-dealer BLAKE2s row digests of all four round-1 tensors:
    (k, ...) local-dealer slices -> three (k, 8) uint32 arrays.  A
    convoy's stacks go in as they are, (c, n, ...): every axis before a
    dealer's own ((t+1, C, L) of a commitment, (n, L) of a share row) is
    a dealer axis, and the rows come back flat, (c * n, 8), ceremony
    after ceremony.

    Every array is row-digested along the dealer axis (never tree-hashed
    flat), so EVERY part of the transcript is shard-foldable — a mesh
    that keeps commitments dealer-sharded (no allgather) still derives
    the canonical digest by exchanging 3 x 32 bytes per dealer.

    Backend-dispatched (``device_hash.digest_dispatch``).  **The device
    leg reads the tensors where they are**: five jitted dispatches and
    nothing else, ``gd.affine_canon`` on ``a_comm`` and on ``e_comm`` in
    the shape they have and ``_tree_from_words_jit`` three times, the
    rows' flattening, the casts and the joining of a dealer's share and
    hiding rows inside that program.  Device arrays (deal's outputs,
    finished or not) never leave the device and nothing is dispatched
    between the programs; numpy arrays go in as the programs' arguments.
    **The host leg fetches the four tensors itself** (``np.asarray``:
    for device arrays the one trip to the host; their bytes are booked
    in ``round1_host_bytes_total``, which therefore stands still while
    the device leg serves) and runs the big-int canonicalisation
    (``gd.affine_canon_host``) plus the batched numpy tree — on CPU that
    replaces the XLA per-op-overhead path that made fiat_shamir the
    slowest ceremony phase.  Both legs produce the SAME three row-digest
    arrays bit for bit.
    """
    from ..crypto import device_hash as dh

    if dispatch is None:
        dispatch = dh.digest_dispatch()
    lead = np.ndim(shares) - 2
    k = math.prod(np.shape(shares)[:lead])
    shape = f"{k}x{np.shape(e_comm)[lead]}"
    first = dispatch != "host" and (cfg.curve, shape) not in _DIGEST_LEG_SEEN
    if first:
        # the device leg is jitted outside the executable store: a
        # process's first call at a shape traces and compiles it, and the
        # dispatches below return only then.  Booked once per shape so
        # that set-up can say what the leg cost it.
        _DIGEST_LEG_SEEN.add((cfg.curve, shape))
        t0 = time.perf_counter()
    # Commitments are digested in CANONICAL affine form: projective Z
    # scale depends on the addition schedule (platform/flags), and rho
    # must be a function of the logical transcript, not of which kernel
    # computed it (gd.affine_canon's docstring has the full argument).
    if dispatch == "host":
        a_comm, e_comm, shares, hidings = (
            np.asarray(x) for x in (a_comm, e_comm, shares, hidings)
        )
        REGISTRY.inc(
            "round1_host_bytes_total",
            a_comm.nbytes + e_comm.nbytes + shares.nbytes + hidings.nbytes,
        )
        a_canon = gd.affine_canon_host(cfg.cs, a_comm)
        e_canon = gd.affine_canon_host(cfg.cs, e_comm)
    else:
        a_canon = gd.affine_canon(cfg.cs, a_comm)
        e_canon = gd.affine_canon(cfg.cs, e_comm)
    rows_a = dh.row_digests(a_canon, domain=1, dispatch=dispatch, lead=lead)
    rows_e = dh.row_digests(e_canon, domain=2, dispatch=dispatch, lead=lead)
    rows_sr = dh.row_digests((shares, hidings), domain=3, dispatch=dispatch, lead=lead)
    if first:
        REGISTRY.observe(
            "digest_leg_first_call_seconds",
            time.perf_counter() - t0,
            curve=cfg.curve,
            shape=shape,
        )
    return rows_a, rows_e, rows_sr


def dealer_rows_traced(cfg: CeremonyConfig, a_comm, e_comm, shares, hidings):
    """The device leg of :func:`_dealer_rows_device` with nothing of the
    host in it, for a caller that traces it into a program of its own
    (the mesh's ``mesh_digest_rows``, a shard's dealers each): the same
    canonicalisation and the same trees over (k, ...) dealer slices, so
    the same three (k, 8) row-digest arrays bit for bit.  Books nothing."""
    from ..crypto import device_hash as dh

    rows_a = dh.row_digests(gd.affine_canon(cfg.cs, a_comm), domain=1, dispatch="device")
    rows_e = dh.row_digests(gd.affine_canon(cfg.cs, e_comm), domain=2, dispatch="device")
    rows_sr = dh.row_digests((shares, hidings), domain=3, dispatch="device")
    return rows_a, rows_e, rows_sr


def transcript_digest_device(
    cfg: CeremonyConfig, a_comm, e_comm, shares, hidings
) -> bytes:
    """THE canonical engine transcript digest (device-resident).

    Same binding guarantee as the byte-level :func:`transcript_digest`
    (every limb of all four round-1 tensors), different digest function:
    the tensors are hashed where they live with the BLAKE2s Merkle tree
    (crypto.device_hash) and only (n, 32)-byte dealer row digests reach
    the host — instead of shipping ~2 GB of share matrices at n=4096.
    Fully shard-foldable along the dealer axis (commitments included),
    so a mesh never needs the replicated tensors just to hash them
    (:func:`sharded_transcript_digest` computes this exact value from
    dealer-sharded arrays).
    """
    return _fold_digest_device(
        cfg, *_dealer_rows_device(cfg, a_comm, e_comm, shares, hidings)
    )


def transcript_digest(cfg: CeremonyConfig, a_comm, e_comm, shares, hidings) -> bytes:
    """Digest of the COMPLETE round-1 broadcast transcript.

    Binds every limb of all four round-1 tensors — bare commitments A,
    randomized commitments E, and the delivered share/hiding matrices
    (the engine's stand-ins for the public broadcast: in the wire
    protocol the encrypted shares are public and determine s/r,
    reference committee.rs:163-186).  An adaptive dealer cannot change
    any part of its round-1 output without changing the derived batch
    randomizers.

    Structure is canonical and byte-level — the wire/audit alternative
    to the canonical engine digest (:func:`transcript_digest_device`);
    callers must pick ONE digest family per ceremony, and every engine
    path (BatchedCeremony, bench, sharded, driver entry) uses the
    device family via :func:`derive_rho`'s default.
    """
    rows = _dealer_row_digests(np.asarray(shares), np.asarray(hidings))
    # Same canonical-form discipline as the device digest family: the
    # audit digest must agree for the same logical transcript no matter
    # which schedule produced the projective coordinates.
    a_canon = np.asarray(gd.affine_canon(cfg.cs, jnp.asarray(a_comm)))
    e_canon = np.asarray(gd.affine_canon(cfg.cs, jnp.asarray(e_comm)))
    return _fold_digest(cfg, a_canon, e_canon, rows)


def sharded_transcript_digest(cfg: CeremonyConfig, a, e, s, r) -> bytes:
    """transcript_digest_device over mesh-sharded round-1 output.

    ALL FOUR tensors are dealer-sharded (the scalable mesh layout never
    replicates the commitments).  Bit-identical to
    ``transcript_digest_device`` on the unsharded arrays — the sharded
    and single-chip engines derive the SAME rho from the same
    transcript: :func:`sharded_dealer_rows` folded as the flat digest
    folds its rows.
    """
    return _fold_digest_device(cfg, *sharded_dealer_rows(cfg, a, e, s, r))


def sharded_dealer_rows(cfg: CeremonyConfig, a, e, s, r) -> list[np.ndarray]:
    """The three (n, 8) per-dealer row-digest arrays of dealer-sharded
    round-1 tensors, on the host, shard by shard through
    :func:`_dealer_rows_device` (so on whichever leg the backend takes).

    Each process digests its local dealer rows; only 3 x 32 bytes per
    dealer cross process boundaries, so this works on multi-host meshes
    where ``np.asarray(s)`` would fail (shards on non-addressable
    devices).  Every shard's programs are dispatched before any shard's
    rows are fetched, so the shards' devices work at once.  All four
    tensors must share ONE dealer layout: either all dealer-sharded
    identically or all replicated (mixed layouts fail the
    identical-sharding check).
    """
    rows = [np.zeros((cfg.n, 8), np.uint32) for _ in range(3)]
    per = []
    for t in (a, e, s, r):
        shards = sorted(
            t.addressable_shards, key=lambda sh: sh.index[0].start or 0
        )
        per.append(shards)
    seen = set()
    pending = []
    for sh_a, sh_e, sh_s, sh_r in zip(*per):
        sl = sh_s.index[0]
        if not (sh_r.index[0] == sl and sh_a.index[0] == sl and sh_e.index[0] == sl):
            # typed, not an assert: a mixed dealer layout would silently
            # fold the WRONG rows into the digest under ``python -O``
            # (asserts compile away) — and a wrong-but-valid rho is a
            # soundness bug, not a crash.
            raise ValueError(
                "sharded_transcript_digest: round-1 tensors must share one "
                "dealer-axis layout (all dealer-sharded identically or all "
                f"replicated); got a/e/s/r slices "
                f"{sh_a.index[0]}/{sh_e.index[0]}/{sl}/{sh_r.index[0]}"
            )
        if (sl.start, sl.stop) in seen:  # replicated shard copy
            continue
        seen.add((sl.start, sl.stop))
        pending.append(
            (sl, _dealer_rows_device(cfg, sh_a.data, sh_e.data, sh_s.data, sh_r.data))
        )
    for sl, shard_rows in pending:
        for dst, src in zip(rows, shard_rows):
            dst[sl] = np.asarray(src)
    if jax.process_count() > 1:  # pragma: no cover — single-process CI
        from jax.experimental import multihost_utils as mhu

        gathered = np.asarray(mhu.process_allgather(jnp.asarray(np.stack(rows))))
        # each dealer row is owned by exactly one process; others are 0
        rows = list(np.bitwise_or.reduce(gathered, axis=0))
    return rows


def fiat_shamir_rho(cfg: CeremonyConfig, transcript: bytes, rho_bits: int) -> np.ndarray:
    """Public batch-verification randomizers derived from the round-1
    transcript (publicly recomputable, so the batch check is itself
    verifiable).  ``transcript`` must be a binding digest of the full
    round-1 broadcast — use :func:`transcript_digest`.  Returns (n, L)
    uint32 limbs with rho_bits entropy.

    Lane j is ``hashlib.blake2b(transcript || j)``: one call of the C
    library a lane, joined into the (n, nbytes) array that the tail
    masks and splits into limbs.  A lane is 36 bytes, one compression;
    the numpy form this replaced (``crypto.blake2.blake2b_batch``) pays
    some 3,800 array operations a compression whatever n is, all under
    the interpreter lock, and lost to the C library at every lane count
    (the figures: PERF.md section 6, PR 37).  It stays in the tests as
    the independent reference, byte for byte
    (tests/test_digest_dispatch.py).  Books ``rho_lanes_total``, n a
    call."""
    fs = cfg.cs.scalar
    nbytes = (rho_bits + 7) // 8
    # mask to EXACTLY rho_bits: the point side (_point_rlc) consumes only
    # the low rho_bits, while the field side (_field_dot) consumes every
    # set bit — they must see the same weights for any rho_bits.
    mask = (1 << rho_bits) - 1
    blake2b = hashlib.blake2b
    dig = np.frombuffer(
        b"".join(
            blake2b(
                transcript + j.to_bytes(4, "little"),
                digest_size=nbytes,
                person=b"dkgtpu-rlc",
            ).digest()
            for j in range(cfg.n)
        ),
        np.uint8,
    ).reshape(cfg.n, nbytes)
    REGISTRY.inc("rho_lanes_total", cfg.n)
    out = np.zeros((cfg.n, fs.limbs), np.uint32)
    if (1 << rho_bits) > fs.modulus:
        # masked value may exceed the scalar modulus: reduce per lane
        # exactly as fh.encode always has (rare — rho_bits at/above the
        # field size; the vector path below must not re-implement the
        # reduction)
        for j in range(cfg.n):
            out[j] = fh.encode(
                fs, int.from_bytes(dig[j].tobytes(), "little") & mask
            )
        return out
    # little-endian bytes -> 16-bit limbs, masked to exactly rho_bits
    nlimb = min((nbytes + 1) // 2, fs.limbs)
    buf = np.zeros((cfg.n, nlimb * 2), np.uint8)
    buf[:, :nbytes] = dig
    limbs16 = np.ascontiguousarray(buf).view("<u2").astype(np.uint32)
    full, rem = divmod(rho_bits, 16)
    if rem and full < nlimb:
        limbs16[:, full] &= (1 << rem) - 1
    if full + (1 if rem else 0) < nlimb:
        limbs16[:, full + (1 if rem else 0):] = 0
    out[:, :nlimb] = limbs16
    return out


def derive_rho(
    cfg: CeremonyConfig, a_comm, e_comm, shares, hidings, rho_bits: int,
    *, device: bool = True, trace=None,
) -> np.ndarray:
    """rho from the real round-1 transcript — the only sound way to get
    batch randomizers (every caller path: engine, bench, sharded,
    driver entry).

    Binds ALL FOUR round-1 tensors.  The bare commitments A must be
    bound too: they feed ``master_key_from_bare`` and (in the reference,
    round 4) the second share check, so a dealer must not be able to
    pick A after seeing rho any more than E/s/r.

    ``device=True`` (default) hashes the tensors with the Merkle family
    (:func:`transcript_digest_device`), whose backend leg — jitted
    device tree vs numpy batch — is picked by
    ``crypto.device_hash.digest_dispatch`` (DKG_TPU_DIGEST knob);
    ``device=False`` uses the byte-level host audit digest.

    Pass a :class:`dkg_tpu.utils.tracing.CeremonyTrace` to split the
    fiat_shamir span into ``digest`` / ``rho`` sub-timings and record
    which digest leg ran (``digest_dispatch`` meta field).
    """
    from ..crypto import device_hash as dh

    dispatch = dh.digest_dispatch() if device else "audit"
    digest_fn = transcript_digest_device if device else transcript_digest
    t0 = time.perf_counter()
    transcript = digest_fn(cfg, a_comm, e_comm, shares, hidings)
    t1 = time.perf_counter()
    rho = fiat_shamir_rho(cfg, transcript, rho_bits)
    if trace is not None:
        trace.record_sub("fiat_shamir", "digest", t1 - t0)
        trace.record_sub("fiat_shamir", "rho", time.perf_counter() - t1)
        trace.meta["digest_dispatch"] = dispatch
    return rho


class BatchedCeremony:
    """Single-host happy-path ceremony over device arrays: deal, batch
    verify, aggregate, master key.  The complaint path drops to the
    per-party host state machine (dkg_tpu.dkg.committee) which this
    engine mirrors kernel-for-equation."""

    def __init__(self, curve: str, n: int, t: int, shared_string: bytes, rng):
        import time as _time

        from ..groups import precompute as gp

        self.cfg = CeremonyConfig(curve, n, t)
        cs = self.cfg.cs
        self.group = gh.ALL_GROUPS[curve]
        self.ck = CommitmentKey.generate(self.group, shared_string)
        # g/h tables come from the persistent precompute cache: the
        # second ceremony in a process (and, via the disk cache, the
        # second process) pays zero table-build cost.  The stats delta
        # is kept so run() can attribute table-build vs steady-state
        # time in the trace (bench.py's `warm` flag reads it).
        before = gp.stats()
        t0 = _time.perf_counter()
        self.g_table = gp.generator_table(cs)
        self.h_table = gp.base_table(cs, self.ck.h)
        self.table_seconds = _time.perf_counter() - t0
        after = gp.stats()
        self.table_stats = {
            k: after[k] - before[k] for k in after if isinstance(after[k], int)
        }
        self.rng = rng
        fs = cs.scalar
        self.coeffs_a = jnp.asarray(fh.draw_limbs(fs, rng, (n, t + 1)))
        self.coeffs_b = jnp.asarray(fh.draw_limbs(fs, rng, (n, t + 1)))

    def run(self, rho_bits: int = 128, trace=None, tamper=None):
        """Full ceremony over device arrays, including the blame path.

        Happy path: one RLC batch verification covers all n·(n-1) pair
        relations.  If ANY recipient's batch check fails, the engine
        drops to per-pair blame assignment (``verify_pairwise`` — the
        reference's complaint trigger, committee.rs:305-317), records
        one complaint per failing (recipient, dealer) pair, disqualifies
        the guilty dealers (the engine is its own adjudicator: it holds
        the plaintext share matrix, so re-checking IS adjudication —
        the wire path's evidence/DLEQ machinery lives in
        complaints_batch.adjudicate_round1_batch), and completes the
        ceremony over the qualified set (committee.rs:369-398, 453-462).

        Aborts with DkgError(MISBEHAVIOUR_HIGHER_THRESHOLD) when more
        than t dealers are disqualified (committee.rs:340-347).

        Returns a dict of device results; ``complaints`` is a list of
        (accuser_recipient_index, accused_dealer_index) 1-based pairs
        (empty on the happy path) and ``qualified`` the final dealer
        mask.  Pass a :class:`dkg_tpu.utils.tracing.CeremonyTrace` to
        collect per-phase wall-clock + device profiler annotations.

        ``tamper`` is a fault-injection hook for tests: called as
        ``tamper(a, e, s, r) -> (a, e, s, r)`` after dealing, it plays
        the role of the reference tests' hand-corrupted broadcasts
        (committee.rs:1127-1128, 1188).
        """
        import jax as _jax

        from ..utils.tracing import phase_span
        from .errors import DkgError, DkgErrorKind

        cfg = self.cfg
        if trace is not None:
            # table acquisition happened in __init__; record it as its
            # own phase so deal/verify numbers are steady-state
            trace.record("tables", self.table_seconds)
            trace.meta["table_cache"] = dict(self.table_stats)
        with phase_span(trace, "deal"):
            a, e, s, r = deal_chunked(
                cfg, self.coeffs_a, self.coeffs_b, self.g_table, self.h_table
            )
            _jax.block_until_ready(e)
        if tamper is not None:
            a, e, s, r = tamper(a, e, s, r)
        with phase_span(trace, "fiat_shamir"):
            rho = jnp.asarray(derive_rho(cfg, a, e, s, r, rho_bits, trace=trace))
        with phase_span(trace, "verify"):
            ok = verify_batch(cfg, e, s, r, rho, rho_bits, self.g_table, self.h_table)
            _jax.block_until_ready(ok)

        qualified = jnp.ones((cfg.n,), bool)
        complaints: list[tuple[int, int]] = []
        if not bool(np.asarray(ok).all()):
            with phase_span(trace, "blame"):
                pw = np.asarray(
                    verify_pairwise(cfg, e, s, r, self.g_table, self.h_table)
                )  # (n_dealers, n_recipients)
                guilty = ~pw.all(axis=1)
                complaints = [
                    (int(i) + 1, int(j) + 1)
                    for j, i in zip(*np.nonzero(~pw))
                ]
                qualified = jnp.asarray(~guilty)
            if int(guilty.sum()) > cfg.t:
                if trace is not None:
                    trace.meta.update(
                        {"curve": cfg.curve, "n": cfg.n, "t": cfg.t}
                    )
                return {
                    "bare": a,
                    "randomized": e,
                    "shares": s,
                    "hidings": r,
                    "ok": ok,
                    "qualified": qualified,
                    "complaints": complaints,
                    "error": DkgError(DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD),
                }

        with phase_span(trace, "finalise"):
            final_shares = aggregate_shares(cfg, s, qualified)
            master = master_key_from_bare(cfg, a, qualified)
            _jax.block_until_ready(master)
        if trace is not None:
            trace.meta.update({"curve": cfg.curve, "n": cfg.n, "t": cfg.t})
        return {
            "bare": a,
            "randomized": e,
            "shares": s,
            "hidings": r,
            "ok": ok,
            "qualified": qualified,
            "complaints": complaints,
            "final_shares": final_shares,
            "master": master,
        }
