"""Device legs of epoch operations — thin wrappers over the ceremony's
batched dealing/verify kernels.

Everything EC-expensive in an epoch op goes through the same entry
points the ceremony uses (lint rule DKG008 pins this):

* dealing: :func:`~dkg_tpu.dkg.ceremony.deal_chunked` (commitments +
  share rows in one batched call) and
  :func:`~dkg_tpu.dkg.hybrid_batch.seal_shares_pipeline` (KEM+DEM for
  all recipients at once), packaged by ``broadcasts_from_batch``;
* recipient-side decryption: ``open_shares_batch`` (one batched KEM
  recovery for all dealers);
* share verification: ``gd.fixed_base_mul`` + ``gd.eval_point_poly``
  over all (dealer, share) rows at once — the bare-commitment twin of
  complaints_batch.check_randomized_shares_limbs (epochs carry no
  Pedersen hiding leg, the dealt constants are already bound by the
  previous epoch's commitments).
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..dkg.ceremony import CeremonyConfig, deal_chunked
from ..dkg.hybrid_batch import (
    broadcasts_from_batch,
    open_shares_batch,
    seal_shares_pipeline,
)
from ..fields import host as fh
from ..groups import device as gd
from ..groups import precompute
from ..groups import host as gh


def epoch_cfg(group: gh.HostGroup, n: int, t: int) -> CeremonyConfig:
    """Jit-static shape of one epoch dealing: the RECIPIENT committee's
    (n, t)."""
    return CeremonyConfig(group.name, n, t)


def deal_epoch_poly(
    group: gh.HostGroup,
    cfg: CeremonyConfig,
    constant: int,
    rng,
    recipient_pks: list,
) -> tuple[tuple, tuple]:
    """Deal one degree-``cfg.t`` polynomial with the given constant term
    to ``cfg.n`` recipients via the batched ceremony kernels.

    constant = 0 is a refresh deal (zero-constant, master-invariant);
    constant = the dealer's current share is a reshare deal
    (shares-of-the-share).  Returns ``(commitments, encrypted_shares)``
    — the (t+1) BARE commitment points and one sealed EncryptedShares
    per recipient.  The hiding polynomial is identically zero: epochs
    use bare Feldman commitments only.
    """
    cs, fs = cfg.cs, group.scalar_field
    coeffs = np.empty((1, cfg.t + 1, fs.limbs), np.uint32)
    coeffs[0, 0] = fh.encode(fs, constant)
    coeffs[0, 1:] = fh.draw_limbs(fs, rng, (cfg.t,))
    coeffs_a = jnp.asarray(coeffs)
    coeffs_b = jnp.zeros_like(coeffs_a)
    g_table = precompute.generator_table(cs)
    # zero hiding coefficients make the h-leg a no-op, so the g table
    # stands in for h — epochs need no commitment key at all
    bare, _rand, shares, hidings = deal_chunked(
        cfg, coeffs_a, coeffs_b, g_table, g_table
    )
    pks_dev = gd.from_host(cs, [p.point for p in recipient_pks])
    r_enc = jnp.asarray(fh.draw_limbs(fs, rng, (1, cfg.n)))
    sealed = seal_shares_pipeline(
        group, cfg, shares, hidings, pks_dev, r_enc, g_table
    )
    b = broadcasts_from_batch(group, cfg, np.asarray(bare), sealed)[0]
    return b.committed_coefficients, b.encrypted_shares


def open_my_shares(
    group: gh.HostGroup,
    cfg: CeremonyConfig,
    sk: int,
    deals: dict,
    my_index: int,
) -> dict:
    """Decrypt this member's sealed share from every deal in one
    batched KEM recovery: {dealer_index: share_int | None}."""
    order = sorted(deals)
    pairs = []
    for j in order:
        es = deals[j].shares_for(my_index)
        pairs.append((es.share_ct, es.randomness_ct))
    vals = open_shares_batch(group, cfg, sk, pairs)
    return {j: vals[k][0] for k, j in enumerate(order)}


def check_bare_shares(
    group: gh.HostGroup,
    indices: list[int],
    shares: list[int],
    coeffs_list: list[tuple],
) -> np.ndarray:
    """Batched g*s == sum_l idx^l A_l over k independent (dealer, share)
    rows — one fixed-base batch mult + one batched point-Horner."""
    if not indices:
        return np.zeros((0,), dtype=bool)
    cs = gd.ALL_CURVES[group.name]
    fs = group.scalar_field
    k, tp1 = len(indices), len(coeffs_list[0])
    s_limbs = jnp.asarray(fh.encode(fs, shares))
    flat = [c for coeffs in coeffs_list for c in coeffs]
    cpts = gd.from_host(cs, flat).reshape(k, tp1, cs.ncoords, cs.field.limbs)
    idx = jnp.asarray(indices, dtype=jnp.uint32)
    nbits = max(2, int(max(indices)).bit_length())
    lhs = gd.fixed_base_mul(cs, precompute.generator_table(cs), s_limbs)
    rhs = gd.eval_point_poly(cs, cpts, idx, nbits)
    return np.asarray(gd.eq(cs, lhs, rhs))


def check_reshare_constants(
    group: gh.HostGroup,
    prev_commitments: tuple,
    dealer_indices: list[int],
    claimed_constants: list,
) -> np.ndarray:
    """Batched A_{i,0} == eval(prev_commitments, i): a reshare dealer's
    constant term must commit to its ACTUAL share of the current
    aggregate — the binding that makes the reshared secret provably the
    old one."""
    if not dealer_indices:
        return np.zeros((0,), dtype=bool)
    cs = gd.ALL_CURVES[group.name]
    k, tp1 = len(dealer_indices), len(prev_commitments)
    prev = gd.from_host(cs, list(prev_commitments))
    cpts = jnp.broadcast_to(
        prev[None], (k, tp1, cs.ncoords, cs.field.limbs)
    )
    idx = jnp.asarray(dealer_indices, dtype=jnp.uint32)
    nbits = max(2, int(max(dealer_indices)).bit_length())
    lhs = gd.from_host(cs, list(claimed_constants))
    rhs = gd.eval_point_poly(cs, cpts, idx, nbits)
    return np.asarray(gd.eq(cs, lhs, rhs))


def combine_reshare_commitments(
    group: gh.HostGroup,
    lam_limbs: jnp.ndarray,  # (M, L) Lagrange-at-zero coefficients
    coeffs_list: list[tuple],  # M dealers' (t'+1) commitment tuples
) -> tuple:
    """New aggregate commitments C'_l = sum_i lambda_i * A_{i,l} as ONE
    batched scalar-mult over all M*(t'+1) points plus a point-add fold."""
    cs = gd.ALL_CURVES[group.name]
    m, tp1 = len(coeffs_list), len(coeffs_list[0])
    flat = [c for coeffs in coeffs_list for c in coeffs]
    pts = gd.from_host(cs, flat).reshape(m, tp1, cs.ncoords, cs.field.limbs)
    lam_b = jnp.broadcast_to(lam_limbs[:, None, :], (m, tp1, lam_limbs.shape[-1]))
    scaled = gd.scalar_mul(cs, lam_b, pts)
    acc = scaled[0]
    for i in range(1, m):
        acc = gd.add(cs, acc, scaled[i])
    return tuple(gd.to_host(cs, np.asarray(acc)))
