"""In-process epoch operations over a full share vector.

The service lane (service.scheduler) holds ALL final shares of a hosted
ceremony in one process, so refresh/reshare need no channel, no sealing
and no complaints — just the polynomial algebra, batched on device:

* refresh: every "dealer" row i contributes a zero-constant degree-t
  polynomial u_i; new_share_j = old_share_j + sum_i u_i(j).  The
  aggregate constant F(0) gains sum_i u_i(0) = 0, so the master key is
  untouched by construction.
* reshare: dealer row i deals a degree-t' polynomial h_i with
  h_i(0) = old_share_i; new_share_j = sum_i lambda_i * h_i(j) with
  lambda_i the Lagrange-at-zero coefficients of the OLD indices.  The
  new aggregate's constant is sum_i lambda_i * old_share_i = F(0).

Both are one :func:`~dkg_tpu.poly.device.eval_many` call (an (n,
t+1)-coefficient tensor evaluated at all recipient indices at once)
plus field-add folds — no per-pair scalar loops (lint rule DKG008).
tests/test_epoch_inprocess.py pins both against the poly.host oracle.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..fields import device as fd
from ..fields import host as fh
from ..fields.host import FieldSpec
from ..poly import device as poly_device


def _indices(fs: FieldSpec, n: int) -> jnp.ndarray:
    return jnp.asarray(fh.encode(fs, list(range(1, n + 1))))  # (n, L)


def _coeff_tensor(fs: FieldSpec, constants: list[int], ncoeffs: int, rng):
    """(rows, ncoeffs, L) coefficient tensor: column 0 holds
    ``constants``, the rest fresh CSPRNG scalars (host-side sampling,
    like the ceremony's batched_dealing)."""
    out = np.empty((len(constants), ncoeffs, fs.limbs), np.uint32)
    out[:, 0] = fh.encode(fs, list(constants))
    out[:, 1:] = fh.draw_limbs(fs, rng, (len(constants), ncoeffs - 1))
    return jnp.asarray(out)


def _fold_dealers(fs: FieldSpec, m: jnp.ndarray) -> jnp.ndarray:
    """Sum an (n_dealers, n_recipients, L) share matrix over dealers."""
    acc = m[0]
    for i in range(1, m.shape[0]):
        acc = fd.add(fs, acc, m[i])
    return acc


def refresh_shares(
    fs: FieldSpec, n: int, t: int, shares: list[int], rng
) -> list[int]:
    """Proactively refresh a full (n, t) share vector; the shared
    secret (and master key) is invariant.  Returns the new shares."""
    if len(shares) != n:
        raise ValueError(f"expected {n} shares, got {len(shares)}")
    coeffs = _coeff_tensor(fs, [0] * n, t + 1, rng)  # (n, t+1, L)
    deltas = poly_device.eval_many(fs, coeffs, _indices(fs, n))  # (n, n, L)
    old = jnp.asarray(fh.encode(fs, shares))
    new = fd.add(fs, old, _fold_dealers(fs, deltas))
    return [int(v) for v in fh.decode(fs, np.asarray(new))]


def reshare_shares(
    fs: FieldSpec,
    n: int,
    t: int,
    shares: list[int],
    n_new: int,
    t_new: int,
    rng,
) -> list[int]:
    """Reshare an (n, t) share vector into a fresh (n_new, t_new) one of
    the SAME secret.  Returns the new committee's shares (1..n_new)."""
    if len(shares) != n:
        raise ValueError(f"expected {n} shares, got {len(shares)}")
    if n < t + 1:
        raise ValueError(f"need at least t+1={t + 1} dealers, have {n}")
    if n_new < t_new + 1:
        raise ValueError(
            f"new committee of {n_new} cannot reconstruct at threshold "
            f"{t_new} (need n' >= t'+1)"
        )
    coeffs = _coeff_tensor(fs, shares, t_new + 1, rng)  # (n, t_new+1, L)
    m = poly_device.eval_many(fs, coeffs, _indices(fs, n_new))  # (n, n_new, L)
    lam = poly_device.lagrange_at_zero_coeffs(fs, _indices(fs, n))  # (n, L)
    lam_b = jnp.broadcast_to(lam[:, None, :], m.shape)
    new = _fold_dealers(fs, fd.mul(fs, lam_b, m))  # (n_new, L)
    return [int(v) for v in fh.decode(fs, np.asarray(new))]
