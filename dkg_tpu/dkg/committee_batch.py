"""Device-accelerated round-1 dealing for co-located committee members.

``DistributedKeyGeneration.init`` (committee.py) is the per-party wire
path: serial host scalar-mults per coefficient and per recipient
(mirroring reference committee.rs:124-216).  When a host drives many
parties — the sharded-ceremony deployment, or any simulation — dealing
for all of them at once is a batched device job:

* commitments A_l / E_l for every local dealer: two fixed-base batch
  mults (ceremony.deal; reference hot loop #1, committee.rs:151-159);
* the share matrix via batched Horner (reference hot loop #2,
  committee.rs:163-186 / polynomial.rs:68-74);
* KEM points for every (dealer, recipient) pair: two batched ladder
  calls (hybrid_batch.kem_batch; reference elgamal.rs:134-145);
* DEM sealing + wire packaging host-side (hybrid_batch.seal_shares).

The result is bit-identical in structure to n independent ``init``
calls: each local party gets a ``DkgPhase1`` whose state machine then
proceeds through phases 2-5 exactly as the host path — so the fast
dealing path and the reference-parity protocol logic compose.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from ..crypto.elgamal import SymmetricKey, open_pair_with_kems
from ..fields import host as fh
from ..groups import device as gd
from ..groups import precompute
from ..utils.tracing import CeremonyTrace, phase_span
from .committee import DkgPhase1, DkgPhase2, Environment, FetchedPhase1, _State
from .hybrid_batch import broadcasts_from_batch, seal_shares_pipeline
from .broadcast import (
    BroadcastPhase1,
    BroadcastPhase2,
    MisbehavingPartiesRound1,
    ProofOfMisbehaviour,
)
from .ceremony import CeremonyConfig, deal_chunked
from .errors import DkgError, DkgErrorKind
from .procedure_keys import (
    MemberCommunicationKey,
    decode_scalar_pair,
    sort_committee,
)


def batched_dealing(
    env: Environment,
    rng,
    comm_keys: list[MemberCommunicationKey],
    members: list[int] | None = None,
    trace: CeremonyTrace | None = None,
) -> list[tuple[DkgPhase1, BroadcastPhase1]]:
    """Round-1 dealing for the local parties ``members`` (1-based sorted
    indices; default: every committee member, the in-process-simulation
    case).  ``comm_keys`` holds the full committee's keys in unsorted
    order; each local party must have its key present.

    Returns one (phase1, broadcast) pair per local party, in ``members``
    order — drop-in for per-party ``DistributedKeyGeneration.init``.
    ``trace`` records ``deal`` (engine polynomials + commitments) and
    ``seal`` (KEM + DEM, with a ``pairs_sealed`` counter) separately so
    traces show deal vs seal vs verify time.
    """
    group = env.group
    cs = gd.ALL_CURVES[group.name]
    fs = group.scalar_field
    n, t = env.nr_members, env.threshold
    if len(comm_keys) != n:
        raise ValueError("committee size does not match environment")
    pks = sort_committee(group, [k.public() for k in comm_keys])
    key_by_enc = {k.public().sort_key(group): k for k in comm_keys}
    sorted_keys = [key_by_enc[p.sort_key(group)] for p in pks]
    if members is None:
        members = list(range(1, n + 1))
    m = len(members)

    cfg = CeremonyConfig(group.name, n, t)
    g_table = precompute.generator_table(cs)
    h_table = precompute.base_table(cs, env.commitment_key.h)

    # secret sampling stays host-side CSPRNG (SURVEY §7 hard part f)
    coeffs_a = jnp.asarray(fh.draw_limbs(fs, rng, (m, t + 1)))
    coeffs_b = jnp.asarray(fh.draw_limbs(fs, rng, (m, t + 1)))
    with phase_span(trace, "deal"):
        bare_dev, rand_dev, shares_dev, hidings_dev = deal_chunked(
            cfg, coeffs_a, coeffs_b, g_table, h_table
        )

    # device KEM + DEM for all (dealer, recipient) pairs, chunk-
    # pipelined so host sealing overlaps the next chunk's kernels
    pks_dev = gd.from_host(cs, [p.point for p in pks])
    r_enc = jnp.asarray(fh.draw_limbs(fs, rng, (m, n)))
    with phase_span(trace, "seal"):
        sealed = seal_shares_pipeline(
            group, cfg, shares_dev, hidings_dev, pks_dev, r_enc, g_table
        )
        if trace is not None:
            trace.bump("pairs_sealed", m * n)
    broadcasts = broadcasts_from_batch(group, cfg, np.asarray(rand_dev), sealed)

    shares_host = fh.decode(fs, np.asarray(shares_dev))
    hidings_host = fh.decode(fs, np.asarray(hidings_dev))
    bare_host = [gd.to_host(cs, np.asarray(bare_dev[d])) for d in range(m)]
    rand_host = [gd.to_host(cs, np.asarray(rand_dev[d])) for d in range(m)]

    out = []
    for d, my in enumerate(members):
        state = _State(env, my, sorted_keys[my - 1], pks)
        state.bare_coeff_points = tuple(bare_host[d])
        state.randomized_coeff_points = tuple(rand_host[d])
        state.bare_coeffs[my] = state.bare_coeff_points
        state.randomized_coeffs[my] = state.randomized_coeff_points
        state.received_shares[my] = (
            int(shares_host[d, my - 1]),
            int(hidings_host[d, my - 1]),
        )
        out.append((DkgPhase1(state), broadcasts[d]))
    return out


def batched_share_verification(
    phase1s: list[DkgPhase1],
    fetched: list[FetchedPhase1],
    rng,
) -> list[tuple["DkgPhase2 | DkgError", BroadcastPhase2 | None]]:
    """Round-2 share verification for many co-located parties at once.

    Semantics are EXACTLY per-party ``DkgPhase1.proceed(fetched, rng)``
    (reference hot loop committee.rs:273-317) — same state mutations,
    complaints (sender order preserved), error returns, and threshold
    abort — but the two per-pair device costs run as bulk kernels over
    all (recipient, dealer) pairs:

    * KEM recovery sk_i * e1 (one per distinct pair e1): one batched
      ``scalar_mul`` call instead of n*(n-1) host ladder walks;
    * the commitment check g*s + h*s' == sum_l x_i^l E_{j,l}: two
      fixed-base batch mults + one batched point-Horner
      (committee.rs:292-296 as one wide op).

    ChaCha DEM decode, scalar decoding, and the (rare) complaint
    evidence generation stay host-side.  ``fetched`` is the shared
    broadcast-channel view every local party consumes — the in-process
    simulation seam (reference: committee.rs:1337-1338).
    """
    if not phase1s:
        return []
    sts = [p._state for p in phase1s]
    env, group = sts[0].env, sts[0].group
    cs = gd.ALL_CURVES[group.name]
    fs = group.scalar_field
    sender_order = [f.sender_index for f in fetched]

    # --- stage 1: host triage in fetched order (dropouts, misaddressed
    # data), collecting one KEM exponentiation per distinct pair e1
    kem_sks: list[int] = []
    kem_pts: list[tuple] = []
    jobs: list[tuple[int, int, object, int, int]] = []
    errors: list[DkgError | None] = [None] * len(sts)
    for i, st in enumerate(sts):
        for f in fetched:
            j = f.sender_index
            if j == st.index:
                continue
            if f.broadcast is None:
                st.disqualify(j)  # silent dropout (committee.rs:332-337)
                continue
            mine = f.broadcast.shares_for(st.index)
            if mine is None or mine.recipient_index != st.index:
                errors[i] = DkgError(DkgErrorKind.FETCHED_INVALID_DATA, index=j)
                break
            k1 = len(kem_sks)
            kem_sks.append(st.comm_key.sk)
            kem_pts.append(mine.share_ct.e1)
            if group.eq(mine.share_ct.e1, mine.randomness_ct.e1):
                k2 = k1  # canonical sealed-pair layout: one KEM for both
            else:
                k2 = len(kem_sks)
                kem_sks.append(st.comm_key.sk)
                kem_pts.append(mine.randomness_ct.e1)
            jobs.append((i, j, mine, k1, k2))

    # --- stage 2: all KEM exponentiations as one device batch
    kem_host: list = []
    if kem_sks:
        kem_dev = gd.scalar_mul(
            cs, jnp.asarray(fh.encode(fs, kem_sks)), gd.from_host(cs, kem_pts)
        )
        kem_host = gd.to_host(cs, np.asarray(kem_dev))

    # --- stage 3: host DEM decode; failures become complaints, decodable
    # pairs queue for the batched commitment check
    complaint_at: dict[tuple[int, int], MisbehavingPartiesRound1] = {}
    share_jobs: list[tuple[int, int, object, int, int]] = []
    for i, j, mine, k1, k2 in jobs:
        st = sts[i]
        pt1, pt2 = open_pair_with_kems(
            group,
            SymmetricKey(kem_host[k1]),
            SymmetricKey(kem_host[k2]),
            mine.share_ct,
            mine.randomness_ct,
        )
        (s, r), kind = decode_scalar_pair(group, pt1, pt2)
        if s is None or r is None:
            st.disqualify(j)  # committee.rs:318-331
            complaint_at[(i, j)] = MisbehavingPartiesRound1(
                j,
                kind or DkgErrorKind.SCALAR_OUT_OF_BOUNDS,
                ProofOfMisbehaviour.generate(group, mine, st.comm_key, rng),
            )
            continue
        share_jobs.append((i, j, mine, s, r))

    # --- stage 4: every commitment check as one device batch (the
    # shared implementation complaint adjudication also uses; dealer
    # commitments converted host->device once per dealer, not per pair)
    if share_jobs:
        from .complaints_batch import check_randomized_shares_limbs

        s_limbs = jnp.asarray(fh.encode(fs, [x[3] for x in share_jobs]))
        r_limbs = jnp.asarray(fh.encode(fs, [x[4] for x in share_jobs]))
        by_sender = {f.sender_index: f.broadcast for f in fetched}
        coeff_np: dict[int, np.ndarray] = {}
        for _, j, *_ in share_jobs:
            if j not in coeff_np:
                coeff_np[j] = np.asarray(
                    gd.from_host(cs, list(by_sender[j].committed_coefficients))
                )
        cpts = jnp.asarray(np.stack([coeff_np[j] for _, j, *_ in share_jobs]))
        idx = jnp.asarray([sts[i].index for i, *_ in share_jobs], dtype=jnp.uint32)
        nbits = max(2, int(env.nr_members).bit_length())
        ok = check_randomized_shares_limbs(
            group, cs, env.commitment_key, idx, s_limbs, r_limbs, cpts, nbits
        )
        for (i, j, mine, s, r), good in zip(share_jobs, ok):
            st = sts[i]
            if bool(good):
                st.received_shares[j] = (s, r)
                st.randomized_coeffs[j] = tuple(
                    by_sender[j].committed_coefficients
                )
            else:
                st.disqualify(j)  # committee.rs:305-317
                complaint_at[(i, j)] = MisbehavingPartiesRound1(
                    j,
                    DkgErrorKind.SHARE_VALIDITY_FAILED,
                    ProofOfMisbehaviour.generate(group, mine, st.comm_key, rng),
                )

    # --- stage 5: per-party assembly, complaints in fetched sender order
    results: list[tuple[DkgPhase2 | DkgError, BroadcastPhase2 | None]] = []
    for i, st in enumerate(sts):
        if errors[i] is not None:
            results.append((errors[i], None))
            continue
        comps = tuple(
            complaint_at[(i, j)] for j in sender_order if (i, j) in complaint_at
        )
        broadcast = BroadcastPhase2(comps) if comps else None
        if len(comps) > env.threshold:
            results.append(
                (DkgError(DkgErrorKind.MISBEHAVIOUR_HIGHER_THRESHOLD), broadcast)
            )
        else:
            results.append((DkgPhase2(st), broadcast))
    return results
