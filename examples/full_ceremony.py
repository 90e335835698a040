"""Complete 3-party DKG ceremony walkthrough (per-party host API).

The executable-spec equivalent of the reference crate's root doctest
(reference: src/lib.rs:60-182): three parties run all five rounds over a
simulated broadcast channel, derive the same master public key, and
verify that Lagrange interpolation of their secret shares reproduces it.

Run:  python examples/full_ceremony.py
"""

from __future__ import annotations

import pathlib
import random
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from dkg_tpu.dkg import (
    DistributedKeyGeneration,
    DkgError,
    Environment,
    FetchedPhase1,
    FetchedPhase3,
    MemberCommunicationKey,
    sort_committee,
)
from dkg_tpu.groups import host as gh
from dkg_tpu.poly import lagrange_interpolation


def main(curve=gh.RISTRETTO255, n=3, t=1, rng=None):
    rng = rng or random.SystemRandom()
    group = curve

    # --- setup: environment + long-term communication keys -------------
    env = Environment.init(group, t, n, b"example shared string")
    keys = [MemberCommunicationKey.generate(group, rng) for _ in range(n)]
    pks = sort_committee(group, [k.public() for k in keys])
    # place each key at its canonical (sorted) committee position
    by_pos = [None] * n
    for k in keys:
        enc = group.encode(k.public().point)
        pos = next(i for i, pk in enumerate(pks) if group.encode(pk.point) == enc)
        by_pos[pos] = k

    # --- round 1: everyone deals --------------------------------------
    phase1, round1 = [], []
    for i in range(n):
        ph, b = DistributedKeyGeneration.init(env, rng, by_pos[i], pks, i + 1)
        phase1.append(ph)
        round1.append(b)

    # "Parties publish in the blockchain; all parties fetch the data."
    def fetch1(me):
        return [
            FetchedPhase1.from_broadcast(env, j + 1, round1[j])
            for j in range(n)
            if j != me
        ]

    # --- round 2: verify received shares ------------------------------
    phase2 = []
    for i in range(n):
        nxt, complaints = phase1[i].proceed(fetch1(i), rng)
        assert not isinstance(nxt, DkgError), nxt
        assert complaints is None  # honest run: nothing to complain about
        phase2.append(nxt)

    # --- round 3: qualified set + bare commitments ---------------------
    all_r1 = [FetchedPhase1.from_broadcast(env, j + 1, round1[j]) for j in range(n)]
    phase3, round3 = [], []
    for i in range(n):
        nxt, b = phase2[i].proceed([], all_r1)
        assert not isinstance(nxt, DkgError), nxt
        phase3.append(nxt)
        round3.append(b)

    # --- round 4: re-verify against bare commitments -------------------
    def fetch3(me):
        return [
            FetchedPhase3.from_broadcast(env, j + 1, round3[j])
            for j in range(n)
            if j != me
        ]

    phase4 = []
    for i in range(n):
        nxt, complaints = phase3[i].proceed(fetch3(i))
        assert not isinstance(nxt, DkgError), nxt
        phase4.append(nxt)

    # --- round 5 + finalise --------------------------------------------
    results = []
    for i in range(n):
        ph5, _ = phase4[i].proceed([])
        assert not isinstance(ph5, DkgError)
        res, _ = ph5.finalise([])
        assert not isinstance(res, DkgError), res
        results.append(res)

    # --- consistency: one key to rule them all -------------------------
    # (the caller-side cross-checks from the reference's walkthrough,
    # lib.rs:172-177 — a mismatch is DkgError(INCONSISTENT_MASTER_KEY))
    master = results[0][0]
    err = master.check_consistent(group, [mk for mk, _ in results[1:]])
    assert err is None, err

    shares = [r[1].value for r in results]
    secret = lagrange_interpolation(
        group.scalar_field, 0, shares[: t + 1], list(range(1, t + 2))
    )
    err = master.check_reproduced_by(group, secret)
    assert err is None, err

    print(f"ceremony OK: n={n} t={t} curve={group.name}")
    print(f"master public key: {group.encode(master.point).hex()}")
    return master


if __name__ == "__main__":
    main()
