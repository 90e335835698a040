"""Plain reference for a seeded ceremony: what the served path must return, bit for bit.

A request's seed fixes its dealers' polynomials: `random.Random(seed)` draws
the n x (t+1) coefficients of the sharing polynomials row by row, each by
rejection from `getrandbits(bits of q)`.  From those alone:

* master key  = (sum_i a_i0 mod q) * G, encoded as the group encodes it;
* final share of party j (1-based, x_j = j as in dkg/committee.py)
              = sum_i f_i(j) mod q = sum_k (sum_i a_ik) j^k mod q,
  laid out as the engine returns it: little-endian 16-bit limbs in uint32.

Python ints and `dkg_tpu/groups/host.py` (the big-int group and the field
modulus it carries) only: nothing the device, the engine or the limb field
code computed.
"""

from __future__ import annotations

import random

import numpy as np

from dkg_tpu.groups import host as gh

LIMB_BITS = 16


def column_sums(curve: str, n: int, t: int, seed: int) -> list[int]:
    """sum_i a_ik mod q for k = 0..t, the coefficients of the summed polynomial."""
    q = gh.ALL_GROUPS[curve].scalar_field.modulus
    bits = q.bit_length()
    rng = random.Random(seed)
    sums = [0] * (t + 1)
    for _ in range(n):
        for k in range(t + 1):
            x = rng.getrandbits(bits)
            while x >= q:
                x = rng.getrandbits(bits)
            sums[k] += x
    return [s % q for s in sums]


def master_bytes(curve: str, sums: list[int]) -> bytes:
    group = gh.ALL_GROUPS[curve]
    return group.encode(group.scalar_mul_vartime(sums[0], group.generator()))


def final_share(curve: str, sums: list[int], party: int) -> int:
    """Horner evaluation of the summed polynomial at x = party (1-based)."""
    q = gh.ALL_GROUPS[curve].scalar_field.modulus
    acc = 0
    for c in reversed(sums):
        acc = (acc * party + c) % q
    return acc


def share_limbs(value: int, n_limbs: int) -> np.ndarray:
    return np.array(
        [(value >> (LIMB_BITS * i)) & ((1 << LIMB_BITS) - 1) for i in range(n_limbs)], np.uint32
    )


def check_outcome(request: dict, outcome, parties: list[int]) -> dict:
    """Counts of what differs between one fetched outcome and the reference.

    `request` is {"curve", "n", "t", "seed"}; `parties` are the 1-based
    indices whose final shares are compared (empty: the master key only).
    Every count has the limit 0.
    """
    n, t = request["n"], request["t"]
    bad = {"not_done": 0, "unqualified": 0, "complaints": 0, "master_mismatch": 0, "share_limbs_off": 0}
    if outcome.status != "done":
        bad["not_done"] = 1
        return bad
    bad["unqualified"] = sum(1 for q in outcome.qualified if not q) + abs(len(outcome.qualified) - n)
    bad["complaints"] = len(outcome.complaints)
    sums = column_sums(request["curve"], n, t, request["seed"])
    bad["master_mismatch"] = int(bytes(outcome.master) != master_bytes(request["curve"], sums))
    shares = np.asarray(outcome.final_shares)
    if shares.shape[0] != n:
        bad["share_limbs_off"] = shares.size or 1
        return bad
    for j in parties:
        want = share_limbs(final_share(request["curve"], sums, j), shares.shape[1])
        bad["share_limbs_off"] += int((shares[j - 1] != want).sum())
    return bad
