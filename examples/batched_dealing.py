"""Device-batched dealing feeding the standard wire protocol.

Round 1 for all four parties runs as batched device kernels
(commitments, share matrix, KEM) via dkg_tpu.dkg.committee_batch;
rounds 2-5 then proceed through the reference-parity per-party state
machine — demonstrating that the fast dealing path and the wire
protocol compose (run: python examples/batched_dealing.py).
"""

import pathlib
import random
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from dkg_tpu.dkg.committee import (
    Environment,
    FetchedComplaints2,
    FetchedComplaints4,
    FetchedPhase1,
    FetchedPhase3,
    FetchedPhase5,
)
from dkg_tpu.dkg.committee_batch import batched_dealing
from dkg_tpu.dkg.procedure_keys import MemberCommunicationKey
from dkg_tpu.groups import host as gh


def main() -> None:
    rng = random.SystemRandom()
    group = gh.RISTRETTO255
    n, t = 4, 1
    env = Environment.init(group, t, n, b"batched-dealing-example")
    keys = [MemberCommunicationKey.generate(group, rng) for _ in range(n)]

    # round 1: ONE batched device job deals for every local party
    dealt = batched_dealing(env, rng, keys)
    phases = [p for p, _ in dealt]
    broadcasts = [b for _, b in dealt]
    print(f"dealt for {n} parties in one batched job")

    fetched1 = [FetchedPhase1.from_broadcast(env, j + 1, broadcasts[j]) for j in range(n)]
    phases2 = []
    for p in phases:
        nxt, complaints = p.proceed(fetched1, rng)
        assert complaints is None
        phases2.append(nxt)
    print("round 2: all shares verified, no complaints")

    phases3, b3 = [], []
    for p in phases2:
        nxt, b = p.proceed([FetchedComplaints2(i + 1, None) for i in range(n)], fetched1)
        phases3.append(nxt)
        b3.append(b)
    phases4 = []
    for p in phases3:
        nxt, _ = p.proceed([FetchedPhase3.from_broadcast(env, j + 1, b3[j]) for j in range(n)])
        phases4.append(nxt)
    phases5 = []
    for p in phases4:
        nxt, _ = p.proceed([FetchedComplaints4(i + 1, None) for i in range(n)])
        phases5.append(nxt)

    results = [p.finalise([FetchedPhase5(i + 1, None) for i in range(n)])[0] for p in phases5]
    masters = [m for m, _ in results]
    assert all(group.eq(m.point, masters[0].point) for m in masters)
    print("rounds 3-5: master public key agreed by all parties")
    print("master:", group.encode(masters[0].point).hex())


if __name__ == "__main__":
    main()
