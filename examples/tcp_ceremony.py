"""A real networked ceremony: TCP hub + one thread per party.

Each party only talks to the broadcast hub (publish once per round,
fetch everyone's round messages) — the deployment shape the reference
delegates to "the blockchain" (src/lib.rs:91-92).  Swap the threads for
processes/machines by pointing TcpHubChannel at the hub's address.

The transport is hardened for flaky networks: RPCs retry with capped
exponential backoff, the whole ceremony shares one fetch-deadline
budget, and the hub keeps the first publish per (round, sender) while
recording equivocation attempts as evidence (docs/fault_model.md; tune
via DKG_TPU_NET_* or the TcpHubChannel keyword arguments below).
Run: python examples/tcp_ceremony.py
"""

import pathlib
import random
import sys
import threading

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from dkg_tpu.dkg.committee import Environment
from dkg_tpu.dkg.procedure_keys import MemberCommunicationKey, sort_committee
from dkg_tpu.groups import host as gh
from dkg_tpu.net import TcpHub, TcpHubChannel, run_party


def main() -> None:
    group = gh.RISTRETTO255
    rng = random.SystemRandom()
    n, t = 4, 1

    env = Environment.init(group, t, n, b"tcp-ceremony-example")
    keys = [MemberCommunicationKey.generate(group, rng) for _ in range(n)]
    pks = sort_committee(group, [k.public() for k in keys])
    by_pk = {group.encode(k.public().point): k for k in keys}
    sorted_keys = [by_pk[group.encode(p.point)] for p in pks]

    hub = TcpHub().start()
    host, port = hub.address
    print(f"hub listening on {host}:{port}")

    results = [None] * n

    def party(i: int) -> None:
        # attempts/backoff ride out transient socket failures; budget_s
        # caps the ceremony's total fetch waiting so silent parties cost
        # one shared deadline, not one timeout per round
        chan = TcpHubChannel(host, port, attempts=6, backoff_ms=100, budget_s=240.0)
        results[i] = run_party(
            chan, env, sorted_keys[i], pks, i + 1, random.SystemRandom(), timeout=60.0
        )

    threads = [threading.Thread(target=party, args=(i,)) for i in range(n)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    hub.stop()

    assert all(r and r.ok for r in results)
    m0 = results[0].master.point
    assert all(group.eq(r.master.point, m0) for r in results)
    print(f"{n} parties agreed on master key: {group.encode(m0).hex()}")


if __name__ == "__main__":
    main()
