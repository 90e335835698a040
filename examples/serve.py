"""Ceremony-service walkthrough: submit / poll / result with backpressure.

Runs a tiny multi-tenant :class:`~dkg_tpu.service.scheduler.
CeremonyScheduler` in-process (two workers over one warm runtime),
submits a handful of seeded ceremonies, polls one through its
queued -> running -> done lifecycle, and then deliberately overflows a
depth-2 admission queue to show the reject-on-full contract a fronting
HTTP server would map to 503 + Retry-After.

The shapes are deliberately small (n=5 pads to the smallest (8, 2)
bucket) so the example compiles in seconds on a laptop CPU; see
scripts/fleet_bench.py for the throughput-shaped workload and
docs/service.md for the architecture.

Run:  JAX_PLATFORMS=cpu python examples/serve.py
"""

from __future__ import annotations

import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from dkg_tpu.service import (
    CeremonyRequest,
    CeremonyScheduler,
    QueueFullError,
    WarmRuntime,
)


def main() -> int:
    runtime = WarmRuntime()

    # -- a small service: 2 workers, room for 8 queued ceremonies -------
    with CeremonyScheduler(
        concurrency=2, queue_depth=8, batch_max=2, runtime=runtime
    ) as service:
        print("submit: 4 seeded ceremonies (n=5, t=2 -> bucket (8,2))")
        reqs = [
            CeremonyRequest("ristretto255", 5, 2, seed=1000 + i, rho_bits=32)
            for i in range(4)
        ]
        ids = [service.submit(r) for r in reqs]
        for cid in ids:
            print(f"  admitted {cid}: {service.poll(cid)}")

        # poll the first one through its lifecycle (a real client would
        # poll over HTTP; the status strings are the contract)
        seen = []
        while service.poll(ids[0]) not in ("done", "failed", "expired"):
            status = service.poll(ids[0])
            if not seen or seen[-1] != status:
                seen.append(status)
            time.sleep(0.05)
        seen.append(service.poll(ids[0]))
        print(f"lifecycle of {ids[0]}: {' -> '.join(seen)}")

        for cid in ids:
            out = service.result(cid, timeout=600)
            assert out.status == "done", out
            print(
                f"  {cid}: {out.status}, master {out.master.hex()[:16]}..., "
                f"qualified {sum(out.qualified)}/{out.n}"
            )

    # -- backpressure: a full queue REJECTS instead of blocking ---------
    print("\nbackpressure: queue_depth=2, burst of 6 submissions")
    with CeremonyScheduler(
        concurrency=1, queue_depth=2, batch_max=1, runtime=runtime
    ) as tiny:
        admitted, rejected = [], 0
        for i in range(6):
            try:
                admitted.append(
                    tiny.submit(
                        CeremonyRequest("ristretto255", 5, 2, seed=2000 + i, rho_bits=32)
                    )
                )
            except QueueFullError as exc:
                # an HTTP front door maps this to 503 + Retry-After
                rejected += 1
                print(f"  submission {i}: rejected ({exc})")
        print(f"  admitted {len(admitted)}, rejected {rejected}")
        for cid in admitted:
            out = tiny.result(cid, timeout=600)
            print(f"  {cid}: {out.status}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
