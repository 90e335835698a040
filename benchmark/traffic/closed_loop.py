"""Closed loop: `outstanding` requests in flight, the next sent when one is fetched.

Parameters (the cell's file): `outstanding`.  The requests are the
configuration's mix, block after block; each block holds every shape in the
mix's exact proportions and is shuffled from the seed, so every seed offers
the same work in another order.  No due times: a slow system is sent less.
"""

from __future__ import annotations

import itertools
import random

from bench_traffic import mix_block, request_for


def plan(params: dict, config: dict, seed: int, seconds: float) -> dict:
    del seconds  # the harness stops sending when the window closes
    rng = random.Random(seed)
    block = mix_block(config)

    def stream():
        for i in itertools.count():
            if i % len(block) == 0:
                shapes = list(block)
                rng.shuffle(shapes)
            yield None, request_for(config, shapes[i % len(block)], seed, i)

    return {"outstanding": int(params["outstanding"]), "requests": stream()}
