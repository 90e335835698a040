"""Open loop: requests sent on a schedule at `rate_per_s`, whatever the system does.

Parameters (the cell's file): `rate_per_s`.  Over `seconds` that is
N = round(rate * seconds) arrivals.  Their gaps are the N stratified quantiles
of the exponential distribution with that rate, -ln(1 - (i + 1/2)/N) / rate,
shuffled from the seed and scaled to fill the window exactly: Poisson-shaped
arrivals, and every seed has the same set of gaps and the same count of each
shape in another order, so that seeds differ in order and not in load.
Latency is taken from the due time, not from when the generator got to it.
"""

from __future__ import annotations

import math
import random

from bench_traffic import mix_block, request_for


def plan(params: dict, config: dict, seed: int, seconds: float) -> dict:
    rate = float(params["rate_per_s"])
    count = max(1, round(rate * seconds))
    rng = random.Random(seed)
    gaps = [-math.log(1.0 - (i + 0.5) / count) / rate for i in range(count)]
    rng.shuffle(gaps)
    scale = seconds / sum(gaps) * (count / (count + 1.0))
    block = mix_block(config)
    shapes = [block[i % len(block)] for i in range(count)]
    rng.shuffle(shapes)
    due, requests = 0.0, []
    for i in range(count):
        due += gaps[i] * scale
        requests.append((due, request_for(config, shapes[i], seed, i)))
    return {"outstanding": None, "requests": iter(requests)}
