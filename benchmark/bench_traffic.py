"""What every traffic kind shares: the mix's block of shapes and a request from a seed."""

from __future__ import annotations

import functools
import math


def mix_block(config: dict) -> list[tuple[int, int]]:
    """The smallest list of (n, t) that holds the mix's shapes in exact proportion."""
    counts = [int(m["count"]) for m in config["mix"]]
    g = functools.reduce(math.gcd, counts)
    return [(int(m["n"]), int(m["t"])) for m in config["mix"] for _ in range(int(m["count"]) // g)]


def request_for(config: dict, shape: tuple[int, int], seed: int, index: int) -> dict:
    """One seeded ceremony request; its own seed is unique within the run."""
    return {
        "curve": config["curve"],
        "n": shape[0],
        "t": shape[1],
        "seed": (int(seed) << 24) + index,
        "rho_bits": int(config["rho_bits"]),
    }
