"""Closed loop (`closed_loop.plan`, imported, not copied) for a cell whose
programs only a program with a given seam can prepare inside a run's set-up.

Parameters (the cell's file): `outstanding`, and `needs`: `{"module":
..., "attribute": ...}`, something the program has had since it could serve
the cell's configuration from set-up to the first timed request inside the
limits (1200 s the first time in a checkout, 360 s after).  The harness calls
`plan` before it builds a table or a program, so a program without the seam
stops here, in the first minute and with a line that says why, where it would
otherwise bake for most of an hour (`ceremony_n1024.closed`: the (1024,341)
store took 2882 s before the seam, PERF.md section 6).
"""

from __future__ import annotations

import importlib

from traffic import closed_loop  # benchmark/ is on sys.path (run.py puts it there)


def plan(params: dict, config: dict, seed: int, seconds: float) -> dict:
    needs = params["needs"]
    try:
        module = importlib.import_module(needs["module"])
    except ImportError:
        module = None
    if not hasattr(module, needs["attribute"]):
        shapes = sorted({(int(m["n"]), int(m["t"])) for m in config["mix"]})
        raise SystemExit(
            f"benchmark: this program has no {needs['module']}.{needs['attribute']}: it cannot prepare "
            f"{config['name']} {shapes} inside a run's set-up limits, so the cell is not run on it"
        )
    return closed_loop.plan(params, config, seed, seconds)
