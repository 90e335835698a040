#!/usr/bin/env python
"""Bisect the Edwards Mosaic hang (round-4 verdict item 4).

Round 4 observed: the 4-double+add multi-op fused window body
(ops.pallas_point.pt_window_step) compiles in 77 s for Weierstrass but
Mosaic never returned for the SAME structure on Edwards (hard-killed at
~870 s), so ristretto255 — the reference's only curve
(/root/reference/src/groups.rs:11-53) — runs the least-accelerated
multi-op tier (plain XLA composition, groups/device.py window_step).

This script isolates WHERE the Edwards body stops compiling by running
progressively larger fused bodies, EACH IN ITS OWN CHILD PROCESS under
a hard subprocess timeout (a Mosaic hang is unkillable in-process:
signals fire between bytecodes, and a blocked device call never
returns).  Every candidate that compiles is verified against the host
oracle and timed.  The ladder of bodies, smallest first:

    dbl1    pt_double  n_doubles=1      (single-op — round-4 known-good)
    win1    pt_window_step n_doubles=1  (1 dbl + unified add)
    dbl2    pt_double  n_doubles=2
    win2    pt_window_step n_doubles=2
    dbl4    pt_double  n_doubles=4
    win4    pt_window_step n_doubles=4  (the round-4 hang, re-confirmed
                                         under a bounded timeout)
    ladder4  pt_ladder_mul_add nbits=4  (fori_loop body: code size ~1
    ladder14 pt_ladder_mul_add nbits=14  window step regardless of nbits)

plus `xla_rate`: the measured XLA-composed Edwards window-step rate
next to the Weierstrass one at the same batch — the "what does the
gate cost" number the verdict asked for if no fused body lands.

Writes EDWARDS_BISECT.json at the repo root; prints one JSON line per
candidate.  Run on a live chip:

    cd /root/repo && timeout 3600 python scripts/ed_bisect.py
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(_REPO))


CHILD_TMPL = r"""
import json, random, sys, time
import numpy as np
import jax, jax.numpy as jnp
from dkg_tpu.utils import compilecache
compilecache.enable()
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh
from dkg_tpu.ops import pallas_point as pp

cs = gd.RISTRETTO255
group = gh.ALL_GROUPS["ristretto255"]
rng = random.Random(0xED)
g = group.generator()
B = 8
pts = [group.scalar_mul(rng.randrange(1, 1000), g) for _ in range(B)]
qts = [group.scalar_mul(rng.randrange(1, 1000), g) for _ in range(B)]
p = gd.from_host(cs, pts)
q = gd.from_host(cs, qts)

def canon(arr):
    return [group.encode(x) for x in gd.to_host(cs, arr)]

t0 = time.time()
CASE
dt = time.time() - t0
print(json.dumps({"ok": bool(ok), "seconds": round(dt, 1)}))
"""

CASES = {
    "dbl1": """
out = pp.pt_double(cs, p, 1, interpret=False)
ref = gd._double_xla(cs, p)
ok = canon(out) == canon(ref)
""",
    "win1": """
out = pp.pt_window_step(cs, p, q, 1, interpret=False)
ref = gd._add_xla(cs, gd._double_xla(cs, p), q)
ok = canon(out) == canon(ref)
""",
    "dbl2": """
out = pp.pt_double(cs, p, 2, interpret=False)
ref = gd._double_xla(cs, gd._double_xla(cs, p))
ok = canon(out) == canon(ref)
""",
    "win2": """
out = pp.pt_window_step(cs, p, q, 2, interpret=False)
ref = gd._add_xla(cs, gd._double_xla(cs, gd._double_xla(cs, p)), q)
ok = canon(out) == canon(ref)
""",
    "dbl4": """
out = pp.pt_double(cs, p, 4, interpret=False)
ref = p
for _ in range(4):
    ref = gd._double_xla(cs, ref)
ok = canon(out) == canon(ref)
""",
    "win4": """
out = pp.pt_window_step(cs, p, q, 4, interpret=False)
ref = p
for _ in range(4):
    ref = gd._double_xla(cs, ref)
ref = gd._add_xla(cs, ref, q)
ok = canon(out) == canon(ref)
""",
    "ladder4": """
k = jnp.asarray([rng.randrange(16) for _ in range(B)], jnp.uint32)
out = pp.pt_ladder_mul_add(cs, p, q, k, 4, interpret=False)
ref = gd._add_xla(cs, gd.scalar_mul(cs, jnp.zeros((B, cs.scalar.limbs), jnp.uint32).at[:, 0].set(k), p), q)
ok = canon(out) == canon(ref)
""",
    "ladder14": """
k = jnp.asarray([rng.randrange(1 << 14) for _ in range(B)], jnp.uint32)
out = pp.pt_ladder_mul_add(cs, p, q, k, 14, interpret=False)
ref = gd._add_xla(cs, gd.scalar_mul(cs, jnp.zeros((B, cs.scalar.limbs), jnp.uint32).at[:, 0].set(k), p), q)
ok = canon(out) == canon(ref)
""",
}

# the "what does the gate cost" number: XLA-composed window-step rate,
# Edwards vs Weierstrass, same batch (1024 lanes, 64 steps)
XLA_RATE = """
import json, random, time
import numpy as np
import jax, jax.numpy as jnp
from jax import lax
from dkg_tpu.utils import compilecache
compilecache.enable()
from dkg_tpu.groups import device as gd
from dkg_tpu.groups import host as gh

def rate(curve):
    cs = gd.ALL_CURVES[curve]
    group = gh.ALL_GROUPS[curve]
    rng = random.Random(0xA7E)
    B, steps = 1024, 64
    pts = [group.scalar_mul(rng.randrange(1, 1000), group.generator()) for _ in range(8)]
    p = jnp.broadcast_to(gd.from_host(cs, pts)[:1], (B,) + (cs.ncoords, cs.field.limbs))
    @jax.jit
    def run(p0):
        def step(acc, _):
            return gd.window_step(cs, acc, p0, 4, False), None
        acc, _ = lax.scan(step, p0, None, length=steps)
        return acc
    out = run(p)
    np.asarray(out[0, 0, 0])  # sync
    t0 = time.time()
    out = run(p)
    np.asarray(out[0, 0, 0])
    dt = time.time() - t0
    return B * steps / dt

ed = rate("ristretto255")
ws = rate("secp256k1")
print(json.dumps({"ed_window_steps_per_s": round(ed, 1),
                  "ws_window_steps_per_s": round(ws, 1),
                  "ed_over_ws": round(ed / ws, 3)}))
"""


def run_child(code: str, timeout_s: float) -> dict:
    """Time-boxed case runner.  This parent never touches a JAX
    backend, so each child gets the chip to itself."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code], cwd=str(_REPO),
            capture_output=True, text=True, timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return {"ok": False, "error": f"time-box (Mosaic hang?): {timeout_s}s"}
    out = proc.stdout
    if proc.returncode != 0:
        return {"ok": False, "error": proc.stderr.strip()[-300:]}
    try:
        return json.loads(out.strip().splitlines()[-1])
    except (ValueError, IndexError):
        return {"ok": False, "error": f"bad output: {out[-200:]}"}


def main() -> int:
    per_case = float(os.environ.get("ED_BISECT_TIMEOUT", "420"))
    report = {"what": "Edwards fused-body Mosaic bisect (round-4 verdict item 4)",
              "per_case_timeout_s": per_case, "cases": {}}
    os.environ.setdefault("DKG_TPU_PALLAS", "1")
    win_hung = False
    for name, case in CASES.items():
        # a hang on a SMALLER win body makes larger win bodies pointless
        # (same structure, strictly more ops) — dbl*/ladder* shapes are
        # independent and still run
        if win_hung and name.startswith("win"):
            res = {"ok": False, "error": "skipped: smaller win body hung"}
        else:
            res = run_child(CHILD_TMPL.replace("CASE", case), per_case)
            # "time-box" is run_child's marker for an expired per-case
            # budget (the Mosaic-hang signature) — compile errors and
            # wrong results do NOT stop the ladder
            if name.startswith("win") and not res.get("ok") and "time-box" in str(res.get("error", "")):
                win_hung = True
        report["cases"][name] = res
        print(json.dumps({"case": name, **res}), flush=True)
    res = run_child(XLA_RATE, 1800.0)
    report["xla_rate"] = res
    print(json.dumps({"case": "xla_rate", **res}), flush=True)
    out = _REPO / "EDWARDS_BISECT.json"
    out.write_text(json.dumps(report, indent=1))
    print(json.dumps({"edwards_bisect": "written", "path": str(out)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
