#!/usr/bin/env python
"""Standalone Mosaic lowering check — run FIRST on a live TPU.

Compiles and executes the fused Pallas kernels (mod_mul, mod_madd,
pt_add, pt_window_step, pt_ladder_mul_add, plus the MXU tier's
mxu_mod_mul fused multiply-reduce) at the smallest real shapes
on the chip, BEFORE any bench rung touches them — so a BlockSpec/layout
rejection or a pathological Mosaic compile surfaces as a five-minute
diagnosis instead of a lost bench run (the round-3 48-minute silent
hang).  Verifies each result against the host oracle.

Each kernel gets a best-effort SIGALRM budget (--per-kernel-s, default
240) so a slow compile is reported per-kernel and the queue moves on;
a hang inside a blocked device call can outlive the alarm (signals
only fire between bytecodes), so callers MUST still wrap the whole run
in an external ``timeout`` — that is the hard stop.

Run from the repo root on a machine with the chip:

    timeout 900 python scripts/mosaic_check.py

Prints one JSON line per kernel: {"kernel", "curve", "ok", "seconds"}
and a final {"mosaic_check": "pass"|"fail"} summary line; exit 1 on
any failure.  (The MSM seam these kernels feed,
reference: traits.rs:234-237).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())

os.environ.setdefault("DKG_TPU_PALLAS", "1")

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dkg_tpu.utils import compilecache  # noqa: E402

compilecache.enable()

from dkg_tpu.fields import host as fh  # noqa: E402
from dkg_tpu.groups import device as gd  # noqa: E402
from dkg_tpu.groups import host as gh  # noqa: E402
from dkg_tpu.ops import pallas_field as pf  # noqa: E402
from dkg_tpu.ops import pallas_mxu as pm  # noqa: E402
from dkg_tpu.ops import pallas_point as pp  # noqa: E402

CURVE = sys.argv[1] if len(sys.argv) > 1 else "secp256k1"
PER_KERNEL_S = int(sys.argv[2]) if len(sys.argv) > 2 else 240
B = 8  # tiny batch: smallest shapes that still tile one BLOCK row


def sync(x):
    np.asarray(x[(0,) * x.ndim] if x.ndim else x)


def step(name, fn):
    import signal

    def _alarm(signum, frame):
        raise TimeoutError(f"per-kernel budget {PER_KERNEL_S}s exceeded")

    t0 = time.time()
    old = signal.signal(signal.SIGALRM, _alarm)
    signal.alarm(PER_KERNEL_S)
    try:
        ok = bool(fn())
        err = None
    except Exception as exc:  # noqa: BLE001 — report, don't crash the queue
        ok, err = False, f"{type(exc).__name__}: {exc}"[:300]
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
    rec = {"kernel": name, "curve": CURVE, "ok": ok, "seconds": round(time.time() - t0, 1)}
    if err:
        rec["error"] = err
    print(json.dumps(rec), flush=True)
    return ok


def main() -> int:
    import random

    print(json.dumps({"devices": [str(d) for d in jax.devices()]}), flush=True)
    group = gh.ALL_GROUPS[CURVE]
    cs = gd.ALL_CURVES[CURVE]
    fs = cs.field
    rng = random.Random(0x4D4F53)
    xs = [rng.randrange(fs.modulus) for _ in range(B)]
    ys = [rng.randrange(fs.modulus) for _ in range(B)]
    xl = jnp.asarray(fh.encode(fs, xs))
    yl = jnp.asarray(fh.encode(fs, ys))

    def chk_mul():
        out = pf.mod_mul(fs, xl, yl, interpret=False)
        sync(out)
        got = [int(v) for v in fh.decode(fs, np.asarray(out))]
        return got == [x * y % fs.modulus for x, y in zip(xs, ys)]

    def chk_madd():
        out = pf.mod_madd(fs, xl, yl, yl, interpret=False)
        sync(out)
        got = [int(v) for v in fh.decode(fs, np.asarray(out))]
        return got == [(x * y + y) % fs.modulus for x, y in zip(xs, ys)]

    g = group.generator()
    pts_host = [group.scalar_mul(rng.randrange(1, 100), g) for _ in range(B)]
    qts_host = [group.scalar_mul(rng.randrange(1, 100), g) for _ in range(B)]
    p_dev = gd.from_host(cs, pts_host)
    q_dev = gd.from_host(cs, qts_host)

    def chk_add():
        out = pp.pt_add(cs, p_dev, q_dev, interpret=False)
        sync(out)
        got = [group.encode(p) for p in gd.to_host(cs, out)]
        want = [group.encode(group.add(a, b)) for a, b in zip(pts_host, qts_host)]
        return got == want

    def chk_window():
        # 4 doublings then conditional add: one Straus window step
        out = pp.pt_window_step(cs, p_dev, q_dev, 4, interpret=False)
        sync(out)
        got = [group.encode(p) for p in gd.to_host(cs, out)]
        want = []
        for a, b in zip(pts_host, qts_host):
            acc = a
            for _ in range(4):
                acc = group.add(acc, acc)
            want.append(group.encode(group.add(acc, b)))
        return got == want

    def chk_ladder():
        ks = [rng.randrange(1, 1 << 16) for _ in range(B)]
        kl = jnp.asarray(ks, jnp.uint32)
        out = pp.pt_ladder_mul_add(cs, p_dev, q_dev, kl, 16, interpret=False)
        sync(out)
        got = [group.encode(p) for p in gd.to_host(cs, out)]
        want = [
            group.encode(group.add(group.scalar_mul(k, a), b))
            for k, a, b in zip(ks, pts_host, qts_host)
        ]
        return got == want

    def chk_mxu_mul():
        # the MXU-native fused multiply-reduce (ops/pallas_mxu.py) —
        # one f32 GEMM fold + lazy carry, vs the int-level oracle
        out = pm.mxu_mod_mul(fs, xl, yl, interpret=False)
        sync(out)
        got = [int(v) for v in fh.decode(fs, np.asarray(out))]
        return got == [x * y % fs.modulus for x, y in zip(xs, ys)]

    results = [
        step("mod_mul", chk_mul),
        step("mod_madd", chk_madd),
        step("pt_add", chk_add),
        step("pt_window_step", chk_window),
        step("pt_ladder_mul_add", chk_ladder),
        step("mxu_mod_mul", chk_mxu_mul),
    ]
    ok = all(results)
    print(json.dumps({"mosaic_check": "pass" if ok else "fail"}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
