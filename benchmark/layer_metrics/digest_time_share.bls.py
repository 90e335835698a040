"""dkg/ceremony.py transcript digest: as `digest_time_share`, in the BLS12-381 G1 cell,
whose end-to-end metric is the latency: share of the device's busy time inside the
programs that canonicalise and hash the round-1 tensors for Fiat-Shamir (the XLA
modules `jit_affine_canon`, whose inversion chain runs over 381 bits of 24 limbs
here, and `jit__tree_from_words_jit`), in percent."""

from bench_trace import modules_s

MODULES = ("jit_affine_canon", "jit__tree_from_words_jit")


def read(ctx: dict) -> float | None:
    trace = ctx["trace"]
    if trace is None or not trace["busy_s"]:
        return None
    return 100.0 * modules_s(trace, MODULES) / (trace["busy_s"] * max(1, trace["devices"]))
