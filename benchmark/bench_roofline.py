"""The point kernels by name in a reduced trace, and the least time the chip could take
for their work: what `ed_multi_kernel_time_share.*` and `pt_kernels_roofline_share.*` read.

**By name.**  `bench_trace.reduce` keeps every device operation under
`<module>/<instruction>`, a Mosaic kernel as `jit_verify_batch/pt_add.120[tpu_custom_call]`:
the instruction carries the `name=` its `pallas_call` was given (`ops/pallas_point.py`:
`pt_add`, `pt_madd`, `pt_double`, `pt_window_step`, `pt_ladder_mul_add`).
`kernel_seconds` adds the seconds up by that name, over every device.

**The work** (`point_kernel_blocks`): the 128-lane blocks each kernel is launched on for
one width-1 request of (n, t, rho_bits), program by program, from the schedule
`dkg/ceremony.py` and `groups/device.py` trace with the fused kernels on (Straus, 4-bit
windows of rho, 16-bit fixed-base windows, one lane block = 128 lanes):

* `jit_deal`: two fixed-base multiplies over the n (t+1) commitment lanes, a `pt_madd`
  a window of the scalar; one `pt_add` joins them;
* `jit_verify_batch`: the point-RLC's table 2P..15P (14 `pt_add` over the commitment
  lanes), then a tree over the dealers (`groups.device._tree_tiles`: each level adds the
  upper half of the dealers onto the lower) and one `pt_window_step` on the t+1
  accumulator lanes for every 4 bits of rho; the Horner ladder, one `pt_ladder_mul_add`
  a coefficient over the n recipients, bits(n) wide; two fixed-base multiplies over the
  n recipients and the `pt_add` that joins them;
* `jit_master_key_from_bare`: a pairwise tree of `pt_add` over the n dealers.

`tests/benchmark/test_benchmark_ristretto.py` holds this count, kernel by kernel, to the
launches in the program's own traced jaxpr (every `pallas_call`'s grid times the lengths
of the scans around it) and to its trace-time counters (`point_rlc_lanes_traced_total`,
`fixed_base_traced_total`), so a PR that changes the schedule fails there.  Only the
Edwards formulas are counted: the one cell that reads this is ristretto255's.

**The least time.**  A field multiplication is L^2 limb multiply-adds for the product and
L^2 for the reduction, L = 16: 4 L^2 operations.  A kernel lane costs its formula's
field multiplications (unified add 9, mixed add 8, doubling 8; additions and selects are
not counted), a block 128 lanes; it reads and writes its operand and result rows of 128
words.  The least time of a launch is the larger of operations over the peak and bytes over
the bandwidth, from `PEAKS`.  **On the v5e the bytes bound is the larger for every point
kernel** (`pt_add`: 0.120 microseconds for 98,304 bytes against 0.006 for 1.18 M
operations; `pt_ladder_mul_add` at 9 bits 0.126 against 0.108): a point kernel that ran at
its roofline would be waiting on HBM.  `roofline_share` counts the work of the module
executions the slice holds **whole** and divides by the seconds the trace books to `pt_*`
kernels over the **whole** slice, cut executions included: it reads low, never high.
"""

from __future__ import annotations

import re

BLOCK = 128  # lanes a kernel block (ops/pallas_field.BLOCK)
LIMBS = 16  # 16-bit limbs of 2^255 - 19
ROWS = 4 * LIMBS  # an extended Edwards point: 64 rows of 128 words a block
FIXED_WINDOWS = 16  # 16-bit windows of a 256-bit scalar (the on-chip fixed-base table)
RLC_WINDOW = 4  # bits of rho a Straus window step takes
TABLE_ADDS = 14  # 2P..15P

# Peaks by `device_kind`: (operations a second, bytes a second).  Google Cloud
# documentation, "TPU v5e": 197 TFLOP/s in bf16, 819 GB/s of HBM bandwidth a chip.
PEAKS = {"TPU v5 lite": (197e12, 819e9)}

MUL_OPS = 4 * LIMBS * LIMBS  # product and reduction, a multiply-add two operations
EDWARDS_MULS = {"pt_add": 9, "pt_madd": 8, "pt_double": 8}
KERNEL = re.compile(r"/(pt_[a-z_]+?)(?:\.\d+)?\[tpu_custom_call\]$")
MULTI_OP = ("pt_window_step", "pt_ladder_mul_add")
PROGRAMS = ("jit_deal", "jit_verify_batch", "jit_master_key_from_bare")


def kernel_seconds(trace: dict) -> dict[str, float]:
    """Device seconds of each point kernel in the slice, by the kernel's name, all devices' added."""
    out: dict[str, float] = {}
    for op, total in trace["ops"].items():
        hit = KERNEL.search(op)
        if hit:
            out[hit[1]] = out.get(hit[1], 0.0) + total["seconds"]
    return out


def _blocks(lanes: int) -> int:
    return -(-lanes // BLOCK)


def _tree_blocks(m: int, cols: int) -> int:
    """`_tree_tiles` over m dealers of `cols` lanes each: a `pt_add` a level."""
    total = 0
    while m > 1:
        m = (m + 1) // 2
        total += _blocks(m * cols)
    return total


def point_kernel_blocks(n: int, t: int, rho_bits: int) -> dict[str, dict[str, int]]:
    """Blocks each point kernel is launched on for one width-1 (n, t) request, by program."""
    commitments, recipients = _blocks(n * (t + 1)), _blocks(n)
    steps = -(-rho_bits // RLC_WINDOW)
    pairwise, m = 0, n
    while m > 1:  # `_tree_reduce`: neighbours, an odd count padded with the identity
        m = (m + 1) // 2
        pairwise += _blocks(m)
    return {
        "jit_deal": {"pt_madd": 2 * FIXED_WINDOWS * commitments, "pt_add": commitments},
        "jit_verify_batch": {
            "pt_add": TABLE_ADDS * commitments + steps * _tree_blocks(n, t + 1) + recipients,
            "pt_window_step": steps * _blocks(t + 1),
            "pt_ladder_mul_add": (t + 1) * recipients,
            "pt_madd": 2 * FIXED_WINDOWS * recipients,
        },
        "jit_master_key_from_bare": {"pt_add": pairwise},
    }


def block_cost(kernel: str, index_bits: int) -> tuple[int, int]:
    """(operations, bytes) of one launch of `kernel` on one block of Edwards points."""
    if kernel == "pt_window_step":
        muls, rows = RLC_WINDOW * EDWARDS_MULS["pt_double"] + EDWARDS_MULS["pt_add"], 3 * ROWS
    elif kernel == "pt_ladder_mul_add":
        muls = index_bits * (EDWARDS_MULS["pt_double"] + EDWARDS_MULS["pt_add"]) + EDWARDS_MULS["pt_add"]
        rows = 3 * ROWS + index_bits  # the multiplier's bits ride in as rows
    else:
        muls, rows = EDWARDS_MULS[kernel], 3 * ROWS
    return muls * MUL_OPS * BLOCK, rows * BLOCK * 4


def least_seconds(device_kind: str, n: int, t: int, rho_bits: int, runs: dict[str, int]) -> float:
    """The least time `device_kind` could take for the point kernels of `runs[program]`
    executions of each program of an (n, t) request.  An unknown device is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}: add it to bench_roofline.PEAKS with its source")
    ops_peak, bytes_peak = PEAKS[device_kind]
    total = 0.0
    for program, kernels in point_kernel_blocks(n, t, rho_bits).items():
        for kernel, blocks in kernels.items():
            ops, nbytes = block_cost(kernel, int(n).bit_length())
            total += runs.get(program, 0) * blocks * max(ops / ops_peak, nbytes / bytes_peak)
    return total


def roofline_share(trace: dict | None, config: dict, device_kind: str) -> float | None:
    """Percent: the least time for the point kernels of the program executions the slice
    holds whole, over the seconds of `pt_*` kernels in the whole slice.  None without a
    trace, and on a program whose slice holds no `pt_window_step`: the schedule counted
    here is the fused tier's, and a composed tier launches other kernels."""
    if trace is None:
        return None
    by_kernel = kernel_seconds(trace)
    if not by_kernel.get("pt_window_step"):
        return None
    seconds = sum(by_kernel.values())
    (shape,) = config["mix"]  # one committee shape: the width-1 programs of one bucket
    runs = {p: len(trace["module_runs"].get(p, ())) for p in PROGRAMS}
    least = least_seconds(device_kind, int(shape["n"]), int(shape["t"]), int(config["rho_bits"]), runs)
    return 100.0 * least / seconds
