"""The sharded ceremony's collectives in a reduced trace, and the least time the chips'
interconnect could take for them: what `collective_time_share.*`,
`collective_roofline_share.*` and `shard_busy_skew.*` read; and its phases from the
program's own spans (`phase_ms`: `deal_phase_ms.*`, `digest_phase_ms.*`, `verify_phase_ms.*`).

**By name.**  `bench_trace.reduce` keeps every device operation under
`<module>/<instruction>`, all device planes added up.  A collective's instruction is named
for the JAX primitive it was traced from or for its HLO opcode (the v5e's compiler emits
both: `all_to_all.92`, `all_gather.11`, `all-gather.5`; `-start` / `-done` where XLA splits
one): `collective_seconds` adds those up under the opcode's name.  An operation of that name also holds the
wait for the slowest shard to reach it, so the seconds read high and the shares built on
them as said below.

**The work** (`collective_bytes`): the bytes ONE chip must send for one request of
(n, t) on a party mesh of `devices`, from the programs `dkg_tpu/parallel/mesh.py` traces:

* `mesh_verify_finalise` delivers the share matrix and the hiding matrix dealer-sharded ->
  recipient-sharded: two tiled `all_to_all` over the shard's (n / devices, n, L) words, in
  recipient chunks (`_verify_chunk_default`; the chunking moves no byte more), of which the
  part addressed to the other `devices - 1` shards leaves the chip;
* the same program gathers the partial rho-combined commitment columns, (t + 1, C, L) a
  shard, and the partial master point, (C, L): an `all_gather` sends a shard's part to each
  of the others.

The digest program and the two deal programs have no collective.  `tests/benchmark/
test_benchmark_sharded.py` holds this count to the collectives in the programs' own traced
jaxprs (operand shapes x the scans around them), so a PR that changes what is exchanged
fails there.

**The least time.**  Bytes over the chip's published interconnect bandwidth
(`ICI_PEAKS`, by `device_kind`; another device is an error): 1,600 Gbit/s = 200 GB/s a
v5e chip, all links together, so a chip that sent at the peak on every link at once.
A traced slice of this cell holds no request whole (a whole one is 18 M device events on
four planes: PERF.md section 6, PR 44), so `roofline_share` counts **operations, not
requests**: `collective_calls` says how often a plane executes each opcode a request
(the `all_to_all` once a tensor a recipient chunk, each `all_gather` once), the slice's
executions of an opcode over that are the requests' worth of it the slice holds, a
fraction where a request is cut, and that times the opcode's bytes a request is what was
sent inside the seconds counted.  An operation the slice cuts is counted by its seconds
and, where its start lies outside, not by its bytes, and the waits for the slowest shard
are inside the seconds: **it reads low, never high**.  Both counts are held to the traced
programs by the same test.
"""

from __future__ import annotations

import re

from bench_spans import hist_delta

# Google Cloud documentation, "TPU v5e": 1,600 Gbit/s of chip-to-chip interconnect a chip.
ICI_PEAKS = {"TPU v5 lite": 200e9}

COLLECTIVE = re.compile(
    r"/(all[-_]to[-_]all|all[-_]gather|all[-_]reduce|collective[-_]permute|reduce[-_]scatter)(?:[-_](?:start|done))?(?:\.\d+)?$"
)
VERIFY = "jit_mesh_verify_finalise"
# the programs in which no shard waits for another: a plane's execution is its own work
ALONE = ("jit_mesh_deal_commitments", "jit_mesh_deal_shares", "jit_mesh_digest_rows")
WORD = 4  # a limb rides in a uint32


def phase_ms(counters: dict, *ops: str) -> float | None:
    """Milliseconds a request spends in the mesh's phases `ops`
    (`mesh_collective_seconds{op}`, the host's clock around each phase as
    `run_sharded_ceremony` and the served route book it), each the mean over the requests
    that passed it in the window, added up.  The phases are read from the program's spans
    and not from the trace because the traced slice is shorter than a request: which
    programs it holds whole depends on where it falls, and a cut execution reads that, not
    what the program took.  None on a program without the series."""
    total, seen = 0.0, False
    for op in ops:
        seconds, served = hist_delta(counters, "mesh_collective_seconds", op=op)
        if served:
            total, seen = total + seconds / served, True
    return total * 1e3 if seen else None


def collective_seconds(trace: dict) -> dict[str, float]:
    """Device seconds inside collective operations in the slice, by opcode, all planes' added."""
    out: dict[str, float] = {}
    for op, total in trace["ops"].items():
        hit = COLLECTIVE.search(op)
        if hit:
            name = hit[1].replace("_", "-")
            out[name] = out.get(name, 0.0) + total["seconds"]
    return out


def collective_bytes(n: int, t: int, devices: int, scalar_limbs: int, point_words: int) -> dict[str, int]:
    """Bytes one chip sends to the others for one (n, t) request, by opcode.
    `point_words` is a point's coordinates x limbs (secp256k1: 3 x 16)."""
    block = n // devices
    away = devices - 1
    to_all = 2 * block * (n - block) * scalar_limbs * WORD  # shares and hidings, the part that leaves
    gather = away * ((t + 1) * point_words + point_words) * WORD  # partial columns, partial master
    return {"all-to-all": to_all, "all-gather": gather}


def collective_calls(n: int, devices: int, chunk: int) -> dict[str, int]:
    """Executions of each opcode on ONE plane for one request: `all_to_all` of the share and
    of the hiding matrix once a recipient chunk of `chunk` (the program's
    `_verify_chunk_default`; a ragged tail is one more), `all_gather` of the partial columns
    and of the partial master point once each."""
    block = n // devices
    turns = 1 if not chunk or chunk >= block else -(-block // chunk)
    return {"all-to-all": 2 * turns, "all-gather": 2}


def collective_counts(trace: dict) -> dict[str, int]:
    """Executions of collective operations in the slice, by opcode, all planes' added; where
    XLA splits one into `-start` and `-done`, the start is the execution."""
    out: dict[str, int] = {}
    for op, total in trace["ops"].items():
        hit = COLLECTIVE.search(op)
        if hit and not re.search(r"[-_]done(?:\.\d+)?$", op):
            name = hit[1].replace("_", "-")
            out[name] = out.get(name, 0) + int(total["count"])
    return out


def time_share(trace: dict | None) -> float | None:
    """Percent of the planes' busy time inside collective operations; None where the slice holds none."""
    if trace is None or not trace["busy_s"]:
        return None
    seconds = sum(collective_seconds(trace).values())
    return 100.0 * seconds / (trace["busy_s"] * max(1, trace["devices"])) if seconds else None


def roofline_share(
    trace: dict | None, config: dict, device_kind: str, scalar_limbs: int, point_words: int, chunk: int
) -> float | None:
    """Percent: the least time a chip's interconnect could take for the collective
    operations the slice holds (their count over `collective_calls` a request, times
    `collective_bytes` a request, opcode by opcode), over the seconds a plane spent inside
    collective operations in the slice.  None without a trace or where the slice holds no
    collective."""
    if trace is None:
        return None
    devices = max(1, trace["devices"])
    seconds = sum(collective_seconds(trace).values()) / devices
    if not seconds:
        return None
    if device_kind not in ICI_PEAKS:
        raise KeyError(f"no interconnect peak for device kind {device_kind!r}: add it to bench_collectives.ICI_PEAKS with its source")
    (shape,) = config["mix"]  # one committee shape
    n, t = int(shape["n"]), int(shape["t"])
    a_request = collective_bytes(n, t, devices, scalar_limbs, point_words)
    calls = collective_calls(n, devices, chunk)
    seen = collective_counts(trace)
    sent = sum(seen.get(op, 0) / devices / calls[op] * a_request[op] for op in calls)
    return 100.0 * sent / ICI_PEAKS[device_kind] / seconds


def busy_skew(trace: dict | None) -> float | None:
    """Least over greatest device seconds of the shards in the programs that hold no
    collective (`ALONE`), program by program over the executions the slice holds whole,
    the programs weighted by their seconds: 1.0 where every shard takes as long as the
    slowest.  `bench_trace.reduce` keeps a plane's busy time only in the mean over the
    planes, so this reads the work no shard can wait in; inside `mesh_verify_finalise` a
    slow shard shows as the others' seconds in the collectives."""
    if trace is None:
        return None
    least = greatest = 0.0
    for name in ALONE:
        # where the slice holds none whole, the executions at its edge: the planes are
        # cut at one instant, so their seconds still differ by what the shards differ
        runs = trace["module_runs"].get(name) or trace["module_runs_cut"].get(name)
        if runs:
            least, greatest = least + min(runs), greatest + max(runs)
    return least / greatest if greatest else None
