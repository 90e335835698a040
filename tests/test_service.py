"""Multi-tenant ceremony service (dkg_tpu.service).

Three layers, cheapest first:

* pure-policy tests — bucketing ladder, convoy splitting, request ids,
  journal replay/compaction, scheduler admission/deadline/backpressure
  semantics with the ENGINE MONKEYPATCHED OUT (no JAX work at all, so
  the scheduler's concurrency story is exercised hundreds of times per
  second);
* real-engine tests at the smallest bucket (ristretto255 (5,2) ->
  bucket (8,2), width-1 convoys so the plain executables are shared
  with the rest of the suite's in-process jit cache) — the
  padded-vs-unpadded oracle, scheduler end-to-end masters vs fresh
  references, and WAL-backed crash recovery;
* ``slow``-marked legs — the stacked (vmapped) convoy lane's bit-
  exactness, the convoy-batched Fiat-Shamir fold, and the secp256k1
  wire-byte oracle (padded KEM/DEM bytes == unpadded pipeline bytes).
"""

from __future__ import annotations

import json
import random
import threading
import time

import numpy as np
import pytest

from dkg_tpu.service import buckets, engine
from dkg_tpu.service import scheduler as scheduler_mod
from dkg_tpu.service.durable import ServiceJournal
from dkg_tpu.service.engine import CeremonyOutcome, CeremonyRequest
from dkg_tpu.service.faultsvc import ServiceFaultPlan
from dkg_tpu.service.scheduler import CeremonyScheduler, QueueFullError
from dkg_tpu.utils.metrics import REGISTRY, MetricsRegistry

CURVE = "ristretto255"
N, T = 5, 2  # buckets to (8, 2): the smallest ladder rung


# ---------------------------------------------------------------------------
# bucketing policy (pure python)
# ---------------------------------------------------------------------------


def test_bucket_for_rounds_up_to_ladder():
    assert buckets.bucket_for(5, 2) == buckets.Bucket(8, 2)
    assert buckets.bucket_for(8, 2) == buckets.Bucket(8, 2)
    assert buckets.bucket_for(5, 3) == buckets.Bucket(8, 3)
    assert buckets.bucket_for(16, 5) == buckets.Bucket(16, 5)
    assert buckets.bucket_for(9, 3) == buckets.Bucket(16, 4)
    assert buckets.bucket_for(24, 8) == buckets.Bucket(32, 8)
    assert buckets.bucket_for(64, 16) == buckets.Bucket(64, 16)
    # committee sizes below the floor pad up to it
    assert buckets.bucket_for(2, 1) == buckets.Bucket(8, 2)


def test_bucket_for_escalates_degenerate_thresholds():
    # t beyond n_pad's maximal rung escalates to the next n bucket
    b = buckets.bucket_for(8, 4)  # rungs at n=8 are (2, 3)
    assert b.n == 16 and b.t >= 4


def test_bucket_for_rejects_unbucketable_shapes():
    with pytest.raises(ValueError):
        buckets.bucket_for(1, 1)
    with pytest.raises(ValueError):
        buckets.bucket_for(buckets.MAX_BUCKET_N + 1, 2)
    with pytest.raises(ValueError):
        buckets.bucket_for(5, 5)  # t >= n
    with pytest.raises(ValueError):
        buckets.bucket_for(5, 0)


def test_t_rungs_ascend_and_dominate_regimes():
    for n_pad in (8, 16, 32, 64, 4096):
        rungs = buckets.t_rungs(n_pad)
        assert rungs == tuple(sorted(rungs))
        assert rungs[-1] == (n_pad - 1) // 2  # maximal honest-majority


def test_split_widths_greedy_ladder():
    assert buckets.split_widths(7) == [4, 2, 1]
    assert buckets.split_widths(8) == [8]
    assert buckets.split_widths(9) == [8, 1]
    assert buckets.split_widths(0) == []
    assert buckets.split_widths(7, batch_max=2) == [2, 2, 2, 1]
    with pytest.raises(ValueError):
        buckets.split_widths(-1)
    # every decomposition sums back and uses only ladder widths
    for k in range(0, 40):
        ws = buckets.split_widths(k)
        assert sum(ws) == k
        assert all(w in buckets.WIDTHS for w in ws)


def test_width_cap_stops_stacking_past_the_crossover():
    # below the crossover the full ladder is available; at/above it the
    # bucket runs width-1 (stacking is a measured loss there)
    assert buckets.width_cap(buckets.Bucket(8, 2)) == buckets.WIDTHS[0]
    assert buckets.width_cap(buckets.Bucket(16, 5)) == buckets.WIDTHS[0]
    assert buckets.width_cap(buckets.Bucket(32, 8)) == buckets.WIDTHS[0]
    assert buckets.width_cap(buckets.Bucket(64, 16)) == 1
    assert buckets.width_cap(buckets.Bucket(4096, 1365)) == 1


def test_padded_config_requires_domination():
    from dkg_tpu.dkg import ceremony as ce

    cfg = ce.CeremonyConfig(CURVE, 5, 2)
    assert cfg.padded(8, 2).n == 8
    with pytest.raises(ValueError):
        cfg.padded(4, 2)
    with pytest.raises(ValueError):
        cfg.padded(8, 1)


def test_request_id_binds_identity_and_sequence():
    req = CeremonyRequest(CURVE, N, T, seed=1)
    assert engine.request_id(req, 0) == engine.request_id(req, 0)
    assert engine.request_id(req, 0) != engine.request_id(req, 1)
    other = CeremonyRequest(CURVE, N, T, seed=2)
    assert engine.request_id(req, 0) != engine.request_id(other, 0)


def test_convoy_key_separates_incompatible_requests():
    a = CeremonyRequest(CURVE, 5, 2, seed=1)
    b = CeremonyRequest(CURVE, 8, 2, seed=2)  # same bucket, same key
    assert a.convoy_key() == b.convoy_key()
    assert a.convoy_key() != CeremonyRequest(CURVE, 5, 2, rho_bits=64).convoy_key()
    assert (
        a.convoy_key()
        != CeremonyRequest(CURVE, 5, 2, shared_string=b"other").convoy_key()
    )


def test_start_convoy_rejects_mixed_keys():
    with pytest.raises(ValueError):
        engine.start_convoy(
            engine.WarmRuntime(),
            [
                CeremonyRequest(CURVE, N, T, seed=1),
                CeremonyRequest(CURVE, N, T, seed=2, rho_bits=64),
            ],
        )


# ---------------------------------------------------------------------------
# durability journal (pure python over PartyWal)
# ---------------------------------------------------------------------------


def test_journal_replay_partitions_pending_and_terminal(tmp_path):
    j = ServiceJournal(tmp_path)
    r1 = CeremonyRequest(CURVE, 5, 2, seed=11, durable=True, tag="one")
    r2 = CeremonyRequest(CURVE, 6, 2, seed=12, durable=True, deadline_s=9.0)
    j.record_request("cid1", 0, r1)
    j.record_request("cid2", 1, r2)
    j.record_done(
        CeremonyOutcome(
            ceremony_id="cid1", status="done", curve=CURVE, n=5, t=2,
            bucket_n=8, bucket_t=2, master=b"\x01\x02",
            qualified=(True,) * 5, complaints=((2, 1),),
        )
    )
    pending, terminal, replays = j.replay()
    assert set(pending) == {"cid2"} and replays == {}
    seq, req = pending["cid2"]
    assert seq == 1
    assert (req.curve, req.n, req.t, req.seed) == (CURVE, 6, 2, 12)
    assert req.durable and req.deadline_s == 9.0
    assert set(terminal) == {"cid1"}
    out = terminal["cid1"]
    assert out.status == "done" and out.master == b"\x01\x02"
    assert out.qualified == (True,) * 5 and out.complaints == ((2, 1),)


def test_journal_skips_unparseable_bodies_and_compacts(tmp_path):
    j = ServiceJournal(tmp_path)
    j.record_request("cid1", 0, CeremonyRequest(CURVE, 5, 2, seed=1, durable=True))
    j.wal.append(b"not json {")  # version skew, not corruption
    j.wal.append(json.dumps({"no": "kind"}).encode())
    pending, terminal, replays = j.replay()
    assert set(pending) == {"cid1"} and not terminal
    j.compact(pending, terminal, replays)
    # compacted journal replays to the identical state, junk dropped
    pending2, terminal2, _ = ServiceJournal(tmp_path).replay()
    assert set(pending2) == {"cid1"} and not terminal2
    assert pending2["cid1"][1] == pending["cid1"][1]


# ---------------------------------------------------------------------------
# scheduler semantics with the engine monkeypatched out (no JAX work)
# ---------------------------------------------------------------------------


class _FakeEngine:
    """Stand-in for start_convoy/finish_convoy: records convoy widths,
    optionally gates the start call on an event so tests can hold a
    worker mid-pipeline while they poke the queue."""

    def __init__(self, gate: threading.Event | None = None):
        self.gate = gate
        self.widths: list[int] = []
        self.starts = 0

    def start(self, runtime, reqs, ids=None):
        self.starts += 1
        self.widths.append(len(reqs))
        if self.gate is not None:
            assert self.gate.wait(timeout=10)
        return {"reqs": list(reqs), "ids": list(ids)}

    def finish(self, runtime, fl):
        return [
            CeremonyOutcome(
                ceremony_id=cid, status="done", curve=r.curve, n=r.n, t=r.t,
                bucket_n=r.bucket().n, bucket_t=r.bucket().t,
                master=b"M:" + cid.encode(),
                qualified=(True,) * r.n,
            )
            for cid, r in zip(fl["ids"], fl["reqs"])
        ]


@pytest.fixture()
def fake_engine(monkeypatch):
    fake = _FakeEngine(gate=threading.Event())
    monkeypatch.setattr(scheduler_mod, "start_convoy", fake.start)
    monkeypatch.setattr(scheduler_mod, "finish_convoy", fake.finish)
    yield fake
    fake.gate.set()  # never leave a worker parked on the gate


def _wait_status(sch, cid, status, timeout=5.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if sch.poll(cid) == status:
            return
        time.sleep(0.005)
    raise AssertionError(f"{cid} never reached {status} (at {sch.poll(cid)})")


def test_submit_validates_before_queueing(fake_engine):
    sch = CeremonyScheduler(concurrency=1, queue_depth=4, batch_max=1, runtime=object())
    try:
        with pytest.raises(ValueError):
            sch.submit(CeremonyRequest(CURVE, 1, 1))  # unbucketable
        with pytest.raises(ValueError):
            sch.submit(CeremonyRequest(CURVE, 5, 2, durable=True))  # no seed
        with pytest.raises(ValueError):  # seeded but scheduler has no WAL
            sch.submit(CeremonyRequest(CURVE, 5, 2, seed=1, durable=True))
        assert sch.poll("nonexistent") == "unknown"
        with pytest.raises(KeyError):
            sch.result("nonexistent")
    finally:
        fake_engine.gate.set()
        sch.close()


def test_backpressure_rejects_when_queue_full(fake_engine):
    reg = MetricsRegistry()
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=2, batch_max=1, runtime=object(), metrics=reg
    )
    try:
        held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
        _wait_status(sch, held, "running")  # worker parked on the gate
        q1 = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=1))
        sch.submit(CeremonyRequest(CURVE, 5, 2, seed=2))
        with pytest.raises(QueueFullError):
            sch.submit(CeremonyRequest(CURVE, 5, 2, seed=3))
        assert sch.poll(q1) == "queued"
        with pytest.raises(TimeoutError):
            sch.result(q1, timeout=0.01)
        snap = reg.snapshot()["counters"]
        assert snap["service_rejected_total"] == 1
        assert snap["service_submitted_total"] == 3
    finally:
        fake_engine.gate.set()
        sch.close()
    assert sch.result(held).master == b"M:" + held.encode()
    assert sch.result(q1).status == "done"


def test_deadline_expires_queued_ceremonies(fake_engine):
    sch = CeremonyScheduler(concurrency=1, queue_depth=8, batch_max=1, runtime=object())
    try:
        held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
        _wait_status(sch, held, "running")
        doomed = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=1, deadline_s=0.05))
        time.sleep(0.15)  # expires while the worker is parked
    finally:
        fake_engine.gate.set()
    out = sch.result(doomed, timeout=5)
    assert out.status == "expired"
    assert out.error == "DEADLINE_EXCEEDED"
    assert out.master == b""
    sch.close()


def test_convoys_batch_same_key_in_ladder_widths(fake_engine):
    sch = CeremonyScheduler(concurrency=1, queue_depth=16, batch_max=8, runtime=object())
    try:
        held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0, rho_bits=32))
        _wait_status(sch, held, "running")
        # three same-key requests with a different-key one interleaved:
        # the stranger must never ride in their convoy
        ids_a = [
            sch.submit(CeremonyRequest(CURVE, 5, 2, seed=1 + i)) for i in range(2)
        ]
        id_b = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=9, rho_bits=64))
        ids_a.append(sch.submit(CeremonyRequest(CURVE, 5, 2, seed=3)))
    finally:
        fake_engine.gate.set()
    outs = [sch.result(i, timeout=10) for i in ids_a + [id_b, held]]
    assert all(o.status == "done" for o in outs)
    sch.close()
    # ladder truncation: 3 same-key mates pop as width 2 (next rung
    # under 3), then the different-key head as 1, then the leftover
    assert fake_engine.widths == [1, 2, 1, 1]


@pytest.mark.parametrize(
    "shapes,bucket,width,real",
    [
        ([(24, 8), (32, 8)], "32x8", 2, 56),  # two real sizes in one stack
        ([(48, 16)], "64x16", 1, 48),  # WIDTH_CAP_N: a convoy of its own
        ([(16, 5)] * 4, "16x5", 4, 64),  # exact: no phantom lane
    ],
    ids=["padded_stack", "padded_heavy", "exact_stack"],
)
def test_convoy_seconds_and_lanes_by_bucket(fake_engine, shapes, bucket, width, real):
    reg = MetricsRegistry()
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=16, batch_max=8, runtime=object(), metrics=reg
    )
    try:
        held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
        _wait_status(sch, held, "running")  # the rest queue up behind it
        cids = [
            sch.submit(CeremonyRequest(CURVE, n, t, seed=1 + i))
            for i, (n, t) in enumerate(shapes)
        ]
    finally:
        fake_engine.gate.set()
    assert all(sch.result(c, timeout=10).status == "done" for c in cids + [held])
    sch.close()
    snap = reg.snapshot()
    # one observation a convoy, under its bucket and its width
    convoys = {
        k: v["count"]
        for k, v in snap["histograms"].items()
        if k.startswith("service_convoy_seconds")
    }
    assert convoys == {
        'service_convoy_seconds{bucket="8x2",width="1"}': 1,
        f'service_convoy_seconds{{bucket="{bucket}",width="{width}"}}': 1,
    }
    assert snap["counters"]["service_convoys_total"] == 2  # unlabelled, as before
    lanes = {
        kind: snap["counters"][
            f'service_convoy_lanes_total{{bucket="{bucket}",kind="{kind}"}}'
        ]
        for kind in ("real", "phantom")
    }
    bucket_n = int(bucket.partition("x")[0])
    assert lanes["real"] == real
    assert lanes["real"] + lanes["phantom"] == width * bucket_n
    held_lanes = 'service_convoy_lanes_total{bucket="8x2",kind="%s"}'
    assert snap["counters"][held_lanes % "real"] == 5
    assert snap["counters"][held_lanes % "phantom"] == 3


def test_close_without_drain_fails_queued_work(fake_engine):
    sch = CeremonyScheduler(concurrency=1, queue_depth=8, batch_max=1, runtime=object())
    held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    _wait_status(sch, held, "running")
    dropped = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=1))
    fake_engine.gate.set()
    sch.close(drain=False)
    out = sch.result(dropped, timeout=5)
    assert out.status == "failed" and out.error == "SHUTDOWN"
    with pytest.raises(QueueFullError):
        sch.submit(CeremonyRequest(CURVE, 5, 2, seed=2))


def test_recovery_resubmits_pending_and_reserves_terminal(tmp_path, fake_engine):
    reg = MetricsRegistry()
    j = ServiceJournal(tmp_path)
    j.record_request("cidA", 0, CeremonyRequest(CURVE, 5, 2, seed=21, durable=True))
    j.record_request("cidB", 1, CeremonyRequest(CURVE, 5, 2, seed=22, durable=True))
    j.record_done(
        CeremonyOutcome(
            ceremony_id="cidT", status="done", curve=CURVE, n=5, t=2,
            bucket_n=8, bucket_t=2, master=b"\xaa\xbb",
        )
    )
    fake_engine.gate.set()  # recovery runs straight through
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=8,
        wal_dir=str(tmp_path), runtime=object(), metrics=reg,
    )
    # terminal outcome re-served from the journal, never re-run
    assert sch.poll("cidT") == "done"
    assert sch.result("cidT").master == b"\xaa\xbb"
    # pending ceremonies resubmitted under their ORIGINAL ids and run
    for cid in ("cidA", "cidB"):
        out = sch.result(cid, timeout=10)
        assert out.status == "done" and out.master == b"M:" + cid.encode()
    assert reg.snapshot()["counters"]["service_recovered_total"] == 2
    sch.close()
    starts_after_first = fake_engine.starts
    assert starts_after_first >= 1

    # second restart: everything is terminal now — nothing re-runs
    sch2 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=8,
        wal_dir=str(tmp_path), runtime=object(),
    )
    for cid, master in (("cidA", b"M:cidA"), ("cidB", b"M:cidB"), ("cidT", b"\xaa\xbb")):
        assert sch2.poll(cid) == sch2.result(cid).status == "done"
        assert sch2.result(cid).master == master
    sch2.close()
    assert fake_engine.starts == starts_after_first


def test_scheduler_reads_envknobs(monkeypatch, fake_engine):
    monkeypatch.delenv("DKG_TPU_SERVICE_WAL_DIR", raising=False)
    monkeypatch.setenv("DKG_TPU_SERVICE_CONCURRENCY", "2")
    monkeypatch.setenv("DKG_TPU_SERVICE_QUEUE_DEPTH", "5")
    monkeypatch.setenv("DKG_TPU_SERVICE_BATCH_MAX", "4")
    monkeypatch.setenv("DKG_TPU_SERVICE_DEADLINE_S", "30.5")
    sch = CeremonyScheduler(runtime=object())
    try:
        assert sch.concurrency == 2
        assert sch.queue_depth == 5
        assert sch.batch_max == 4
        assert sch.default_deadline_s == 30.5
        assert len(sch._workers) == 2
    finally:
        fake_engine.gate.set()
        sch.close()
    monkeypatch.setenv("DKG_TPU_SERVICE_QUEUE_DEPTH", "zero")
    with pytest.raises(ValueError):
        CeremonyScheduler(runtime=object())


def test_scheduler_reads_resilience_envknobs(monkeypatch, fake_engine):
    monkeypatch.delenv("DKG_TPU_SERVICE_WAL_DIR", raising=False)
    monkeypatch.setenv("DKG_TPU_SERVICE_RETRIES", "0")
    monkeypatch.setenv("DKG_TPU_SERVICE_RETRY_BACKOFF_S", "0.25")
    monkeypatch.setenv("DKG_TPU_SERVICE_MAX_REPLAYS", "7")
    sch = CeremonyScheduler(concurrency=1, runtime=object())
    try:
        assert sch.retries == 0, "0 disables transient retries"
        assert sch.retry_backoff_s == 0.25
        assert sch.max_replays == 7
    finally:
        fake_engine.gate.set()
        sch.close()
    for name, bad in (
        ("DKG_TPU_SERVICE_RETRIES", "-1"),
        ("DKG_TPU_SERVICE_RETRY_BACKOFF_S", "fast"),
        ("DKG_TPU_SERVICE_MAX_REPLAYS", "0"),
    ):
        monkeypatch.setenv(name, bad)
        with pytest.raises(ValueError, match=name):
            CeremonyScheduler(concurrency=1, runtime=object())
        monkeypatch.delenv(name)


# ---------------------------------------------------------------------------
# blast-radius isolation, watchdog, crash-loop guard (engine monkeypatched)
# ---------------------------------------------------------------------------


def test_poison_bisection_isolates_one_request_at_width_4(fake_engine):
    """A width-4 convoy with one poisoned member: the three healthy
    requests complete exactly as a fault-free run would, and only the
    culprit — found by bisecting down the width ladder — ends poisoned."""
    reg = MetricsRegistry()
    plan = ServiceFaultPlan(seed=1).poison("bad")
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=16, batch_max=8, runtime=object(),
        metrics=reg, fault_plan=plan,
    )
    try:
        held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0, rho_bits=32))
        _wait_status(sch, held, "running")  # park so a width-4 convoy forms
        ids = [
            sch.submit(
                CeremonyRequest(
                    CURVE, 5, 2, seed=10 + i,
                    tag="bad" if i == 2 else f"ok{i}",
                )
            )
            for i in range(4)
        ]
    finally:
        fake_engine.gate.set()
    outs = [sch.result(i, timeout=10) for i in ids]
    sch.close()
    for i, out in enumerate(outs):
        if i == 2:
            assert out.status == "poisoned"
            assert out.error.startswith("PoisonedRequest: PoisonFault")
        else:
            assert out.status == "done"
            assert out.master == b"M:" + ids[i].encode()
    snap = reg.snapshot()["counters"]
    assert snap["service_poisoned_total"] == 1
    # width 4 -> halves (2, 2) -> the bad half -> (1, 1): two bisections
    assert snap["service_convoy_bisections_total"] == 2
    # the poison refired at widths 4, 2, and 1 — deterministic chaos
    assert plan.injected["poison"] == 3


def test_transient_fault_retries_and_recovers(fake_engine):
    reg = MetricsRegistry()
    plan = ServiceFaultPlan().transient(times=1)
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1, runtime=object(),
        metrics=reg, fault_plan=plan, retries=2, retry_backoff_s=0.0,
    )
    fake_engine.gate.set()
    cid = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    out = sch.result(cid, timeout=10)
    sch.close()
    assert out.status == "done" and out.master == b"M:" + cid.encode()
    snap = reg.snapshot()["counters"]
    assert snap["service_retries_total"] == 1
    assert "service_poisoned_total" not in snap
    assert "service_convoy_bisections_total" not in snap


def test_transient_retries_exhausted_fail_typed(fake_engine):
    reg = MetricsRegistry()
    plan = ServiceFaultPlan().transient(times=10)
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1, runtime=object(),
        metrics=reg, fault_plan=plan, retries=1, retry_backoff_s=0.0,
    )
    fake_engine.gate.set()
    cid = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    out = sch.result(cid, timeout=10)
    sch.close()
    assert out.status == "failed"
    assert out.error.startswith("TransientEngineError")
    snap = reg.snapshot()["counters"]
    assert snap["service_retries_total"] == 1
    assert snap['service_failed_total{kind="TransientEngineError"}'] == 1


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_watchdog_respawns_crashed_worker_and_requeues(fake_engine):
    """A WorkerCrash (BaseException) kills the worker THREAD; the
    watchdog respawns it and re-queues the orphaned convoy, which then
    completes normally."""
    reg = MetricsRegistry()
    plan = ServiceFaultPlan().crash_worker(at_start=1)
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1, runtime=object(),
        metrics=reg, fault_plan=plan, watchdog_interval_s=0.05,
    )
    fake_engine.gate.set()
    cid = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    out = sch.result(cid, timeout=10)
    sch.close()
    assert out.status == "done" and out.master == b"M:" + cid.encode()
    snap = reg.snapshot()["counters"]
    assert snap["service_worker_restarts_total"] >= 1
    assert snap["service_requeued_total"] == 1


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning"
)
def test_repeated_worker_crashes_fail_the_request_typed(fake_engine):
    """A request whose convoy kills its worker TWICE is treated as the
    probable culprit: failed with WORKER_CRASH instead of crash-looping
    the pool forever."""
    reg = MetricsRegistry()
    plan = ServiceFaultPlan().crash_worker(at_start=1).crash_worker(at_start=2)
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1, runtime=object(),
        metrics=reg, fault_plan=plan, watchdog_interval_s=0.05,
    )
    fake_engine.gate.set()
    cid = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    out = sch.result(cid, timeout=10)
    sch.close()
    assert out.status == "failed"
    assert "WORKER_CRASH" in out.error
    snap = reg.snapshot()["counters"]
    assert snap["service_worker_restarts_total"] >= 2
    assert snap['service_failed_total{kind="WORKER_CRASH"}'] == 1


def test_crash_loop_guard_counts_replays_and_poisons(tmp_path, fake_engine):
    reg = MetricsRegistry()
    j = ServiceJournal(tmp_path)
    j.record_request(
        "cidR", 0, CeremonyRequest(CURVE, 5, 2, seed=31, durable=True)
    )
    fake_engine.gate.set()
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        wal_dir=str(tmp_path), runtime=object(), metrics=reg,
    )
    assert sch.result("cidR", timeout=10).status == "done"
    sch.close()
    # the recovery stamped replay #1 into the WAL before re-queueing:
    # the crash-loop guard's memory of this attempt survives compaction
    _, terminal, replays = ServiceJournal(tmp_path).replay()
    assert "cidR" in terminal and replays == {"cidR": 1}

    # a request that already burned max_replays recoveries is the likely
    # CAUSE of those crashes: the next recovery poisons it instead of
    # queueing it for another round of taking the process down
    j2 = ServiceJournal(tmp_path)
    j2.record_request(
        "cidP", 1, CeremonyRequest(CURVE, 5, 2, seed=32, durable=True)
    )
    for count in (1, 2, 3):
        j2.record_replay("cidP", count)
    reg2 = MetricsRegistry()
    sch2 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        wal_dir=str(tmp_path), runtime=object(), metrics=reg2,
        max_replays=3,
    )
    assert sch2.poll("cidP") == "poisoned"
    out = sch2.result("cidP")
    assert out.error.startswith("PoisonedRequest") and "REPLAY_LIMIT" in out.error
    assert reg2.snapshot()["counters"]["service_poisoned_total"] == 1
    sch2.close()

    # the poisoned verdict is itself journalled: the NEXT recovery
    # re-serves it terminally without another replay round
    sch3 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        wal_dir=str(tmp_path), runtime=object(), max_replays=3,
    )
    assert sch3.poll("cidP") == "poisoned"
    sch3.close()


def test_failure_paths_emit_kind_only_never_payloads(
    tmp_path, fake_engine, monkeypatch
):
    """The obslog redaction contract for the service failure paths:
    reject/expire/poison events carry the error KIND and ceremony id,
    never the exception message (which may embed share or seed
    material).  The caller-facing outcome keeps the full error."""
    from dkg_tpu.utils.obslog import ObsLog

    canary = "5ecret-c4nary-d34db33f"
    log = ObsLog(path=tmp_path / "svc.jsonl")
    reg = MetricsRegistry()

    # leg 1 (fake engine): backpressure reject + queued-deadline expiry
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=1, batch_max=1, runtime=object(),
        metrics=reg, log=log,
    )
    held = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
    _wait_status(sch, held, "running")
    doomed = sch.submit(
        CeremonyRequest(CURVE, 5, 2, seed=1, deadline_s=0.01)
    )
    with pytest.raises(QueueFullError):
        sch.submit(CeremonyRequest(CURVE, 5, 2, seed=2))
    time.sleep(0.05)
    fake_engine.gate.set()
    assert sch.result(doomed, timeout=10).status == "expired"
    sch.close()

    # leg 2: an engine exploding with secret-bearing text -> poisoned
    def _bomb(runtime, reqs, ids=None):
        raise RuntimeError(f"engine exploded holding {canary}")

    monkeypatch.setattr(scheduler_mod, "start_convoy", _bomb)
    sch2 = CeremonyScheduler(
        concurrency=1, queue_depth=4, batch_max=1, runtime=object(),
        metrics=reg, log=log,
    )
    cid = sch2.submit(CeremonyRequest(CURVE, 5, 2, seed=3))
    out = sch2.result(cid, timeout=10)
    sch2.close()
    assert out.status == "poisoned"
    assert canary in out.error, "the CALLER gets the full error"

    log.close()
    raw = (tmp_path / "svc.jsonl").read_text()
    assert canary not in raw, "the obslog stream must never see payloads"
    events = [json.loads(line) for line in raw.splitlines()]
    kinds = {e["kind"] for e in events}
    assert {"service_rejected", "service_expired", "service_poisoned"} <= kinds
    rej = next(e for e in events if e["kind"] == "service_rejected")
    assert rej["error_kind"] == "QUEUE_FULL"
    pois = next(e for e in events if e["kind"] == "service_poisoned")
    assert pois["error_kind"] == "RuntimeError" and pois["ceremony"] == cid
    # each failure path owns a DISTINCT metric series
    snap = reg.snapshot()["counters"]
    assert snap["service_rejected_total"] == 1
    assert snap['service_expired_total{where="queued"}'] == 1
    assert snap["service_poisoned_total"] == 1


# ---------------------------------------------------------------------------
# real engine, smallest bucket: pad-and-mask oracle + end-to-end masters
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def runtime():
    return engine.WarmRuntime()


@pytest.fixture(scope="module")
def convoy1(runtime):
    """One seeded width-1 ceremony through the padded lane, plus its
    in-flight tensors (kept for the tensor-level oracle)."""
    req = CeremonyRequest(CURVE, N, T, seed=0xC0FFEE, rho_bits=32)
    fl = engine.start_convoy(runtime, [req])
    outs = engine.finish_convoy(runtime, fl)
    return req, fl, outs


def test_padded_run_matches_unpadded_real_lanes(runtime, convoy1):
    """The pad-and-mask contract at tensor level: every real lane of the
    padded round-1 tensors is bit-identical to the unpadded run, and the
    phantom dealers deal all-zero shares."""
    import jax.numpy as jnp

    from dkg_tpu.dkg import ceremony as ce

    req, fl, _ = convoy1
    cfg = ce.CeremonyConfig(req.curve, req.n, req.t)
    _, g_table, h_table = runtime.commitment(req.curve, req.shared_string)
    ca, cb = engine.draw_coeffs(cfg, engine.rng_for(req))
    a, e, s, r = ce.deal(cfg, jnp.asarray(ca), jnp.asarray(cb), g_table, h_table)
    n, tc = req.n, req.t + 1
    np.testing.assert_array_equal(np.asarray(fl.a[0])[:n, :tc], np.asarray(a))
    np.testing.assert_array_equal(np.asarray(fl.e[0])[:n, :tc], np.asarray(e))
    np.testing.assert_array_equal(np.asarray(fl.s[0])[:n, :n], np.asarray(s))
    np.testing.assert_array_equal(np.asarray(fl.r[0])[:n, :n], np.asarray(r))
    # phantom dealers are zero polynomials: zero shares to everyone
    assert not np.asarray(fl.s[0])[n:].any()
    assert not np.asarray(fl.r[0])[n:].any()


@pytest.mark.parametrize(
    "n, t, bucket",
    [(16, 5, (16, 5)), (5, 2, (8, 2)), (9, 3, (16, 4))],
    ids=["16x5_exact", "5x2_padded", "9x3_padded"],
)
def test_draw_coeffs_is_batched_ceremonys_draw(n, t, bucket):
    """A seeded request deals ``BatchedCeremony``'s polynomials of the
    same seed: both draw through ``fh.draw_limbs``, ``a`` before ``b``,
    and padding to the bucket only adds zeros (none at all, and no copy,
    where the real shape is the bucket's)."""
    from dkg_tpu.dkg import ceremony as ce

    assert buckets.bucket_for(n, t) == buckets.Bucket(*bucket)
    req = CeremonyRequest(CURVE, n, t, seed=0xBEEF + n)
    a, b = engine.draw_coeffs(ce.CeremonyConfig(CURVE, n, t), engine.rng_for(req))
    ref = ce.BatchedCeremony(CURVE, n, t, b"draw-order", random.Random(req.seed))
    np.testing.assert_array_equal(a, np.asarray(ref.coeffs_a))
    np.testing.assert_array_equal(b, np.asarray(ref.coeffs_b))
    for real in (a, b):
        padded = engine.pad_coeffs(real, *bucket)
        assert padded.shape == (bucket[0], bucket[1] + 1, real.shape[-1])
        assert (padded is real) == ((n, t) == bucket)
        np.testing.assert_array_equal(padded[:n, : t + 1], real)
        assert not padded[n:].any() and not padded[:, t + 1 :].any()


def test_padded_master_matches_fresh_single_run(convoy1):
    """The service's padded+bucketed execution must be invisible in the
    result: same seed, same master key as a fresh unpadded ceremony."""
    req, _, outs = convoy1
    (out,) = outs
    assert out.status == "done"
    assert out.qualified == (True,) * req.n
    assert out.complaints == ()
    assert out.bucket_n == 8 and out.bucket_t == 2
    assert out.final_shares is not None and len(out.final_shares) == req.n
    assert out.master == engine.run_single_reference(req)


# ---------------------------------------------------------------------------
# kept coefficient tensors (engine.CoeffStaging): a large draw's width-1
# convoy writes into tensors the runtime keeps.  The threshold is set
# down to this bucket's 15 scalars a draw; the shapes are convoy1's.
# ---------------------------------------------------------------------------


def _staging_counts():
    c = REGISTRY.snapshot()["counters"]
    return tuple(c.get(f'coeff_staging_total{{event="{e}"}}', 0) for e in ("alloc", "reuse"))


def _serve_one(runtime, req):
    (out,) = engine.finish_convoy(runtime, engine.start_convoy(runtime, [req]))
    return out


def _oracle_faults(req, out):
    """``benchmark/bench_oracle.py``'s verdict (Python ints and the host
    group only): the counts of what differs, every limit 0."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "benchmark"))
    import bench_support

    bench_support.bench_run()  # puts benchmark/ on sys.path
    import bench_oracle

    plain = {"curve": req.curve, "n": req.n, "t": req.t, "seed": req.seed}
    return bench_oracle.check_outcome(plain, out, list(range(1, req.n + 1)))


def test_kept_tensors_are_rewritten_between_requests_and_not_under_them(monkeypatch):
    """Two seeds back to back through one runtime, then the first again:
    each outcome is the plain reference's and a fresh runtime's bit for
    bit (on this backend a device array may alias the host tensor it was
    made from), from ONE pair of tensors, allocated once."""
    monkeypatch.setattr(engine.fh, "BLOCK_MIN_SCALARS", N * (T + 1))
    reqs = [CeremonyRequest(CURVE, N, T, seed=2**33 + s, rho_bits=32) for s in (1, 2, 1)]
    before = _staging_counts()
    rt = engine.WarmRuntime()
    outs = [_serve_one(rt, req) for req in reqs]
    assert tuple(a - b for a, b in zip(_staging_counts(), before)) == (1, 2)
    monkeypatch.setattr(engine.fh, "BLOCK_MIN_SCALARS", 1 << 40)
    before = _staging_counts()
    for req, out in zip(reqs, outs):
        assert not any(_oracle_faults(req, out).values())
        fresh = _serve_one(engine.WarmRuntime(), req)
        assert out.status == fresh.status == "done" and out.master == fresh.master
        np.testing.assert_array_equal(out.final_shares, fresh.final_shares)
    assert _staging_counts() == before  # under the threshold nothing is kept
    assert outs[0].master == outs[2].master != outs[1].master


def test_kept_tensors_pad_lanes_are_zero_after_a_fuller_request(monkeypatch):
    """A (5,2) request through the tensors a full-bucket (8,2) request
    just used: the lanes it does not write read zero, and its outcome is
    a fresh unpadded ceremony's."""
    monkeypatch.setattr(engine.fh, "BLOCK_MIN_SCALARS", N * (T + 1))
    rt = engine.WarmRuntime()
    full = CeremonyRequest(CURVE, 8, 2, seed=77, rho_bits=32)
    assert _serve_one(rt, full).master == engine.run_single_reference(full)
    req = CeremonyRequest(CURVE, N, T, seed=78, rho_bits=32)
    fl = engine.start_convoy(rt, [req])
    staged = fl.staged
    assert staged.a.shape == staged.b.shape == (8, 3, 16) and staged.real == (N, T + 1)
    for x in (staged.a, staged.b):
        assert x[:N].any() and not x[N:].any()
    (out,) = engine.finish_convoy(rt, fl)
    assert fl.staged is None and out.master == engine.run_single_reference(req)


def test_staged_lanes_zero_what_the_last_request_wrote_outside_them():
    pair = engine.StagedCoeffs(np.zeros((8, 4, 2), np.uint32), np.zeros((8, 4, 2), np.uint32))
    for real in ((8, 4), (5, 3), (7, 2), (3, 4)):
        for view in pair.lanes(*real):
            assert view.shape == (*real, 2)
            view[...] = 9
        for x in (pair.a, pair.b):
            assert (x[: real[0], : real[1]] == 9).all()
            assert not x[real[0] :].any() and not x[:, real[1] :].any()


def test_two_convoys_in_flight_never_share_kept_tensors(monkeypatch):
    """A two-deep worker starts its next convoy while deal of the last
    may still read its tensors: each convoy in flight has a pair of its
    own, and a pair comes back when its convoy's deal is over."""
    monkeypatch.setattr(engine.fh, "BLOCK_MIN_SCALARS", N * (T + 1))
    rt = engine.WarmRuntime()
    reqs = [CeremonyRequest(CURVE, N, T, seed=900 + i, rho_bits=32) for i in range(3)]
    before = _staging_counts()
    fl0 = engine.start_convoy(rt, [reqs[0]])
    fl1 = engine.start_convoy(rt, [reqs[1]])
    assert fl0.staged is not fl1.staged
    assert not np.shares_memory(fl0.staged.a, fl1.staged.a)
    assert not np.shares_memory(fl0.staged.b, fl1.staged.b)
    first = fl0.staged
    (out0,) = engine.finish_convoy(rt, fl0)
    fl2 = engine.start_convoy(rt, [reqs[2]])  # the worker's next: the pair that came back
    assert fl2.staged is first and fl2.staged is not fl1.staged
    (out1,) = engine.finish_convoy(rt, fl1)
    (out2,) = engine.finish_convoy(rt, fl2)
    assert tuple(a - b for a, b in zip(_staging_counts(), before)) == (2, 1)
    for req, out in zip(reqs, (out0, out1, out2)):
        assert out.master == engine.run_single_reference(req)


def test_staging_under_contending_workers(monkeypatch):
    """More threads than cores lending and giving back: a pair is never
    in two hands, and what the runtime keeps idle stays under its bound."""
    import sys

    shape = (4, 3, 2)
    pair_bytes = 2 * 4 * 3 * 2 * 4
    monkeypatch.setattr(engine, "STAGING_KEEP_BYTES", 3 * pair_bytes)
    staging = engine.CoeffStaging()
    clashes, rounds = [], 300

    def worker(me):
        for _ in range(rounds):
            pair = staging.lend(shape)
            pair.a[...] = me
            time.sleep(0)
            if (pair.a != me).any():
                clashes.append(me)
            staging.give_back(pair)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(i + 1,)) for i in range(24)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(th.is_alive() for th in threads) and not clashes
    idle = staging._idle[shape]
    assert 1 <= len(idle) <= 3 and staging._idle_bytes == len(idle) * pair_bytes
    assert len({id(p) for p in idle}) == len(idle)


@pytest.mark.parametrize("draws", ["fresh", "kept"])
def test_scheduler_end_to_end_masters_match_references(runtime, monkeypatch, draws):
    """Two two-deep workers; ``kept``: every draw into tensors the
    runtime keeps, rewritten while other convoys are live."""
    if draws == "kept":
        monkeypatch.setattr(engine.fh, "BLOCK_MIN_SCALARS", N * (T + 1))
    reqs = [CeremonyRequest(CURVE, N, T, seed=500 + i, rho_bits=32) for i in range(3)]
    with CeremonyScheduler(
        concurrency=2, queue_depth=8, batch_max=1, runtime=runtime
    ) as sch:
        ids = [sch.submit(r) for r in reqs]
        outs = [sch.result(i, timeout=120) for i in ids]
    for req, out in zip(reqs, outs):
        assert out.status == "done"
        assert out.master == engine.run_single_reference(req)
        assert out.completed_at > 0 and out.seconds > 0


def test_durable_restart_resumes_and_reserves(tmp_path, runtime, monkeypatch):
    """Kill-and-restart: requests journalled at admission but never
    finished (the crash window) are re-run from their seeds on restart
    with zero failures and bit-identical masters; a second restart
    re-serves the outcomes without touching the engine."""
    reqs = [
        CeremonyRequest(CURVE, N, T, seed=900 + i, rho_bits=32, durable=True)
        for i in range(2)
    ]
    crashed = ServiceJournal(tmp_path)
    cids = [engine.request_id(r, i) for i, r in enumerate(reqs)]
    for i, (cid, r) in enumerate(zip(cids, reqs)):
        crashed.record_request(cid, i, r)

    sch = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        wal_dir=str(tmp_path), runtime=runtime,
    )
    outs = [sch.result(cid, timeout=300) for cid in cids]
    sch.close()
    assert [o.status for o in outs] == ["done", "done"]
    masters = [engine.run_single_reference(r) for r in reqs]
    assert [o.master for o in outs] == masters

    def _bomb(*a, **kw):
        raise AssertionError("restart with a fully terminal journal re-ran work")

    monkeypatch.setattr(scheduler_mod, "start_convoy", _bomb)
    sch2 = CeremonyScheduler(
        concurrency=1, queue_depth=8, batch_max=1,
        wal_dir=str(tmp_path), runtime=runtime,
    )
    for cid, master in zip(cids, masters):
        assert sch2.poll(cid) == "done"
        out = sch2.result(cid)
        assert out.master == master
        assert out.final_shares is None  # secrets never touch the journal
    sch2.close()


# ---------------------------------------------------------------------------
# slow legs: stacked convoys, convoy-folded Fiat-Shamir, secp wire bytes
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_stacked_convoy_bit_exact_and_rho_fold(runtime):
    """A width-2 stacked convoy (vmapped lane) returns bit-identical
    masters to fresh single runs, and the convoy-folded Fiat-Shamir
    derivation equals the per-ceremony one on every lane."""
    from dkg_tpu.dkg import ceremony as ce

    reqs = [CeremonyRequest(CURVE, N, T, seed=700 + i, rho_bits=32) for i in range(2)]
    fl = engine.start_convoy(runtime, reqs)
    a, e = np.asarray(fl.a), np.asarray(fl.e)
    s, r = np.asarray(fl.s), np.asarray(fl.r)
    rho_convoy = engine.derive_rho_convoy(fl.cfg_pad, a, e, s, r, 32)
    for i in range(2):
        rho_one = ce.derive_rho(fl.cfg_pad, a[i], e[i], s[i], r[i], 32)
        np.testing.assert_array_equal(rho_convoy[i], np.asarray(rho_one))
    outs = engine.finish_convoy(runtime, fl)
    for req, out in zip(reqs, outs):
        assert out.status == "done"
        assert out.master == engine.run_single_reference(req)


@pytest.mark.slow
def test_secp_padded_wire_bytes_match_unpadded_pipeline(runtime):
    """secp256k1 leg with BOTH axes padded ((5,1) -> bucket (8,2)): the
    padded lane's wire-format BroadcastPhase1 bytes are identical to the
    unpadded ``seal_shares_pipeline`` leg, and the master matches a
    fresh unpadded run."""
    import jax.numpy as jnp

    from dkg_tpu.dkg import ceremony as ce
    from dkg_tpu.dkg.hybrid_batch import broadcasts_from_batch, seal_shares_pipeline
    from dkg_tpu.fields import host as fh
    from dkg_tpu.groups import device as gd
    from dkg_tpu.groups import host as gh
    from dkg_tpu.utils import serde

    curve, n, t = "secp256k1", 5, 1
    req = CeremonyRequest(curve, n, t, seed=31337, rho_bits=32)
    assert req.bucket() == buckets.Bucket(8, 2)  # n AND t both pad
    group = gh.ALL_GROUPS[curve]
    pks = [group.scalar_mul(i + 7, group.generator()) for i in range(n)]

    fl = engine.start_convoy(runtime, [req])
    wire_padded = engine.wire_broadcasts(
        runtime, req, fl, 0, pks, random.Random(99)
    )

    # unpadded reference: same coeffs, real-shape deal + seal pipeline
    cfg = ce.CeremonyConfig(curve, n, t)
    _, g_table, h_table = runtime.commitment(curve, req.shared_string)
    ca, cb = engine.draw_coeffs(cfg, engine.rng_for(req))
    _, e_r, s_r, r_r = ce.deal(cfg, jnp.asarray(ca), jnp.asarray(cb), g_table, h_table)
    fs = cfg.cs.scalar
    rng = random.Random(99)
    r_enc = fh.encode(
        fs, [[fs.rand_int(rng) for _ in range(n)] for _ in range(n)]
    )
    sealed = seal_shares_pipeline(
        group, cfg, np.asarray(s_r), np.asarray(r_r),
        gd.from_host(cfg.cs, pks), jnp.asarray(r_enc), g_table,
    )
    bcasts = broadcasts_from_batch(group, cfg, np.asarray(e_r), sealed)
    wire_ref = [serde.encode_phase1(group, b) for b in bcasts]

    assert len(wire_padded) == len(wire_ref) == n
    for got, want in zip(wire_padded, wire_ref):
        assert got == want

    (out,) = engine.finish_convoy(runtime, fl)
    assert out.status == "done"
    assert out.master == engine.run_single_reference(req)
