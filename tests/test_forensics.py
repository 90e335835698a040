"""Data-plane forensics tests: wire accounting exactness, causal-flow
linkage, and critical-path straggler attribution.

Three layers under test:

* ``utils/serde.py`` wire-size formulas vs the counted transport — a
  fault-free live ceremony's published bytes must match the analytical
  prediction EXACTLY (the bench publishes the prediction, perf_regress
  gates it, so drift here would silently ungate the wire);
* ``obslog.to_chrome_trace`` flow events — every publish a round_tail
  consumed must link (ISSUE acceptance: >= 95%);
* ``obslog.critical_path`` / ``scripts/forensics.py`` — the
  compute/transport/retry/quarantine decomposition partitions each
  round barrier (acceptance: sums to barrier within 5%), stragglers
  are named correctly for both delayed and absent parties.
"""

import gzip
import json
import pathlib
import sys

import pytest

from dkg_tpu.groups import host as gh
from dkg_tpu.utils import obslog, serde
from dkg_tpu.utils.metrics import MetricsRegistry

G = gh.RISTRETTO255

_SCRIPTS = pathlib.Path(__file__).resolve().parent.parent / "scripts"


def _scripts_import(name: str):
    sys.path.insert(0, str(_SCRIPTS))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


# ---------------------------------------------------------------------------
# synthetic-event critical path: exact attribution arithmetic
# ---------------------------------------------------------------------------


def _ev(kind, ts, party, round_no, cid="cer01", **kw):
    return {
        "ts": ts, "mono": ts, "kind": kind, "ceremony_id": cid,
        "party": party, "round": round_no, **kw,
    }


@pytest.mark.parametrize("closer", [3, 2])
def test_critical_path_attributes_delayed_straggler(closer):
    """p2 publishes last after an injected 0.6 s delay and a 0.1 s RPC
    backoff; the decomposition charges those buckets and the residuals
    land in compute (before its publish) and transport (after).  The
    same whichever party closes the round last: a straggler that is
    also the closer is charged its delay once, before its publish (it
    was charged again after it, up to that leg's width: ROADMAP D15)."""
    other = 5 - closer
    events = [
        _ev("round_head", 10.0, 1, 1),
        _ev("round_head", 10.0, 2, 1),
        _ev("round_head", 10.1, 3, 1),
        _ev("publish", 10.2, 1, 1, bytes=686, seq=0),
        _ev("publish", 10.3, 3, 1, bytes=686, seq=0),
        _ev("rpc_retry", 10.4, 2, 1, attempt=1, error="OSError",
            backoff_s=0.1, op="publish"),
        _ev("fault_injected", 10.2, 2, 1, fault="delay", sender=2,
            seconds=0.6),
        _ev("publish", 11.0, 2, 1, bytes=686, seq=0),
        _ev("round_tail", 11.1, 1, 1, present=3, senders=[1, 2, 3],
            quarantined_delta=0, timed_out=False),
        _ev("round_tail", 11.15, other, 1, present=3, senders=[1, 2, 3],
            quarantined_delta=0, timed_out=False),
        _ev("round_tail", 11.3, closer, 1, present=3, senders=[1, 2, 3],
            quarantined_delta=0, timed_out=False),
    ]
    reg = MetricsRegistry()
    rows = obslog.critical_path(events, registry=reg)
    assert len(rows) == 1
    row = rows[0]
    assert row["straggler"] == 2 and not row["straggler_absent"]
    assert row["barrier_s"] == pytest.approx(1.3)
    assert row["straggler_lag_s"] == pytest.approx(1.0)  # 10.0 -> 11.0
    assert row["retry_s"] == pytest.approx(0.1)
    assert row["quarantine_s"] == pytest.approx(0.6)
    assert row["compute_s"] == pytest.approx(0.3)  # leg1 minus retry+fault
    assert row["transport_s"] == pytest.approx(0.3)  # 11.0 -> 11.3, the closer's tail
    total = (
        row["compute_s"] + row["transport_s"] + row["retry_s"]
        + row["quarantine_s"]
    )
    assert total == pytest.approx(row["barrier_s"])  # exact partition
    assert row["present"] == 3 and row["expected"] == 3
    # the gauge feeds the SLO layer
    gauges = {
        k: v for k, v in reg.snapshot()["gauges"].items()
        if k.startswith("net_round_straggler_lag_seconds")
    }
    (labels, value), = gauges.items()
    assert 'straggler="2"' in labels and value == pytest.approx(1.0)


def test_critical_path_absent_straggler_charges_quarantine():
    """A timed-out round that never saw p3's publish names p3 as the
    (absent) straggler and charges the whole wait to quarantine —
    compute is zero because no crypto work was witnessed."""
    events = [
        _ev("round_head", 20.0, 1, 2),
        _ev("round_head", 20.0, 2, 2),
        _ev("round_head", 20.0, 3, 2),
        _ev("publish", 20.1, 1, 2, bytes=66, seq=1),
        _ev("publish", 20.2, 2, 2, bytes=66, seq=1),
        _ev("round_tail", 22.0, 1, 2, present=2, senders=[1, 2],
            quarantined_delta=0, timed_out=True),
        _ev("round_tail", 22.0, 2, 2, present=2, senders=[1, 2],
            quarantined_delta=0, timed_out=True),
    ]
    rows = obslog.critical_path(events)
    assert len(rows) == 1
    row = rows[0]
    assert row["straggler"] == 3 and row["straggler_absent"]
    assert row["timed_out"]
    assert row["compute_s"] == 0.0
    assert row["quarantine_s"] == pytest.approx(2.0)
    assert row["barrier_s"] == pytest.approx(2.0)
    assert row["present"] == 2 and row["expected"] == 3


def test_critical_path_skips_rounds_that_never_closed():
    events = [
        _ev("round_head", 1.0, 1, 1),
        _ev("publish", 1.1, 1, 1, bytes=4, seq=0),
    ]
    assert obslog.critical_path(events) == []


def test_critical_path_splits_ceremonies():
    """Two interleaved ceremonies report independently, sorted by id."""
    events = []
    for cid, base in (("cerB", 5.0), ("cerA", 7.0)):
        events += [
            _ev("round_head", base, 1, 1, cid=cid),
            _ev("publish", base + 0.1, 1, 1, cid=cid, bytes=8, seq=0),
            _ev("round_tail", base + 0.2, 1, 1, cid=cid, present=1,
                senders=[1], quarantined_delta=0, timed_out=False),
        ]
    rows = obslog.critical_path(events)
    assert [r["ceremony_id"] for r in rows] == ["cerA", "cerB"]


# ---------------------------------------------------------------------------
# live ceremony: serde-exact wire accounting + flow linkage + forensics CLI
# ---------------------------------------------------------------------------


def _run_ceremony(tmp_path, plan, seed, shared, timeout=5.0):
    from dkg_tpu.net.channel import InProcessChannel
    from dkg_tpu.net.faults import make_committee, run_with_faults

    n, t = 4, 1
    env, keys, pks = make_committee(G, n, t, seed, shared_string=shared)
    chan = InProcessChannel()
    results = run_with_faults(
        env, keys, pks, plan, lambda i: chan, timeout=timeout, seed=seed,
    )
    events = [
        ev
        for p in sorted(tmp_path.glob("*.jsonl"))
        for ev in obslog.load_jsonl(p)
    ]
    return env, results, events


def test_live_fault_free_wire_bytes_match_serde_exactly(monkeypatch, tmp_path):
    from dkg_tpu.net.faults import FaultPlan

    monkeypatch.setenv("DKG_TPU_OBSLOG", str(tmp_path))
    n, t = 4, 1
    env, results, events = _run_ceremony(
        tmp_path, FaultPlan(0x11EE), 0x11EE, b"forensics-wire"
    )
    assert all(r.ok for r in results)
    # the serde formulas predict the counted data plane byte-for-byte:
    # each fault-free party publishes phase1 (dealing) + phase3 (bare
    # commitments) + three empty rounds
    per_party = serde.party_wire_bytes(G, n, t)
    assert per_party == (
        serde.phase1_wire_bytes(G, n, t) + serde.phase3_wire_bytes(G, n, t)
    )
    out_by_party = {}
    for ev in events:
        if ev["kind"] == "publish":
            out_by_party[ev["party"]] = (
                out_by_party.get(ev["party"], 0) + ev["bytes"]
            )
    assert out_by_party == {i: per_party for i in range(1, n + 1)}
    assert sum(out_by_party.values()) == serde.ceremony_wire_bytes(G, n, t)
    # schema conformance on the full fault-free stream
    assert obslog.validate_events(events) == []
    # flow linkage: every publish a tail consumed draws an arrow
    doc = obslog.to_chrome_trace(events)
    starts = [e for e in doc["traceEvents"] if e.get("ph") == "s"]
    pubs = [ev for ev in events if ev["kind"] == "publish"]
    linked_keys = set()
    for e in starts:
        # id: "{cid}:round_tail:{round}:{sender}:{seq}->{fetcher}"
        cid, _, rnd, sender, _ = e["id"].split(":", 4)
        linked_keys.add((cid, int(rnd), int(sender)))
    pub_keys = {
        (ev["ceremony_id"], ev["round"], ev["party"]) for ev in pubs
    }
    assert len(linked_keys & pub_keys) / len(pub_keys) >= 0.95


def test_live_chaos_forensics_report_and_cli(monkeypatch, tmp_path, capsys):
    """A delayed ceremony analysed end to end through the CLI: the
    report names the delayed party as round 1's straggler, charges its
    injected delay to quarantine, and every round's decomposition sums
    to its barrier within 5%."""
    from dkg_tpu.net.faults import FaultPlan

    obsdir = tmp_path / "obs"
    obsdir.mkdir()
    monkeypatch.setenv("DKG_TPU_OBSLOG", str(obsdir))
    plan = FaultPlan(0xF0F0).delay(1, sender=2, seconds=0.3)
    env, results, events = _run_ceremony(
        obsdir, plan, 0xF0F0, b"forensics-chaos"
    )
    assert all(r.ok for r in results)
    assert obslog.validate_events(events) == []

    rows = obslog.critical_path(events)
    assert rows, "no barriers reconstructed"
    r1 = [r for r in rows if r["round"] == 1]
    assert r1 and r1[0]["straggler"] == 2
    # against the delay as the plan slept it, which a loaded host stretches
    # past the nominal 0.3 (a sleep only overshoots)
    assert plan.slept[(1, 2)] >= 0.299
    assert r1[0]["quarantine_s"] == pytest.approx(plan.slept[(1, 2)], abs=0.05)
    for row in rows:
        total = (
            row["compute_s"] + row["transport_s"] + row["retry_s"]
            + row["quarantine_s"]
        )
        assert total == pytest.approx(row["barrier_s"], rel=0.05, abs=1e-6)

    forensics = _scripts_import("forensics")
    out_json = tmp_path / "report.json"
    rc = forensics.main(
        [str(obsdir), "--json", str(out_json), "--metrics"]
    )
    captured = capsys.readouterr().out
    assert rc == 0
    assert "straggler" in captured and "p2" in captured
    assert "net_round_straggler_lag_seconds" in captured  # --metrics leg
    doc = json.loads(out_json.read_text())
    assert doc["rounds"] and doc["rounds"][0]["ceremony_id"]
    # unknown ceremony filter: nothing to analyse is a typed failure
    assert forensics.main([str(obsdir), "--ceremony", "zzzz"]) == 1


# ---------------------------------------------------------------------------
# trace_viz input handling: gzipped sinks and glob patterns
# ---------------------------------------------------------------------------


def test_trace_viz_collects_gz_and_glob_inputs(tmp_path):
    trace_viz = _scripts_import("trace_viz")
    line = json.dumps(_ev("round_head", 1.0, 1, 1)) + "\n"
    plain = tmp_path / "cer01-p001.jsonl"
    plain.write_text(line)
    gz = tmp_path / "cer01-p002.jsonl.gz"
    with gzip.open(gz, "wt", encoding="utf-8") as fh:
        fh.write(json.dumps(_ev("round_head", 1.1, 2, 1)) + "\n")
    # a directory expands to both spellings
    got = trace_viz.collect_paths([str(tmp_path)])
    assert {str(p) for p in got} == {str(plain), str(gz)}
    # a glob pattern narrows to matches only
    got = trace_viz.collect_paths([str(tmp_path / "*.jsonl.gz")])
    assert [str(p) for p in got] == [str(gz)]
    # gzipped sinks parse through the same loader
    evs = obslog.load_jsonl(gz)
    assert [e["party"] for e in evs] == [2]


def test_load_jsonl_tolerates_torn_gzip_tail(tmp_path):
    """A crash mid-write leaves a torn gzip member; the loader keeps
    every complete line instead of poisoning the whole timeline."""
    gz = tmp_path / "torn.jsonl.gz"
    with gzip.open(gz, "wt", encoding="utf-8") as fh:
        fh.write(json.dumps(_ev("round_head", 1.0, 1, 1)) + "\n")
    blob = gz.read_bytes()
    gz.write_bytes(blob + b"\x1f\x8b\x08\x00torn-member")
    evs = obslog.load_jsonl(gz)
    assert [e["kind"] for e in evs] == ["round_head"]


# ---------------------------------------------------------------------------
# serde wire formulas pinned against the live encoders
# ---------------------------------------------------------------------------


def test_serde_wire_formulas_pin_concrete_sizes():
    """The analytical sizes at the bench's reference shape: ristretto255
    points/scalars are 32 bytes, so phase1 at (n=4, t=1) is
    2 + 2*32 + 2 + 4*(2 + 2*(32+4+32)) = 620 and phase3 is 2 + 2*32 =
    66.  A wire-format change moves these on purpose or not at all."""
    assert serde.phase1_wire_bytes(G, 4, 1) == 620
    assert serde.phase3_wire_bytes(G, 4, 1) == 66
    assert serde.party_wire_bytes(G, 4, 1) == 686
    assert serde.ceremony_wire_bytes(G, 4, 1) == 4 * 686
    # scaling shape: phase1 grows linearly in n, commitments in t
    assert (
        serde.phase1_wire_bytes(G, 8, 1) - serde.phase1_wire_bytes(G, 4, 1)
        == 4 * (2 + 2 * (32 + 4 + 32))
    )
    assert (
        serde.phase3_wire_bytes(G, 4, 3) - serde.phase3_wire_bytes(G, 4, 1)
        == 2 * 32
    )
