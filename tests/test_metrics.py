"""Metrics registry tests: counters/gauges/histograms, exports, feeders."""

import json
import threading
import time

from dkg_tpu.utils.metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    observe_party_result,
    observe_trace,
)
from dkg_tpu.utils.tracing import CeremonyTrace


def test_counters_gauges_and_labels():
    reg = MetricsRegistry()
    reg.inc("rpcs_total", op="publish")
    reg.inc("rpcs_total", op="publish")
    reg.inc("rpcs_total", op="fetch")
    reg.inc("bytes_total", 100, direction="in")
    reg.set_gauge("capacity", 3)
    reg.set_gauge("capacity", 7)  # gauges overwrite, counters add
    snap = reg.snapshot()
    assert snap["counters"]['rpcs_total{op="publish"}'] == 2
    assert snap["counters"]['rpcs_total{op="fetch"}'] == 1
    assert snap["counters"]['bytes_total{direction="in"}'] == 100
    assert snap["gauges"]["capacity"] == 7


def test_histogram_cumulative_buckets_and_sum():
    reg = MetricsRegistry()
    for v in (0.003, 0.03, 0.03, 100.0):
        reg.observe("lat_seconds", v)
    h = reg.snapshot()["histograms"]["lat_seconds"]
    assert h["count"] == 4
    assert h["sum"] == sum((0.003, 0.03, 0.03, 100.0))
    # cumulative le semantics: 0.003 <= 0.005; the two 0.03s land at 0.05
    assert h["buckets"]["0.005"] == 1
    assert h["buckets"]["0.05"] == 3
    assert h["buckets"]["60"] == 3  # 100.0 is overflow
    assert h["buckets"]["+Inf"] == 4


def test_snapshot_is_json_able():
    reg = MetricsRegistry()
    reg.inc("a_total")
    reg.observe("b_seconds", 0.5, phase="deal")
    reg.set_gauge("c", 1.5)
    snap = reg.snapshot()
    assert json.loads(json.dumps(snap)) == snap


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.inc("dkg_rpcs_total", 3, op="publish")
    reg.set_gauge("dkg_capacity", 2)
    reg.observe("dkg_lat_seconds", 0.03)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE dkg_rpcs_total counter" in lines
    assert 'dkg_rpcs_total{op="publish"} 3' in lines
    assert "# TYPE dkg_capacity gauge" in lines
    assert "dkg_capacity 2" in lines
    assert "# TYPE dkg_lat_seconds histogram" in lines
    # one _bucket line per default bucket plus +Inf, then _sum/_count
    assert sum(l.startswith("dkg_lat_seconds_bucket{le=") for l in lines) == (
        len(DEFAULT_BUCKETS) + 1
    )
    assert 'dkg_lat_seconds_bucket{le="+Inf"} 1' in lines
    assert "dkg_lat_seconds_sum 0.03" in lines
    assert "dkg_lat_seconds_count 1" in lines
    # text and snapshot describe the same cumulative distribution
    snap = reg.snapshot()["histograms"]["dkg_lat_seconds"]
    for line in lines:
        if line.startswith("dkg_lat_seconds_bucket{le="):
            le = line.split('le="')[1].split('"')[0]
            assert int(line.rsplit(" ", 1)[1]) == snap["buckets"][le]


def test_reset_drops_every_series():
    reg = MetricsRegistry()
    reg.inc("x_total")
    reg.observe("y_seconds", 1.0)
    reg.set_gauge("z", 1)
    reg.reset()
    snap = reg.snapshot()
    at = snap.pop("at")
    assert snap == {"counters": {}, "gauges": {}, "histograms": {}}
    assert 0 < at <= time.perf_counter()  # when it was taken, on the timeline's clock


def test_observe_trace_feeds_phases_subs_and_counters():
    # a fresh local registry: the process-wide one is fed by every
    # phase_span in the suite and would make counts nondeterministic
    reg = MetricsRegistry()
    tr = CeremonyTrace()
    tr.record("deal", 1.0)
    tr.record("verify", 0.25)
    tr.record_sub("fiat_shamir", "digest", 0.125)
    tr.bump("complaints_filed", 2)
    observe_trace(tr, registry=reg)
    snap = reg.snapshot()
    assert snap["counters"]["dkg_ceremonies_total"] == 1
    assert (
        snap["counters"]['dkg_ceremony_counter_total{counter="complaints_filed"}'] == 2
    )
    assert snap["histograms"]['dkg_phase_seconds{phase="deal"}']["count"] == 1
    assert (
        snap["histograms"]['dkg_subphase_seconds{phase="fiat_shamir",sub="digest"}'][
            "count"
        ]
        == 1
    )


def test_observe_party_result_maps_every_counter():
    from dkg_tpu.net.party import PartyResult

    reg = MetricsRegistry()
    res = PartyResult(index=3)
    res.quarantined = 2
    res.timeouts = 1
    res.retries = 4
    res.resumes = 1
    res.wal_records = 6
    res.replayed_rounds = 2
    observe_party_result(res, registry=reg)  # no master -> outcome=error
    snap = reg.snapshot()["counters"]
    assert snap['dkg_parties_total{outcome="error"}'] == 1
    assert snap["dkg_party_quarantined_total"] == 2
    assert snap["dkg_party_round_timeouts_total"] == 1
    assert snap["dkg_party_rpc_retries_total"] == 4
    assert snap["dkg_party_resumes_total"] == 1
    assert snap["dkg_wal_records_total"] == 6
    assert snap["dkg_wal_replayed_rounds_total"] == 2


def test_label_values_are_escaped_in_exposition():
    """A hostile or merely unlucky label value (quotes, backslashes,
    newlines — e.g. an error string used as a label) must not be able
    to break the Prometheus exposition format."""
    reg = MetricsRegistry()
    nasty = 'he said "hi"\\\nand left'
    reg.inc("dkg_errors_total", kind=nasty)
    text = reg.prometheus_text()
    lines = text.splitlines()
    # the exposition stays line-oriented: no raw newline leaked through
    assert all("\n" not in l for l in lines)
    [series] = [l for l in lines if l.startswith("dkg_errors_total{")]
    assert series == (
        'dkg_errors_total{kind="he said \\"hi\\"\\\\\\nand left"} 1'
    )
    # snapshot keys carry the same escaped series name, so exposition
    # lines and snapshot entries always name the same series
    assert reg.snapshot()["counters"][series.rsplit(" ", 1)[0]] == 1


def test_none_valued_labels_are_dropped():
    reg = MetricsRegistry()
    reg.inc("dkg_x_total", ceremony_id=None)
    reg.observe("dkg_y_seconds", 0.1, ceremony_id=None, phase="deal")
    snap = reg.snapshot()
    assert snap["counters"] == {"dkg_x_total": 1}
    assert list(snap["histograms"]) == ['dkg_y_seconds{phase="deal"}']


def test_observe_trace_labels_series_with_ceremony_id():
    reg = MetricsRegistry()
    tr = CeremonyTrace()
    tr.record("deal", 1.0)
    tr.bump("complaints_filed", 1)
    observe_trace(tr, registry=reg, ceremony_id="abc123")
    snap = reg.snapshot()
    assert snap["counters"]['dkg_ceremonies_total{ceremony_id="abc123"}'] == 1
    assert (
        snap["counters"][
            'dkg_ceremony_counter_total{ceremony_id="abc123",counter="complaints_filed"}'
        ]
        == 1
    )
    assert (
        snap["histograms"][
            'dkg_phase_seconds{ceremony_id="abc123",phase="deal"}'
        ]["count"]
        == 1
    )
    # two tenants feeding one registry stay distinct series
    tr2 = CeremonyTrace()
    tr2.record("deal", 2.0)
    observe_trace(tr2, registry=reg, ceremony_id="def456")
    snap = reg.snapshot()
    assert snap["counters"]['dkg_ceremonies_total{ceremony_id="abc123"}'] == 1
    assert snap["counters"]['dkg_ceremonies_total{ceremony_id="def456"}'] == 1


def test_observe_party_result_labels_series_with_ceremony_id():
    from dkg_tpu.net.party import PartyResult

    reg = MetricsRegistry()
    res = PartyResult(index=1)
    res.quarantined = 1
    observe_party_result(res, registry=reg, ceremony_id="c1")
    snap = reg.snapshot()["counters"]
    assert snap['dkg_parties_total{ceremony_id="c1",outcome="error"}'] == 1
    assert snap['dkg_party_quarantined_total{ceremony_id="c1"}'] == 1
    # prometheus text for the labelled registry still parses line-wise
    reg2 = MetricsRegistry()
    observe_party_result(res, registry=reg2)  # no id -> legacy series
    assert "dkg_party_quarantined_total" in reg2.snapshot()["counters"]


def test_registry_is_thread_safe():
    reg = MetricsRegistry()

    def hammer():
        for _ in range(500):
            reg.inc("n_total")
            reg.observe("v_seconds", 0.01)

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = reg.snapshot()
    assert snap["counters"]["n_total"] == 4000
    assert snap["histograms"]["v_seconds"]["count"] == 4000


def test_exposition_consistent_under_concurrent_observe():
    """Every exposition rendered mid-hammer must be internally
    consistent: a histogram's +Inf bucket, its _count sample, and the
    snapshot's count must all describe the same set of observations.
    The regression this guards: prometheus_text() reading the live
    mutable bucket lists after releasing the lock, so one row rendered
    pre-observe and the totals post-observe."""
    import re

    reg = MetricsRegistry()
    stop = threading.Event()

    def pound(lane: str):
        i = 0
        while not stop.is_set():
            reg.observe("h_seconds", (i % 7) * 0.01, lane=lane)
            reg.inc("h_total", lane=lane)
            i += 1

    threads = [
        threading.Thread(target=pound, args=(str(k),)) for k in range(4)
    ]
    for t in threads:
        t.start()
    bucket_re = re.compile(
        r'^h_seconds_bucket\{lane="(\d)",le="\+Inf"\} (\d+)$'
    )
    count_re = re.compile(r'^h_seconds_count\{lane="(\d)"\} (\d+)$')
    try:
        for _ in range(300):
            # JSON snapshot: cumulative +Inf bucket == count, always
            for series, h in reg.snapshot()["histograms"].items():
                assert h["buckets"]["+Inf"] == h["count"], series
            # text exposition: the +Inf row and the _count row of each
            # lane must agree within one rendering
            inf, cnt = {}, {}
            for line in reg.prometheus_text().splitlines():
                m = bucket_re.match(line)
                if m:
                    inf[m.group(1)] = int(m.group(2))
                m = count_re.match(line)
                if m:
                    cnt[m.group(1)] = int(m.group(2))
            assert inf == cnt
    finally:
        stop.set()
        for t in threads:
            t.join()
