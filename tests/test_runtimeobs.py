"""Runtime introspection layer: listeners, probes, scrape surface, SLOs.

Four surfaces under test:

* ``utils/runtimeobs.py`` — install() gating/idempotence, the
  exactly-once compile accounting (a jitted function compiles once and
  every later call is a cache hit, and the counter must say so), the
  cost probe, and snapshot() surviving a registry reset;
* ``service/httpobs.py`` — /metrics, /healthz, /slo and the error
  paths (404 unknown route, 503 unhealthy, 500 broken probe counted in
  ``service_http_errors_total``), against both a bare server and a real
  scheduler (engine monkeypatched out, port 0, sub-second);
* ``service/slo.py`` — quantile/merge/delta/burn math against
  hand-computed fixtures, and the rolling evaluator's windowed delta
  under a fake clock;
* the redaction contract — ceremony master bytes must never transit
  the HTTP surface (same stance as tests/test_obslog.py's grep).
"""

from __future__ import annotations

import json
import secrets
import urllib.error
import urllib.request

import pytest

from dkg_tpu.service import scheduler as scheduler_mod
from dkg_tpu.service import slo as slo_mod
from dkg_tpu.service.engine import CeremonyOutcome, CeremonyRequest
from dkg_tpu.service.httpobs import ObsHttpServer
from dkg_tpu.service.scheduler import CeremonyScheduler
from dkg_tpu.utils import obslog, runtimeobs
from dkg_tpu.utils.metrics import MetricsRegistry

CURVE = "ristretto255"


# -- runtimeobs: gating, idempotence, compile accounting --------------------


def test_install_gating(monkeypatch):
    # an earlier file of this process may have left the listeners on (the
    # serial tier-1 order does: this test failed there at PR 25 too)
    runtimeobs.uninstall()
    try:
        # unset: implicit installers (the scheduler) stay off
        monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
        assert runtimeobs.install() is False
        assert not runtimeobs.enabled()
        # unset + force: benches opt in
        assert runtimeobs.install(force=True) is True
        assert runtimeobs.enabled()
        runtimeobs.uninstall()
        # off: the operator kill-switch wins even over force
        monkeypatch.setenv("DKG_TPU_RUNTIMEOBS", "off")
        assert runtimeobs.install(force=True) is False
        assert not runtimeobs.enabled()
        # on: implicit installers light up
        monkeypatch.setenv("DKG_TPU_RUNTIMEOBS", "on")
        assert runtimeobs.install() is True
        assert runtimeobs.enabled()
        # junk value: loud failure, never a silent default
        monkeypatch.setenv("DKG_TPU_RUNTIMEOBS", "maybe")
        with pytest.raises(ValueError):
            runtimeobs.install()
    finally:
        runtimeobs._reset_for_tests()


def test_install_idempotent(monkeypatch):
    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    try:
        assert runtimeobs.install(force=True) is True
        assert runtimeobs._STATE.listeners_registered
        # repeat installs just retarget/re-enable — never re-register
        # (jax.monitoring has no unregister; doubling listeners would
        # double-count every compile)
        assert runtimeobs.install(force=True) is True
        assert runtimeobs.install(force=True) is True
        assert runtimeobs.enabled()
        runtimeobs.uninstall()
        assert not runtimeobs.enabled()
        # uninstall is a flag flip: listeners stay registered
        assert runtimeobs._STATE.listeners_registered
    finally:
        runtimeobs._reset_for_tests()


def test_jit_compile_counted_exactly_once(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    reg = MetricsRegistry()
    log = obslog.ObsLog()
    # warm the inputs BEFORE install: jnp.arange itself compiles a tiny
    # iota program which must not pollute the count under test
    x = jnp.arange(8, dtype=jnp.int32)
    jax.block_until_ready(x)
    # a fresh salt makes the program unique per run, so a stray
    # persistent compilation cache can never swallow the compile
    salt = secrets.randbits(31) | 1
    try:
        assert runtimeobs.install(registry=reg, log=log, force=True)
        f = jax.jit(lambda v: v * salt + 1)
        jax.block_until_ready(f(x))
        first = reg.snapshot()["counters"].get("jax_compiles_total", 0)
        jax.block_until_ready(f(x))  # in-memory executable cache hit
        snap = reg.snapshot()
        runtime = runtimeobs.snapshot()
        # the runtime block must survive a registry reset (fleet_bench
        # resets REGISTRY between legs but reports one runtime block)
        reg.reset()
        after_reset = runtimeobs.snapshot()
    finally:
        runtimeobs._reset_for_tests()

    assert first == 1
    assert snap["counters"]["jax_compiles_total"] == 1
    stage_hist = [
        s for s in snap["histograms"] if s.startswith("jax_compile_seconds")
    ]
    assert any('stage="backend_compile"' in s for s in stage_hist)
    assert runtime["enabled"] and runtime["compiles_total"] == 1
    assert runtime["compile_seconds_sum"] > 0
    assert after_reset["compiles_total"] == 1
    kinds = [e["kind"] for e in log.events()]
    assert "jax_compile" in kinds
    stages = [
        e.get("stage") for e in log.events() if e["kind"] == "jax_compile"
    ]
    assert "backend_compile" in stages


def test_probe_jitted_records_costs(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    reg = MetricsRegistry()
    x = jnp.arange(16, dtype=jnp.float32)
    try:
        # probes work even with telemetry disabled (benches probe
        # unconditionally); only the registry target needs passing
        f = jax.jit(lambda v: (v * 2.0).sum())
        info = runtimeobs.probe_jitted("toy_sum", f, x, registry=reg)
        assert info is not None
        assert info["name"] == "toy_sum"
        assert len(info["fingerprint"]) == 12  # blake2b digest_size=6
        assert any("float32[16]" in s for s in info["in_shapes"])
        if "flops" in info:  # cost model presence varies per backend
            gauges = reg.snapshot()["gauges"]
            assert (
                gauges['jax_executable_flops{executable="toy_sum"}']
                == info["flops"]
            )
        assert runtimeobs.snapshot()["executables"]["toy_sum"] == info
        # a non-jitted callable has no .lower: probe returns None,
        # never raises (a probe must not fail the bench it rides in)
        assert runtimeobs.probe_jitted("bad", lambda v: v, x) is None
    finally:
        runtimeobs._reset_for_tests()


def test_sample_memory_sets_gauges(monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    reg = MetricsRegistry()
    keep = jnp.ones((128,), dtype=jnp.float32)  # a live buffer to count
    jax.block_until_ready(keep)
    try:
        assert runtimeobs.install(registry=reg, force=True)
        runtimeobs.sample_memory()
        gauges = reg.snapshot()["gauges"]
        # CPU has no allocator stats: the live-buffer fallback must
        # still produce a non-zero footprint for the array held above
        live = [
            v for s, v in gauges.items() if s.startswith("jax_live_buffer_bytes")
        ]
        assert live and live[0] >= keep.nbytes
    finally:
        runtimeobs._reset_for_tests()
    del keep


# -- SLO math against hand-computed fixtures --------------------------------


def test_quantile_hand_computed():
    h = {
        "buckets": {"1.0": 50, "2.5": 90, "5.0": 100, "+Inf": 100},
        "sum": 150.0,
        "count": 100,
    }
    # rank 50 closes exactly at the 1.0 bucket (frac 1.0)
    assert slo_mod.quantile(h, 0.50) == pytest.approx(1.0)
    # rank 99 lands 9/10 into (2.5, 5.0]: 2.5 + 2.5 * 0.9
    assert slo_mod.quantile(h, 0.99) == pytest.approx(4.75)
    # every observation overflowed: the largest finite bound is the
    # honest answer a fixed-layout histogram can give
    over = {"buckets": {"1.0": 0, "+Inf": 10}, "sum": 99.0, "count": 10}
    assert slo_mod.quantile(over, 0.99) == pytest.approx(1.0)
    assert slo_mod.quantile({"buckets": {}, "sum": 0, "count": 0}, 0.5) is None


def test_merge_histograms_across_labels():
    reg = MetricsRegistry()
    reg.observe("service_ceremony_seconds", 0.8, bucket="16x5")
    reg.observe("service_ceremony_seconds", 2.0, bucket="32x8")
    reg.observe("service_ceremony_seconds", 2.0, bucket="32x8")
    snap = reg.snapshot()
    merged = slo_mod.merge_histograms(snap, "service_ceremony_seconds")
    assert merged["count"] == 3
    assert merged["sum"] == pytest.approx(4.8)
    assert merged["buckets"]["1"] == 1  # only the 0.8s observation
    assert merged["buckets"]["+Inf"] == 3
    assert slo_mod.merge_histograms(snap, "absent_seconds") is None


def test_evaluate_burn_and_violations():
    reg = MetricsRegistry()
    for _ in range(98):
        reg.inc("service_completed_total", status="done")
    reg.inc("service_completed_total", 2, status="poisoned")
    reg.observe("service_ceremony_seconds", 0.4, bucket="16x5")
    snap = reg.snapshot()
    rep = slo_mod.evaluate(snap, slo_mod.SloPolicy(error_budget=0.01))
    # 2 failures / 100 completions = ratio 0.02 → burn 2x the budget
    assert rep["errors"]["completed"] == 100
    assert rep["errors"]["failed"] == 2
    assert rep["errors"]["ratio"] == pytest.approx(0.02)
    assert rep["errors"]["burn"] == pytest.approx(2.0)
    assert rep["errors"]["by_status"] == {"done": 98.0, "poisoned": 2.0}
    assert not rep["ok"] and len(rep["violations"]) == 1
    # a latency objective turns the ceremony leg into a second violation
    tight = slo_mod.evaluate(
        snap, slo_mod.SloPolicy(error_budget=0.05, ceremony_p99_s=0.1)
    )
    assert tight["errors"]["ok"]  # 0.02 <= 0.05
    assert not tight["ceremony"]["ok"]
    assert len(tight["violations"]) == 1
    # absent series report null and never violate (fresh server)
    empty = slo_mod.evaluate(MetricsRegistry().snapshot(), slo_mod.SloPolicy())
    assert empty["ceremony"] is None and empty["sign"] is None
    assert empty["ok"]


def test_evaluator_windowed_delta_fake_clock():
    reg = MetricsRegistry()
    now = {"t": 0.0}
    ev = slo_mod.SloEvaluator(
        registry=reg,
        policy=slo_mod.SloPolicy(window_s=100.0),
        clock=lambda: now["t"],
    )
    reg.inc("service_completed_total", 50, status="done")
    reg.inc("service_completed_total", 50, status="poisoned")  # old sins
    ev.tick()
    now["t"] = 60.0
    reg.inc("service_completed_total", 30, status="done")
    rep = ev.report()
    # the window sees only the delta: 30 clean completions, the old
    # 50/50 disaster is outside the judgment
    assert rep["window_s"] == pytest.approx(60.0)
    assert rep["errors"]["completed"] == 30
    assert rep["errors"]["failed"] == 0
    assert rep["ok"]
    # push the base out of the window: cumulative fallback judges all
    now["t"] = 1000.0
    rep2 = ev.report()
    assert rep2["errors"]["completed"] == 130


# -- HTTP scrape surface ----------------------------------------------------


def _get(port: int, path: str):
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    )


def test_httpobs_routes_direct():
    reg = MetricsRegistry()
    reg.inc("service_submitted_total", 3)
    state = {"ok": True}
    srv = ObsHttpServer(
        registry=reg,
        health_fn=lambda: {"ok": state["ok"], "workers_alive": 1},
        slo_fn=None,
        port=0,
    )
    try:
        text = _get(srv.port, "/metrics").read().decode()
        assert "# TYPE service_submitted_total counter" in text
        assert "service_submitted_total 3" in text
        health = json.load(_get(srv.port, "/healthz"))
        assert health["ok"]
        state["ok"] = False  # unhealthy flips the status code to 503
        with pytest.raises(urllib.error.HTTPError) as e503:
            _get(srv.port, "/healthz")
        assert e503.value.code == 503
        assert json.load(e503.value)["ok"] is False
        with pytest.raises(urllib.error.HTTPError) as e404:
            _get(srv.port, "/slo")  # no slo_fn wired
        assert e404.value.code == 404
        with pytest.raises(urllib.error.HTTPError) as e404b:
            _get(srv.port, "/favicon.ico")
        assert e404b.value.code == 404
    finally:
        srv.close()


def test_httpobs_broken_probe_counted_not_fatal():
    reg = MetricsRegistry()

    def boom():
        raise RuntimeError("probe exploded")

    srv = ObsHttpServer(registry=reg, health_fn=boom, slo_fn=None, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as e500:
            _get(srv.port, "/healthz")
        assert e500.value.code == 500
        assert json.load(e500.value) == {"error": "RuntimeError"}
        snap = reg.snapshot()["counters"]
        assert snap['service_http_errors_total{path="/healthz"}'] == 1
        # the serve thread survived: the next request still answers
        assert _get(srv.port, "/metrics").status == 200
    finally:
        srv.close()


# -- scheduler integration (engine monkeypatched out, no JAX work) ----------


class _FakeEngine:
    def start(self, runtime, reqs, ids=None):
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
    fake = _FakeEngine()
    monkeypatch.setattr(scheduler_mod, "start_convoy", fake.start)
    monkeypatch.setattr(scheduler_mod, "finish_convoy", fake.finish)
    return fake


def test_scheduler_serves_scrape_surface(fake_engine, monkeypatch):
    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    monkeypatch.delenv("DKG_TPU_SERVICE_HTTP_PORT", raising=False)
    reg = MetricsRegistry()
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=4, batch_max=1, runtime=object(),
        metrics=reg, http_port=0,
    )
    try:
        port = sch._http.port
        cid = sch.submit(CeremonyRequest(CURVE, 5, 2, seed=0))
        out = sch.result(cid, timeout=5)
        assert out.status == "done"

        health = json.load(_get(port, "/healthz"))
        assert health["ok"]
        assert health["running"] and not health["draining"]
        assert health["workers_alive"] >= 1
        assert health["wal"] == "off"

        slo_rep = json.load(_get(port, "/slo"))
        assert slo_rep["ok"]
        assert slo_rep["errors"]["completed"] >= 1
        assert slo_rep["errors"]["failed"] == 0

        text = _get(port, "/metrics").read().decode()
        assert 'service_completed_total{status="done"} 1' in text
        assert "service_ceremony_seconds_bucket" in text

        # redaction: the ceremony master secret must never transit the
        # scrape surface (same contract test_obslog.py greps for logs)
        secret = out.master.decode()
        for payload in (text, json.dumps(health), json.dumps(slo_rep)):
            assert secret not in payload
    finally:
        sch.close()
    # close() tears the server down with the scheduler
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        _get(port, "/healthz")


def test_scheduler_http_off_by_default(fake_engine, monkeypatch):
    monkeypatch.delenv("DKG_TPU_RUNTIMEOBS", raising=False)
    monkeypatch.delenv("DKG_TPU_SERVICE_HTTP_PORT", raising=False)
    # its own registry: the process-wide one carries whatever failures
    # the files this worker ran before it recorded on purpose, and the
    # SLO's error budget would judge those
    sch = CeremonyScheduler(
        concurrency=1, queue_depth=4, batch_max=1, runtime=object(),
        metrics=MetricsRegistry(),
    )
    try:
        assert sch._http is None
        assert sch.health()["ok"]  # the dict is served locally regardless
        assert sch.slo_report()["ok"]
    finally:
        sch.close()
