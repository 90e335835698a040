"""Both traffic kinds: the same seed gives the same requests, every seed the
same work in another order, and the open loop's latency runs from due times."""

import collections
import itertools
import json

import pytest

import bench_support

run = bench_support.bench_run()
REDUCED = json.loads((bench_support.ROOT / "benchmark/configs/secp256k1_fleet_mix.json").read_text())
MIX = dict(REDUCED, mix=REDUCED["source_mix"])  # the source's five shapes, which a later cell will send
BIG_SEED = 2**31 + 12345


def _plan(kind, params, seed, seconds=20.0, take=250):
    module = run.load_module(bench_support.ROOT / "benchmark" / "traffic" / f"{kind}.py")
    plan = module.plan(params, MIX, seed, seconds)
    return plan, list(itertools.islice(plan["requests"], take))


@pytest.mark.parametrize(
    "kind,params",
    [("closed_loop", {"outstanding": 64}), ("open_loop_poisson", {"rate_per_s": 12.5})],
)
def test_same_seed_same_requests_other_seed_same_work(kind, params):
    _, a = _plan(kind, params, BIG_SEED)
    _, b = _plan(kind, params, BIG_SEED)
    _, c = _plan(kind, params, BIG_SEED + 1)
    assert a == b and a != c
    shapes = lambda reqs: collections.Counter((r["n"], r["t"]) for _, r in reqs)  # noqa: E731
    assert shapes(a) == shapes(c)
    assert shapes(a)[(16, 5)] == 224 and shapes(a)[(64, 16)] == 2  # the rare shapes are not dropped
    assert len({r["seed"] for _, r in a}) == len(a)


def test_closed_loop_has_no_due_times_and_keeps_its_bound():
    plan, reqs = _plan("closed_loop", {"outstanding": 64}, 7)
    assert plan["outstanding"] == 64 and all(due is None for due, _ in reqs)


def test_open_loop_arrivals_fill_the_window_at_the_rate():
    plan, reqs = _plan("open_loop_poisson", {"rate_per_s": 12.5}, BIG_SEED, seconds=20.0, take=10**6)
    dues = [due for due, _ in reqs]
    assert plan["outstanding"] is None and len(dues) == 250
    assert dues == sorted(dues) and 0 < dues[0] and dues[-1] < 20.0
    gaps = sorted(b - a for a, b in zip([0.0] + dues, dues))
    _, other = _plan("open_loop_poisson", {"rate_per_s": 12.5}, 99, seconds=20.0, take=10**6)
    other_dues = [due for due, _ in other]
    assert gaps == pytest.approx(sorted(b - a for a, b in zip([0.0] + other_dues, other_dues)))
    assert gaps[len(gaps) // 2] == pytest.approx(0.6931 / 12.5, rel=0.05)  # exponential: median ln2/rate


def test_latency_runs_from_the_due_time_and_failures_count_as_worst():
    read = run.load_module(bench_support.ROOT / "benchmark/end_to_end/latency_p95_ms.py").read
    rec = lambda due, sent, fetched, status="done": {  # noqa: E731
        "due_s": due, "sent_s": sent, "fetched_s": fetched, "status": status, "ok": status == "done"
    }
    ctx = {"seconds": 10.0, "cell": {"drain_s": 5.0}}
    # sent half a second late: the wait still counts from when it was due
    ctx["records"] = [rec(1.0, 1.5, 2.0)] * 20
    assert read(ctx) == pytest.approx(1000.0)
    ctx["records"] = [rec(1.0, 1.0, 1.1)] * 18 + [rec(1.0, 1.0, None, "refused")] * 2
    assert read(ctx) == pytest.approx(15000.0)


def test_convoy_interval_runs_from_the_first_completion_to_the_last():
    read = run.load_module(bench_support.ROOT / "benchmark/layer_metrics/convoy_interval_ms.py").read
    rec = lambda engine_s, fetched: {"engine_s": engine_s, "fetched_s": fetched}  # noqa: E731
    # three convoys (equal engine seconds = one convoy), first fetched at 1.0, 1.3 and 1.9 s
    records = [rec(0.25, 1.0), rec(0.25, 1.001), rec(0.26, 1.3), rec(0.27, 1.91), rec(0.27, 1.9)]
    assert read({"records": records}) == pytest.approx(450.0)
    assert read({"records": records[:2]}) is None  # one convoy: nothing to read
