"""`BENCHMARK.json` against the files it names and the characters it may use."""

import json
import re

import pytest

import bench_support

run = bench_support.bench_run()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module", params=["BENCHMARK.json", "tests/benchmark/data/manifest.json"])
def loaded(request):
    return run.load_manifest(bench_support.ROOT / request.param)


def test_names_units_and_references(loaded):
    manifest, _ = loaded
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"] for c in manifest["configs"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for entry in manifest["workloads"] + manifest["configs"] + manifest["end_to_end"] + manifest["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
        assert len(entry.get("why", "x")) <= 200 and "\n" not in entry.get("why", "")
    for w in manifest["workloads"]:
        assert w["config"] in configs and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert {w["config"] for w in manifest["workloads"]} == configs
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in manifest["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m


def test_every_named_file_exists_and_every_cell_reports(loaded):
    manifest, roots = loaded
    for c in manifest["configs"]:
        assert (bench_support.ROOT / c["file"]).is_file()
        assert json.loads((bench_support.ROOT / c["file"]).read_text())["name"] == c["name"]
    for w in manifest["workloads"]:
        cell = json.loads(run.find(roots, "workloads", w["name"], ".json").read_text())
        assert cell["config"] == w["config"]
        assert hasattr(run.load_module(run.find(roots, "traffic", cell["traffic"]["kind"], ".py")), "plan")
        assert run.find(roots, "configs", w["config"], ".json").is_file()
        e2e = [m["name"] for m in run.metrics_for(manifest, "end_to_end", w["name"])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metrics_for(manifest, "per_layer", w["name"])
    for folder, group in (("end_to_end", "end_to_end"), ("layer_metrics", "per_layer")):
        for m in manifest[group]:
            assert callable(run.load_module(run.find(roots, folder, m["name"], ".py")).read)


def test_a_missing_file_is_an_error_not_a_default():
    _, roots = run.load_manifest(bench_support.MANIFEST)
    with pytest.raises(FileNotFoundError):
        run.find(roots, "workloads", "no_such_cell", ".json")
