"""The harness finds configurations, traffic mixes and metric readers by
the names in BENCHMARK.json, so a cell is added by adding files."""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import spec  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = spec.find_cell(bench, w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert cell.traffic["job"] in ("sweep", "detect", "harden")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.reader(m["name"]))


def test_metric_routing(bench):
    det = spec.find_cell(bench, "legacy.detect", ROOT)
    assert {m["name"] for m in det.end_to_end} == {"detect_records_per_s",
                                                  "setup_s"}
    assert {m["name"] for m in det.per_layer} == {
        "device_idle.detect", "sample_ms.detect", "ingest_ms.detect",
        "tables_ms.detect", "verdicts_ms.detect"}


def test_every_config_file_and_metric_is_used(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith("bench/")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))


def test_new_files_add_a_cell_without_editing_any(bench, tmp_path):
    """A new cell, traffic mix and metric come from new files plus new
    entries; no existing file changes."""
    traffic_dir = tmp_path / "traffic"
    shutil.copytree(os.path.join(BENCH, "traffic"), traffic_dir)
    before = {p.name: p.read_bytes() for p in traffic_dir.iterdir()}
    mix = json.loads((traffic_dir / "sweep-64k.json").read_text())
    mix["scenarios_per_call"] = 4096
    (traffic_dir / "sweep-4k.json").write_text(json.dumps(mix))
    metrics_dir = tmp_path / "metrics"
    metrics_dir.mkdir()
    (metrics_dir / "calls_done.sweep.py").write_text(
        "def read(ctx):\n    return float(len(ctx.calls))\n")

    extended = json.loads(json.dumps(bench))
    extended["workloads"].append({
        "name": "hardened.sweep-4k", "config": "uber-paper-hardened",
        "traffic": "sweep-4k", "chips": 1, "why": "one chunk"})
    for m in extended["end_to_end"]:
        if "workloads" in m and "hardened.sweep-64k" in m["workloads"]:
            m["workloads"].append("hardened.sweep-4k")
    extended["per_layer"].append({
        "name": "calls_done.sweep", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "device",
        "moves": "sweep_scen_per_s", "workloads": ["hardened.sweep-4k"]})

    cell = spec.find_cell(extended, "hardened.sweep-4k", ROOT,
                          traffic_dir=str(traffic_dir))
    assert cell.traffic["scenarios_per_call"] == 4096
    assert "sweep_scen_per_s" in {m["name"] for m in cell.end_to_end}
    assert [m["name"] for m in cell.per_layer] == ["calls_done.sweep"]
    read = spec.reader("calls_done.sweep", str(metrics_dir))

    class Ctx:
        calls = [(0.0, 1.0, 1.0)] * 3
    assert read(Ctx()) == 3.0
    assert {p.name: p.read_bytes() for p in traffic_dir.iterdir()
            if p.name in before} == before


def test_unknown_cell_is_refused(bench):
    with pytest.raises(KeyError):
        spec.find_cell(bench, "no.such-cell", ROOT)
