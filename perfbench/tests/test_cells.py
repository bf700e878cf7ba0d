"""Name -> file resolution, the agreement of BENCHMARK.json with the files it
names, and the requirement that a cell, a per-layer metric and a driver can
be ADDED as new files without editing one that exists."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from perfbench import cells

ROOT = cells.BENCH_DIR.parent
BENCH = cells.load_benchmark()


def test_every_cell_resolves_to_files_that_exist():
    for entry in BENCH["workloads"]:
        cell = cells.resolve(entry["name"])
        assert cell.name == f"{cell.config_name}.{cell.traffic_name}"
        assert cell.chips == entry["chips"]
        assert cell.config["name"] == cell.config_name
        for kind, name in (("drivers", cell.traffic["driver"]),
                           ("builders", cell.config["builder"]),
                           ("reference", cell.config["builder"])):
            assert (cells.BENCH_DIR / kind / f"{name}.py").is_file()
        assert [m["name"] for m in cell.end_to_end] == [
            m["name"] for m in BENCH["end_to_end"]]


def test_configs_agree_with_their_files():
    for declared in BENCH["configs"]:
        with open(ROOT / declared["file"], encoding="utf-8") as f:
            on_file = json.load(f)
        assert on_file["source"] == declared["source"]
        assert on_file["reduced"] == declared["reduced"] == []
        assert any(w["config"] == declared["name"] for w in BENCH["workloads"])


def test_every_per_layer_metric_is_a_reader_that_declares_the_same():
    for metric in BENCH["per_layer"]:
        reader = cells.load_plugin("layer_metrics", metric["name"])
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            metric["layer"], metric["unit"], metric["moves"], metric["source"])
        assert callable(reader.reduce)
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    on_disk = {p.stem for p in (cells.BENCH_DIR / "layer_metrics").glob("*.py")}
    assert on_disk == {m["name"] for m in BENCH["per_layer"]}


def test_contract_limits_of_the_benchmark_file():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for entry in BENCH["workloads"] + BENCH["configs"]:
        assert len(entry["why"]) <= 200, entry["name"]
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert "setup_s" in names
    for metric in BENCH["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    four_chip = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four_chip) <= max(1, len(BENCH["workloads"]) // 4)
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024


@pytest.mark.parametrize("workload, message", [
    ("no-such.cell", "unknown workload"),
    ("../etc.passwd", "unknown workload"),
])
def test_unknown_names_are_refused(workload, message):
    with pytest.raises(cells.CellError, match=message):
        cells.resolve(workload)
    with pytest.raises(cells.CellError):
        cells.load_plugin("layer_metrics", "no_such_metric")
    with pytest.raises(cells.CellError, match="plain name"):
        cells.load_plugin("layer_metrics", "../run")


def test_a_metric_with_a_workloads_key_reaches_only_those_cells(tmp_path):
    bench_dir = copy_benchmark(tmp_path)
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["per_layer"][0]["workloads"] = ["bert-large.squad384-dp4"]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    name = bench["per_layer"][0]["name"]
    has = cells.resolve("bert-large.squad384-dp4", bench_dir).per_layer
    has_not = cells.resolve("bert-large.squad384-dp1", bench_dir).per_layer
    assert name in [m["name"] for m in has]
    assert name not in [m["name"] for m in has_not]


# ---------------------------------------------------------------------------
# adding a cell, a per-layer metric and a driver purely as new files
# ---------------------------------------------------------------------------


def copy_benchmark(tmp_path: Path) -> Path:
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(cells.BENCH_DIR, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    return tmp_path / "perfbench"


NEW_DRIVER = '''
"""A new kind of run, added as a file: it measures nothing and says so."""
import json
from perfbench import cells

def run(cell, args, t0):
    values = {"setup_s": 0.5, "echo_widgets": float(cell.traffic["widgets"])}
    reader = cells.load_plugin("layer_metrics", "widget_count", cell.bench_dir)
    values["widget_count"] = reader.reduce(cell.traffic)
    declared = cell.per_layer if args.trace else cell.end_to_end
    return {"correct": True, "attempted": 1, "failed": 0,
            "metrics": {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                        for m in declared if m["name"] in values},
            "device": {"platform": "cpu", "kind": "cpu", "count": 1,
                       "memory_peak_bytes": 0}}
'''
NEW_READER = '''
LAYER = "widgets"
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"

def reduce(traffic):
    return traffic["widgets"]
'''


def snapshot(directory: Path) -> dict:
    return {p.relative_to(directory): p.read_bytes()
            for p in directory.rglob("*") if p.is_file()}


def test_a_cell_a_metric_and_a_driver_are_added_as_new_files_only(tmp_path):
    bench_dir = copy_benchmark(tmp_path)
    before = snapshot(bench_dir)

    # new files ...
    (bench_dir / "configs" / "widget-model.json").write_text(json.dumps(
        {"name": "widget-model", "source": "https://example.org/widget",
         "builder": "none", "reduced": []}))
    (bench_dir / "traffic" / "echo.json").write_text(json.dumps(
        {"driver": "echo", "widgets": 7}))
    (bench_dir / "drivers" / "echo.py").write_text(NEW_DRIVER)
    (bench_dir / "layer_metrics" / "widget_count.py").write_text(NEW_READER)
    # ... and one entry each in BENCHMARK.json
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "widget-model", "source": "https://example.org/widget",
        "file": "perfbench/configs/widget-model.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "widget-model.echo", "config": "widget-model",
        "traffic": "echo", "chips": 1, "why": "test"})
    bench["per_layer"].append({
        "name": "widget_count", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "widgets", "moves": "setup_s",
        "workloads": ["widget-model.echo"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # no file that existed was touched
    after = snapshot(bench_dir)
    assert {k: v for k, v in after.items() if k in before} == before

    cell = cells.resolve("widget-model.echo", bench_dir)
    assert "widget_count" in [m["name"] for m in cell.per_layer]
    # the new metric does not leak into the cells that were there
    old = cells.resolve("bert-large.squad384-dp1", bench_dir)
    assert "widget_count" not in [m["name"] for m in old.per_layer]

    # and the one command runs it; the program under test comes from here
    env = dict(os.environ, PYTHONPATH=str(ROOT))
    for trace, expect in ((0, {"setup_s": 0.5}), (1, {"widget_count": 7})):
        done = subprocess.run(
            [sys.executable, str(bench_dir / "run.py"), "--workload",
             "widget-model.echo", "--seed", "1", "--seconds", "1", "--trace",
             str(trace)],
            capture_output=True, text=True, env=env, timeout=300)
        assert done.returncode == 0, done.stderr
        result = json.loads(done.stdout.strip().splitlines()[-1])
        assert {k: v["value"] for k, v in result["metrics"].items()} == expect
