"""BENCHMARK.json resolves to its files, keeps its contract, and grows by
adding files only."""
from __future__ import annotations

import json
import math
import re
import shutil

import pytest

from bench import harness

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(BENCH, cell)
    assert c.driver.Driver
    assert c.end_to_end and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(m["name"]))
    assert "limits" in c.config and c.config["limits"]["max_rel_err"] > 0


@pytest.mark.parametrize("metric", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_moves_a_metric_its_cells_report(metric):
    moved = {m["name"]: m for m in BENCH["end_to_end"]}[metric["moves"]]
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert harness.reports(moved, cell), (metric["name"], cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        e2e = {m["name"] for m in BENCH["end_to_end"] if harness.reports(m, cell)}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(harness.reports(m, cell) for m in BENCH["per_layer"])


def test_names_units_and_keys_keep_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_configs_name_every_cut_and_live_under_paths():
    for c in BENCH["configs"]:
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert len(c["source"]) <= 200 and len(c["reduced"]) <= 16
        with open(harness.ROOT / c["file"]) as f:
            cfg = json.load(f)
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_each_configuration_carries_its_cpu_test_sizes(config):
    """CPU tests run a cell at its configuration's ``tiny`` sizes: a
    configuration without them would run at full size in tier-1."""
    with open(harness.ROOT / config["file"]) as f:
        cfg = json.load(f)
    tiny = cfg["tiny"]
    assert "k" in tiny
    for key, value in tiny.items():
        assert key in cfg and key != "tiny", key
        assert 0 < value <= cfg[key], key


def test_command_and_run_length_fit_the_check():
    cmd = BENCH["command"]
    assert cmd[1].startswith("bench/") and (harness.ROOT / cmd[1]).is_file()
    t = BENCH["run_seconds"]
    assert 1 <= t <= 51
    # A full check of 24 cells: 2 + 14 runs a cell, each run_seconds + 60 s,
    # 2 x 90 s of compile a cell, 1200 s spare, within 43200 s.
    assert (2 + 14 * 24) * (t + 60) + 24 * 180 + 1200 <= 43200


def test_a_new_cell_is_files_plus_an_entry(tmp_path):
    """A configuration, a traffic mix and a per-layer metric added as new
    files, and the cell as a new entry: nothing existing is edited."""
    shutil.copytree(harness.ROOT / "bench", tmp_path / "bench")
    bench = json.loads(json.dumps(BENCH))
    cfg = json.loads((harness.ROOT / "bench/configs/hpcg-48.json").read_text())
    cfg.update(nx=40, ny=40, nz=40)
    (tmp_path / "bench/configs/hpcg-40.json").write_text(json.dumps(cfg))
    (tmp_path / "bench/traffic/cg_long.json").write_text(json.dumps(
        {"driver": "cg", "iterations_per_set": 200, "rhs_pool": 2, "check_sample": 4}))
    (tmp_path / "bench/metrics/iterations.cg.py").write_text(
        "def read(run):\n    return float(run.iterations) or None\n")
    bench["configs"].append({"name": "hpcg-40", "source": "HPCG", "reduced": ["nx", "ny", "nz"],
                             "file": "bench/configs/hpcg-40.json", "why": "smaller grid"})
    bench["workloads"].append({"name": "hpcg-40.cg_long", "config": "hpcg-40",
                               "traffic": "cg_long", "chips": 1, "why": "long sets"})
    bench["per_layer"].append({"name": "iterations.cg", "unit": "iter", "better": "higher",
                               "source": "host_clock", "layer": "solver",
                               "moves": "cg_iter_ms", "workloads": ["hpcg-40.cg_long"]})
    bench["end_to_end"][1]["workloads"].append("hpcg-40.cg_long")
    cell = harness.resolve(bench, "hpcg-40.cg_long", root=tmp_path)
    assert cell.config["nx"] == 40 and cell.traffic["iterations_per_set"] == 200
    assert [m["name"] for m in cell.per_layer] == ["iterations.cg"]
    run = harness.Run(cell=cell, seed=1, seconds=1.0, iterations=7)
    assert harness.reader("iterations.cg", tmp_path)(run) == 7.0
    assert harness.reader("cg_iter_ms", tmp_path)(run) is not None


def test_missing_metric_file_is_an_error(tmp_path):
    with pytest.raises(FileNotFoundError):
        harness.reader("no_such_metric", tmp_path)(None)


def test_reader_that_finds_nothing_returns_none():
    run = harness.Run(cell=None, seed=1, seconds=1.0)
    for m in BENCH["per_layer"]:
        assert harness.reader(m["name"])(run) is None, m["name"]
    assert math.isclose(harness.reader("setup_s")(run), 0.0)
