"""BENCHMARK.json against the benchmark's contract, and the files it
names; a run without a TPU fails and prints no result."""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import spec  # noqa: E402

ROOT = spec.ROOT
BENCH = spec.load_json(ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"][0] == "python3"
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()


def test_names_units_and_bounds():
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    names = ([m["name"] for m in metrics] + CELLS
             + [c["name"] for c in BENCH["configs"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}


def test_configs_are_files_under_paths_used_by_cells():
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert c["name"] in used
        assert c["file"].startswith("bench/")
        conf = spec.load_json(ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
        assert all(k in conf for k in c["reduced"])
        assert len(c["why"]) <= 200 and len(c["source"]) <= 200


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_loads_and_reports_what_it_must(cell):
    c = spec.load_cell(cell)
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    assert c.chips == 1
    assert c.mix["sampling"] == "greedy"
    for m in c.per_layer:
        assert m["moves"] in e2e
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.load_reader(m["name"]))


def test_per_layer_metrics_name_a_layer_and_one_reporting_metric():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["layer"] and "\n" not in m["layer"]
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in CELLS
            assert "workloads" not in moved or w in moved["workloads"]


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
