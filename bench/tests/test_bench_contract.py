"""BENCHMARK.json is well formed and every name in it finds its files;
the command refuses to run without a TPU or without the program."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import harness

ROOT = harness.ROOT
BENCH = harness.BENCH
B = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(B) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert B["command"] == ["python3", "bench/run.py"]
    assert B["paths"] == ["bench"]
    assert 1 <= B["run_seconds"] <= 51
    cells = len(B["workloads"])
    check_s = (2 + 14 * 24) * (B["run_seconds"] + 60) + 24 * 180 + 1200
    assert check_s <= 43200 and cells <= 24


def test_names_and_one_line_fields():
    names = ([c["name"] for c in B["configs"]]
             + [w["name"] for w in B["workloads"]]
             + [m["name"] for m in B["end_to_end"] + B["per_layer"]])
    assert len(names) == len(set(names))
    for n in names + [w["traffic"] for w in B["workloads"]]:
        assert NAME.match(n), n
    for entry in B["configs"] + B["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for c in B["configs"]:
        assert 1 <= len(c["source"]) <= 200


def test_configs():
    used = {w["config"] for w in B["workloads"]}
    files = set()
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["name"] in used and c["file"].startswith("bench/")
        assert c["file"] not in files
        files.add(c["file"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["n_edges"] == 2 * cfg["n_undirected_edges"]


def test_cells_find_their_files():
    pairs = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] == 1
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        traffic = json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{traffic['driver']}.py").is_file()


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e_by_cell = {c: harness.cell_metrics(B, c) for c in cells}
    for m in B["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
    for m in B["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
        for cell in m["workloads"]:
            e2e, _ = e2e_by_cell[cell]
            assert m["moves"] in {x["name"] for x in e2e}, (m, cell)
    for cell, (e2e, per_layer) in e2e_by_cell.items():
        assert "setup_s" in {x["name"] for x in e2e} and len(e2e) >= 2
        assert per_layer, cell


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "cora.infer",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    proc = _run(ROOT, {})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    proc = _run(tmp_path, {})
    assert proc.returncode != 0 and proc.stdout.strip() == ""
