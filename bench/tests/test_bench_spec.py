"""BENCHMARK.json against the benchmark's contract, and every file it
names found by name."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    SPEC = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]


def test_names_units_and_bounds():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [x["name"] for x in SPEC["configs"] + SPEC["workloads"]
             + metrics]
    assert all(NAME.match(n) for n in names)
    assert len({m["name"] for m in metrics}) == len(metrics)
    assert all(UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
               for m in metrics)
    assert "setup_s" in {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for w in SPEC["workloads"] + SPEC["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


def test_run_seconds_fits_a_full_check_of_24_cells():
    s = SPEC["run_seconds"]
    assert 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    spec, work, conf, c = harness.load_cell(cell)
    assert conf["name"] == work["config"]
    assert set(c["limits"]) == {"loss_gap", "grad_gap", "change_gap",
                                "foreign_rows", "repeated_batches",
                                "latent_sigmas"}
    for part in ("systems", "reference", "flops"):
        importlib.import_module(f"bench.{part}.{conf['family']}")
    entry = {x["name"]: x for x in SPEC["configs"]}[work["config"]]
    assert entry["file"].startswith("bench/")
    assert entry["reduced"] == conf["reduced"]
    from bench.systems import dcgan
    dcgan.program_config(conf, c)     # the program runs the file's sizes


def test_every_config_is_used_and_its_file_is_its_own():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(set(files)) == len(files)
    assert {c["name"] for c in SPEC["configs"]} == {
        w["config"] for w in SPEC["workloads"]}


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_reader_and_cells(metric):
    m = {x["name"]: x for x in SPEC["per_layer"]}[metric]
    assert callable(harness.load_metric(metric).read)
    assert m["moves"] == "samples_per_s"
    for cell in m["workloads"]:
        assert cell in CELLS
        reported = {x["name"] for x in harness.cell_metrics(SPEC, cell,
                                                            "end_to_end")}
        assert {"samples_per_s", "setup_s"} <= reported


def test_every_per_layer_metric_lists_its_cells():
    for m in SPEC["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= set(CELLS), m


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    ends = {m["name"] for m in harness.cell_metrics(SPEC, cell, "end_to_end")}
    assert "setup_s" in ends and len(ends) >= 2
    assert harness.cell_metrics(SPEC, cell, "per_layer")


def _run_py(cwd):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_nonzero_with_no_result_without_a_tpu():
    p = _run_py(ROOT)
    assert p.returncode != 0
    assert "{" not in p.stdout


def test_run_fails_with_only_the_benchmarks_files(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
