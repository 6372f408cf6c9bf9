"""BENCHMARK.json and the files it names: everything a cell needs is found
by name, and a run with no program or no accelerator prints no result."""
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import arch, common, metrics

BENCH = common.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43,200 seconds
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_and_units():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_each_cell_finds_its_files_and_metrics(w):
    work, conf, m, mix = common.cell(w["name"])
    assert (common.HERE / "traffic" / f"{w['traffic']}.json").is_file()
    assert set(conf["reduced"]) == set(m["reduced"])
    assert callable(common.runner(mix["kind"]).run)
    assert callable(arch.load(m).dims)
    e2e = [x["name"] for x in BENCH["end_to_end"]
           if w["name"] in x.get("workloads", [w["name"]])]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = metrics.selected(w["name"])
    assert layer
    for p in layer:
        assert p["moves"] in e2e
        assert callable(metrics.reader(p["name"]))


def test_per_layer_metrics_name_one_layer_and_cells_that_report_the_metric():
    for p in BENCH["per_layer"]:
        moves = {x["name"]: x for x in BENCH["end_to_end"]}[p["moves"]]
        for w in p["workloads"]:
            assert w in moves.get("workloads", [w])


def test_no_program_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    shutil.copy(common.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(common.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns(".traces", "__pycache__"))
    w = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", w,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, capture_output=True, text=True,
                       timeout=120, env=dict(os.environ, PYTHONPATH=""))
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_no_accelerator_no_result():
    w = BENCH["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", w,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=common.ROOT, capture_output=True, text=True,
                       timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no accelerator" in p.stderr
