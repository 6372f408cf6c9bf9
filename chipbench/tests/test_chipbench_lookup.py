"""An architecture module and a traffic kind's runner are found by name: a
copy of each, added as a file under another name, runs the tiny cell to
the same readings; a missing one stops the run, naming the file."""
import shutil
import sys

import pytest

import run
from bench import arch, common
from conftest import CHIPBENCH, tiny_cell

COPIES = ("chipbench_model_Qwen2CopyForCausalLM", "bench.train_copy")


def run_tiny(cell):
    args = run.parse(["--workload", cell[0]["name"], "--seed",
                      str(2 ** 31 + 5), "--seconds", "0.3", "--trace", "0"])
    return run.run_cell(args, cell=cell, require_chip=False)


@pytest.fixture
def copies(tmp_path, monkeypatch):
    """models/ and bench/ of their own in a temporary directory, each with
    a copy of the qwen2 module and of the train runner."""
    (tmp_path / "models").mkdir()
    (tmp_path / "bench").mkdir()
    shutil.copy(f"{CHIPBENCH}/models/Qwen2ForCausalLM.py",
                tmp_path / "models" / "Qwen2CopyForCausalLM.py")
    shutil.copy(f"{CHIPBENCH}/bench/train.py",
                tmp_path / "bench" / "train_copy.py")
    yield tmp_path
    for name in COPIES:
        sys.modules.pop(name, None)


def test_a_module_and_a_runner_added_as_files_run(no_cache, copies,
                                                  monkeypatch):
    cell = tiny_cell("train")
    result, checks, out = run_tiny(cell)
    monkeypatch.setattr(arch, "MODELS", copies / "models")
    monkeypatch.setattr(common, "RUNNERS", copies / "bench")
    work, conf, m, mix = tiny_cell("train")
    m["architectures"] = ["Qwen2CopyForCausalLM"]
    mix["kind"] = "train_copy"
    result2, checks2, out2 = run_tiny((work, conf, m, mix))
    for name in COPIES:
        assert sys.modules[name].__file__.startswith(str(copies))
    assert result["correct"] and result2["correct"]
    assert checks2 == checks
    assert out2["readings"] == out["readings"]
    assert out2["widest"] == out["widest"]


@pytest.mark.parametrize("what,want", [
    ("architectures", "models/NoSuchForCausalLM.py"),
    ("kind", "bench/no_such_kind.py"),
])
def test_a_missing_module_or_runner_stops_the_run_naming_the_file(
        no_cache, what, want):
    work, conf, m, mix = tiny_cell("train")
    if what == "kind":
        mix["kind"] = "no_such_kind"
    else:
        m["architectures"] = ["NoSuchForCausalLM"]
    with pytest.raises(SystemExit) as e:
        run_tiny((work, conf, m, mix))
    assert f"{CHIPBENCH}/{want}" in str(e.value.code)
