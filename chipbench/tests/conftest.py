"""Shared pieces of the benchmark's CPU tests: the import path, and cells
cut to a size the CPU runs in seconds (the widths of the real cells are
only ever run on the chip)."""
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
CHIPBENCH = os.path.dirname(HERE)
sys.path.insert(0, CHIPBENCH)
sys.path.insert(0, os.path.join(os.path.dirname(CHIPBENCH), "src"))

from bench import common  # noqa: E402

TINY_MODEL = dict(hidden_size=64, intermediate_size=128,
                  num_attention_heads=4, num_key_value_heads=2,
                  num_hidden_layers=2, vocab_size=512)
# Limits for the small model, set like the cell's own from CPU readings at
# this size: the program's FP8 path read grad_gap 0.05-0.10,
# grad_gap_median 0.011-0.015 and change_gap 0.017-0.23; the int4 control
# read grad_gap 0.71 and grad_gap_median 0.23, half of the batch 0.64 and
# 0.25, a state left unchanged change_gap 1. The overflow band is the
# cell's own.
TINY_LIMITS = {"grad_gap": 0.4, "grad_gap_median": 0.08, "change_gap": 0.5,
               "drop_mismatch": 0}


def tiny_cell(kind: str = "train", backend: str = "xla"):
    """(workload, configuration entry, configuration file, mix) of the
    real cell with the model and the traffic cut small."""
    work, conf, m, mix = copy.deepcopy(common.cell("qwen2-1.5b.train.4k"))
    m.update(TINY_MODEL)
    m["limits"] = {"train": TINY_LIMITS,
                   "overflow_band": m["limits"]["overflow_band"]}
    m["program"]["set"] = [s.replace("backend=pallas", f"backend={backend}")
                           for s in m["program"]["set"]]
    mix.update(batch=2, seq=64, pool=4)
    return work, conf, m, mix


@pytest.fixture
def no_cache(monkeypatch):
    """Tests leave JAX's persistent compilation cache off."""
    monkeypatch.setattr(common, "use_cache", lambda: None)
