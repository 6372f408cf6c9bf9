"""The trace reduction, on hand-made events and on one recorded training
step (the first 2.2 s of a traced window of `qwen2-1.5b.train.4k` on one
TPU v5 lite: its device ops and the benchmark's host spans)."""
import gzip
import json
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ms = 1_000_000
    return {"dev": [("while", 0, 90 * ms, 0),            # container
                    ("fused_quant_matmul_nn", 10 * ms, 30 * ms, 0),
                    ("fused_quant_matmul_tn", 30 * ms, 20 * ms, 0),
                    ("fp8_attention_fwd", 60 * ms, 10 * ms, 0),
                    ("fusion", 120 * ms, 20 * ms, 0),
                    ("fusion", 190 * ms, 30 * ms, 0)],    # crosses the end
            "host": [("chipbench.window", 0, 200 * ms),
                     ("chipbench.data", 95 * ms, 10 * ms),
                     ("chipbench.on_metrics", 100 * ms, 15 * ms),
                     ("chipbench.step", 140 * ms, 45 * ms)]}


def test_busy_is_the_union_inside_the_window():
    red = trace.reduce(hand_made())
    # [0, 90] + [120, 140] + [190, 200] (clipped) = 120 ms of 200
    assert red["window_s"] == pytest.approx(0.2)
    assert red["busy_s"] == pytest.approx(0.12)


def test_op_time_by_name_leaves_containers_out():
    red = trace.reduce(hand_made())
    assert "while" not in red["op_s"]
    assert red["op_s"]["fusion"] == pytest.approx(0.03)   # 20 + 10 clipped
    assert trace.kernel_s(red, "fused_quant_matmul") == pytest.approx(0.05)
    assert trace.kernel_s(red, "fp8_attention") == pytest.approx(0.01)


def test_gaps_are_named_by_the_covering_host_span():
    red = trace.reduce(hand_made())
    # gaps: [90, 120] (on_metrics 15 ms over data 10 ms), [140, 190]
    assert red["idle_gaps"] == [["chipbench.step", pytest.approx(0.05)],
                                ["chipbench.on_metrics", pytest.approx(0.03)]]


@pytest.mark.parametrize("text,name", [
    ("%fused_quant_matmul_nn.104 = (f8e4m3fn[4096,8960]) custom-call(...)",
     "fused_quant_matmul_nn"),
    ("%while.24 = (s32[]) while(...)", "while"),
    ("fusion", "fusion"),
])
def test_op_names_fold_numbered_instances(text, name):
    assert trace.op_name(text) == name


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(HERE, "data", "train4k_step.json.gz"),
                   "rt") as f:
        ev = json.load(f)
    return trace.reduce(ev)


def test_recorded_step_busy_and_idle(recorded):
    assert recorded["window_s"] == pytest.approx(2.2)
    assert recorded["busy_s"] == pytest.approx(2.188455673, rel=1e-6)
    idle = 1 - recorded["busy_s"] / recorded["window_s"]
    assert 0 < idle < 0.01


def test_recorded_step_kernel_sums(recorded):
    gemm = trace.kernel_s(recorded, "fused_quant_matmul")
    attn = trace.kernel_s(recorded, "fp8_attention")
    assert gemm == pytest.approx(0.842364035 + 0.232624313 + 0.229829954,
                                 rel=1e-6)
    assert attn == pytest.approx(0.235342657 + 0.119945624 + 0.079778402,
                                 rel=1e-6)
    assert recorded["device_ops"][0][0] == "fused_quant_matmul_nn"
    assert sum(recorded["op_s"].values()) <= recorded["busy_s"] * 1.0001


def test_recorded_step_gap_attribution(recorded):
    names = [g[0] for g in recorded["idle_gaps"]]
    assert "chipbench.data" in names and "chipbench.on_metrics" in names
    assert recorded["idle_gaps"][0][1] >= recorded["idle_gaps"][-1][1]
