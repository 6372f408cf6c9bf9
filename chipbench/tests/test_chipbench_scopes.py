"""Device time by the program's scopes, idle gaps by its host spans, and the
readers of the scope and compile metrics, on hand-made events."""
import pytest

from bench import metrics, scopes

MS = 1_000_000
MODULE = "jit_train_step_scaled"


def hand_made():
    return {
        "dev": [("while.24", 0, 90 * MS, 0),                  # container
                ("fused_quant_matmul_nn.104", 10 * MS, 30 * MS, 0),
                ("xor_convert_fusion.7", 40 * MS, 10 * MS, 0),
                ("and_reduce_fusion.3", 60 * MS, 10 * MS, 0),
                ("fusion.1213", 70 * MS, 5 * MS, 0),
                ("copy.9", 80 * MS, 5 * MS, 0),                # no scope
                ("fusion.1213", 120 * MS, 20 * MS, 0),        # other module
                ("fusion.55", 190 * MS, 30 * MS, 0)],          # crosses end
        "mod": [(MODULE, 0, 100 * MS, 0),
                ("jit_convert_element_type", 115 * MS, 30 * MS, 0),
                (MODULE, 185 * MS, 40 * MS, 0)],
        "host": [("chipbench.window", 0, 200 * MS),
                 ("repro.train.record", 88 * MS, 22 * MS),
                 ("chipbench.on_metrics", 112 * MS, 5 * MS),
                 ("repro.train.on_metrics", 111 * MS, 8 * MS),
                 ("repro.train.step_dispatch", 140 * MS, 4 * MS),
                 ("repro.train.device_sync", 145 * MS, 48 * MS)],
        "start_ns": None}


MAP = {"while.24": "train.grads",
       "fused_quant_matmul_nn.104": "train.grads",
       "xor_convert_fusion.7": "fp8.sr_bits",
       "and_reduce_fusion.3": "fp8.amax",
       "fusion.1213": "fp8.quant",
       "fusion.55": "train.optimizer"}


def test_scope_seconds_clip_to_the_window_and_skip_other_modules():
    names = set(MAP) | {"copy.9"}
    ops = {}
    sec = scopes.scope_seconds(hand_made(), MAP, MODULE, names=names,
                               ops=ops)
    assert sec["train.grads"] == pytest.approx(0.030)   # while: nothing
    assert sec["fp8.sr_bits"] == pytest.approx(0.010)
    assert sec["fp8.amax"] == pytest.approx(0.010)
    # the in-module instance only: the same name in another module is not
    assert sec["fp8.quant"] == pytest.approx(0.005)
    assert sec["train.optimizer"] == pytest.approx(0.010)   # clipped
    # unscoped: the copy, and the other module's op
    assert sec[None] == pytest.approx(0.025)
    assert sec["module"] == pytest.approx(0.070)
    assert sec["matched"] == pytest.approx(0.070)
    assert ops[("fusion", "fp8.quant")] == pytest.approx(0.005)
    assert ops[("fusion", None)] == pytest.approx(0.020)


def test_ops_missing_from_the_text_are_not_matched():
    sec = scopes.scope_seconds(hand_made(), MAP, MODULE, names={"copy.9"})
    assert sec["matched"] == pytest.approx(0.005)
    assert sec["module"] == pytest.approx(0.070)


def test_without_a_modules_line_every_op_is_looked_up():
    ev = hand_made()
    ev["mod"] = []
    sec = scopes.scope_seconds(ev, MAP, MODULE)
    assert sec["fp8.quant"] == pytest.approx(0.025)
    assert sec[None] == pytest.approx(0.005)


def test_gaps_are_named_by_the_innermost_covering_span():
    gaps = scopes.name_gaps(hand_made())
    # busy: [0, 90] (the while), [120, 140], [190, 200]; gap [140, 190]:
    # device_sync covers 45 ms, step_dispatch 4; gap [90, 120]: record 20,
    # repro.train.on_metrics 8, chipbench.on_metrics 5
    assert gaps == [["repro.train.device_sync", pytest.approx(0.050)],
                    ["repro.train.record", pytest.approx(0.030)]]
    ev = hand_made()
    ev["host"] = [h for h in ev["host"] if h[0] == "chipbench.window"]
    assert [g[0] for g in scopes.name_gaps(ev)] == ["no_span", "no_span"]


def test_a_gap_inside_nested_spans_takes_the_inner_name():
    ev = hand_made()
    ev["dev"] = [("fusion.1", 0, 100 * MS, 0),
                 ("fusion.2", 120 * MS, 80 * MS, 0)]
    gaps = scopes.name_gaps(ev)
    # [100, 120]: record (10 ms), repro.train.on_metrics [111, 119] (8 ms),
    # chipbench.on_metrics inside it (5 ms) -> record covers most
    assert gaps == [["repro.train.record", pytest.approx(0.020)]]
    ev["host"] = [h for h in ev["host"] if h[0] != "repro.train.record"]
    assert scopes.name_gaps(ev)[0][0] == "repro.train.on_metrics"
    ev["host"].append(("chipbench.on_metrics", 100 * MS, 20 * MS))
    # equal cover: the shorter (inner) span names the gap
    ev["host"].append(("repro.train.on_metrics", 95 * MS, 30 * MS))
    assert scopes.name_gaps(ev)[0][0] == "chipbench.on_metrics"


@pytest.mark.parametrize("text,name", [
    ("%xor_convert_fusion.7 = u8[4096,8960]{1,0} fusion(...)",
     "xor_convert_fusion.7"),
    ("%fused_quant_matmul_nn.104 = (f8e4m3fn[4096,8960]) custom-call(...)",
     "fused_quant_matmul_nn.104"),
])
def test_instance_names_keep_the_number(text, name):
    assert scopes.instance(text) == name


@pytest.mark.parametrize("text", ["jit_train_step_scaled(72474557786291026)",
                                  "HloModule jit_train_step_scaled, is_sch",
                                  "jit_train_step_scaled"])
def test_module_names(text):
    assert scopes.module_of(text) == MODULE


READERS = {"sr_bits_share.train": "fp8.sr_bits",
           "amax_share.train": "fp8.amax",
           "quant_share.train": "fp8.quant",
           "optimizer_share.train": "train.optimizer",
           "scaling_update_share.train": "train.scaling"}


def ctx(kind="train"):
    return {"work": {"kind": kind}, "chips": 1,
            "trace": {"window_s": 2.0, "busy_s": 1.9},
            "scopes": {"fp8.sr_bits": 0.1, "fp8.amax": 0.08,
                       "fp8.quant": 0.05, "train.optimizer": 0.04,
                       "train.grads": 1.6, None: 0.03},
            "compile_s": 21.5}


@pytest.mark.parametrize("name,scope", sorted(READERS.items()))
def test_share_readers(name, scope):
    read = metrics.reader(name)
    want = 100.0 * ctx()["scopes"].get(scope, 0.0) / 2.0
    assert read(ctx()) == pytest.approx(want)
    assert read(ctx("serve")) is None


def test_setup_compile_reader():
    read = metrics.reader("setup_compile_s.train")
    assert read(ctx()) == pytest.approx(21.5)
    assert read(ctx("serve")) is None


def test_readers_give_nothing_where_the_program_keeps_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "_CACHE", {"read": None})
    c = ctx()
    del c["scopes"], c["compile_s"]
    for name in list(READERS) + ["setup_compile_s.train"]:
        assert metrics.reader(name)(c) is None


def test_a_fault_in_reading_gives_nothing(monkeypatch):
    monkeypatch.setattr(scopes, "_CACHE", {})

    def broken(ctx):
        raise RuntimeError("no executable")
    monkeypatch.setattr(scopes, "_read_program", broken)
    c = ctx()
    del c["scopes"]
    assert metrics.reader("sr_bits_share.train")(c) is None


def test_an_older_program_gives_nothing(monkeypatch):
    from repro.obs import trace as program
    monkeypatch.delattr(program, "last_step_text")
    assert scopes._read_program(ctx()) is None


def test_host_spans_and_the_wall_clock_from_a_recorded_trace(tmp_path):
    """On a CPU trace: the program's and the benchmark's spans are read,
    and the trace's start plus a span's offset is the wall clock the
    compile counters keep."""
    import time

    import jax
    from repro.obs.trace import Tracer
    tr = Tracer("repro.train")
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench.window"):
            before = time.time_ns()
            with tr.span("record"):
                pass
            after = time.time_ns()
    ev = scopes.events(str(tmp_path))
    names = {n for n, _, _ in ev["host"]}
    assert {"chipbench.window", "repro.train.record"} <= names
    start = [s for n, s, _ in ev["host"] if n == "repro.train.record"][0]
    assert before <= ev["start_ns"] + start <= after
