"""Precision-health telemetry (src/repro/obs + tools/healthdash).

The load-bearing law: enabling the counters (`QuantConfig.track_health`)
changes NO computed bits — loss, grads, master weights, and amax histories
are locked bit-identical counters-on vs counters-off, under both format
recipes, through the jitted train step and the fused attention kernel.
Plus: metrics pipeline (scalar/vector serialization, jsonl lifecycle),
anomaly detectors, forced-overflow / forced-saturation end-to-end runs,
healthdash rendering + schema validation, and straggler-EMA persistence
across checkpoint restarts.
"""
import dataclasses
import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.loss_scale import LossScaler
from repro.core.precision_policy import QuantConfig
from repro.obs.health import HealthConfig, HealthMonitor
from repro.obs.metrics import SCHEMA_VERSION, MetricsLogger, jsonable
from repro.obs import trace as obs_trace
from repro.obs.trace import SCOPES, Tracer, op_scopes
from repro.scaling import context as sc
from repro.scaling.state import DelayedScaling, SiteRegistry
from repro.tools import healthdash

jax.config.update("jax_platform_name", "cpu")

RECIPES = ("paper_e5m2", "hybrid")


# ---------------------------------------------------------------------------
# serialization + logger lifecycle
# ---------------------------------------------------------------------------

class TestJsonable:
    def test_scalars(self):
        assert jsonable(3) == 3
        assert jsonable(True) is True
        assert jsonable(1.5) == 1.5
        assert jsonable(np.float32(2.5)) == 2.5
        assert jsonable(jnp.asarray(7, jnp.int32)) == 7
        assert jsonable(float("nan")) == "nan"

    def test_vectors_do_not_raise(self):
        """The old loop coerced every metric with float(np.asarray(v)) and
        raised on vectors; jsonable must serialize them as (nested) lists."""
        v = jnp.arange(6, dtype=jnp.float32).reshape(3, 2)
        out = jsonable(v)
        assert out == [[0.0, 1.0], [2.0, 3.0], [4.0, 5.0]]
        json.dumps(out)  # round-trippable

    def test_dict_and_tuple(self):
        out = jsonable({"a": (jnp.ones(2), 1)})
        assert out == {"a": [[1.0, 1.0], 1]}


class TestMetricsLogger:
    def test_jsonl_sink_and_close(self, tmp_path):
        path = str(tmp_path / "m.jsonl")
        with MetricsLogger(path, meta={"arch": "t"}) as logger:
            for i in range(3):
                rec = logger.log({"step": i, "loss": 1.0 / (i + 1),
                                  "health/x#A": jnp.asarray([0.1, 0.2])})
            assert rec["v"] == SCHEMA_VERSION
        assert logger._f is None  # closed on context exit
        lines = [json.loads(l) for l in open(path)]
        assert len(lines) == 3
        assert all(l["v"] == SCHEMA_VERSION for l in lines)
        assert lines[0]["health/x#A"] == [pytest.approx(0.1),
                                          pytest.approx(0.2)]
        meta = json.loads((tmp_path / "m.jsonl.meta.json").read_text())
        assert meta["schema_version"] == SCHEMA_VERSION
        assert meta["arch"] == "t"

    def test_rolling_windows(self):
        logger = MetricsLogger(None, window=4)
        for i in range(10):
            logger.log({"t": float(i)})
        assert logger.values("t") == (6.0, 7.0, 8.0, 9.0)
        assert logger.mean("t") == 7.5
        assert logger.percentile("t", 50) == 7.5
        assert logger.mean("missing") is None

    def test_close_idempotent(self, tmp_path):
        logger = MetricsLogger(str(tmp_path / "m.jsonl"))
        logger.close()
        logger.close()


def profiled_host_spans(path):
    """Names of the host events of the newest profiler trace under `path`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(str(path), "**", "*.xplane.pb"),
                             recursive=True))
    assert files, f"no profiler trace under {path}"
    data = ProfileData.from_file(files[-1])
    return [ev.name for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]


class TestTracer:
    def test_spans_and_export(self, tmp_path):
        tr = Tracer("repro.train")
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("data_wait", step=0):
                pass
            with tr.span("step_dispatch"):
                pass
        d = tr.durations()
        assert set(d) == {"span/data_wait_s", "span/step_dispatch_s"}
        assert all(v >= 0 for v in d.values())
        assert tr.durations() == {}  # popped
        names = profiled_host_spans(tmp_path)
        assert "repro.train.data_wait" in names
        assert "repro.train.step_dispatch" in names

    def test_spans_time_without_a_trace(self):
        tr = Tracer("repro.serve")
        for _ in range(3):
            with tr.span("decode", active=2):
                pass
        d = tr.durations()
        assert list(d) == ["span/decode_s"] and d["span/decode_s"] >= 0


# ---------------------------------------------------------------------------
# device scopes: op_scopes over compiled text
# ---------------------------------------------------------------------------

HAND_HLO = """HloModule jit_step, is_scheduled=true

%fused_computation (param_0: f32[8]) -> u8[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %xor.3 = u8[8]{0} xor(%param_0), metadata={op_name="jit(step)/train.grads/jvp()/fp8.quant/fp8.sr_bits/xor"}
}

%body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %arg = (s32[], f32[8]{0}) parameter(0)
  %gte.1 = f32[8]{0} get-tuple-element(%arg), index=1
  %xor_convert_fusion.2 = u8[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step)/train.grads/jvp()/while/body/fp8.quant/fp8.sr_bits/xor"}
  %convert.5 = f32[8]{0} convert(%gte.1), metadata={op_name="jit(step)/train.grads/transpose(jvp(fp8.quant))/convert_element_type"}
  %and.7 = u8[8]{0} and(%xor_convert_fusion.2), metadata={op_name="jit(step)/train.grads/transpose(jvp())/while/body/fp8.amax/and"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1, %convert.5)
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0), metadata={op_name="p"}
  %while.24 = (s32[], f32[8]{0}) while(%p), body=%body, metadata={op_name="jit(step)/train.grads/jvp()/while"}
  %multiply.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/train.optimizer/mul" source_file="x.py" source_line=3}
  ROOT %copy.1 = f32[8]{0} copy(%p)
}
"""


class TestOpScopes:
    def test_innermost_scope_across_computations(self):
        m = op_scopes(HAND_HLO)
        # nested scopes: the innermost wins, in a fusion and in its body
        assert m["xor_convert_fusion.2"] == "fp8.sr_bits"
        assert m["xor.3"] == "fp8.sr_bits"
        # a backward op carries its forward scope inside the transform
        assert m["convert.5"] == "fp8.quant"
        assert m["and.7"] == "fp8.amax"
        # the while op itself and the entry computation
        assert m["while.24"] == "train.grads"
        assert m["multiply.9"] == "train.optimizer"
        # no scope on the path, or no metadata: left out
        assert "p" not in m and "copy.1" not in m and "gte.1" not in m

    def test_scope_rejects_names_outside_the_vocabulary(self):
        with pytest.raises(ValueError):
            obs_trace.scope("fp8.other")

    def test_scopes_only_add_metadata(self):
        """A scope changes no computed bit and no op of the compiled
        program: only the metadata differs."""
        def f(x, scoped):
            if scoped:
                with obs_trace.scope("fp8.quant"):
                    return (x * 3.0).astype(jnp.float8_e5m2)
            return (x * 3.0).astype(jnp.float8_e5m2)
        x = jnp.linspace(-9.0, 9.0, 64)
        texts = {}
        for scoped in (False, True):
            fn = jax.jit(lambda x, s=scoped: f(x, s))
            texts[scoped] = fn.lower(x).compile().as_text()
            np.testing.assert_array_equal(
                np.asarray(fn(x)).view(np.uint8),
                np.asarray(f(x, False)).view(np.uint8))

        def ops(text):
            return [ln.split(", metadata=")[0] for ln in text.splitlines()
                    if " = " in ln and "parameter(" not in ln]
        assert ops(texts[True]) == ops(texts[False])
        assert set(op_scopes(texts[True]).values()) == {"fp8.quant"}
        assert op_scopes(texts[False]) == {}


def _tiny_loop():
    """The benchmark cell's program path (hybrid formats, delayed scaling)
    on the XLA FP8 backend, at a size the CPU compiles in seconds."""
    from repro.launch.train import make_train_loop, train_config
    cfg, _ = train_config("qwen2-1.5b", overrides=[
        "policy.quant.backend=xla", "policy.quant.recipe=hybrid",
        "policy.quant.scaling=delayed"])
    cfg = cfg.replace(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      d_ff=128, vocab_size=512)
    return make_train_loop(cfg, steps=1, batch=2, seq=64, seed=3,
                           log_every=100)


@pytest.fixture(scope="module")
def tiny_step_scopes():
    loop = _tiny_loop()
    loop.run()
    text = loop.step_text()
    assert obs_trace.last_step_text() == text
    return op_scopes(text)


@pytest.mark.parametrize("name", [s for s in SCOPES
                                  if s != "train.allreduce"])
def test_each_scope_names_an_instruction_of_the_train_step(
        tiny_step_scopes, name):
    """Every phase of the vocabulary is found in the compiled one-device
    train step (the data-parallel reduction exists only on a wire path)."""
    assert name in set(tiny_step_scopes.values())


def test_fused_gemm_sr_draw_and_amax_are_scoped():
    """The fused GEMM's SR bits, drawn outside the kernel, and its amax
    reduction over the per-tile outputs, lowered in interpret mode."""
    from repro.kernels.fused_quant_matmul import ops as fq_ops
    a = jnp.ones((16, 128), jnp.float8_e4m3fn)
    b = jnp.ones((128, 256), jnp.float8_e4m3fn)
    fn = jax.jit(lambda a, b, k: fq_ops.fused_quant_matmul(
        a, b, k, dims="nn", bm=8, bk=128, bn=128, rounding="sr",
        with_amax=True, interpret=True))
    text = fn.lower(a, b, jax.random.PRNGKey(0)).compile().as_text()
    found = set(op_scopes(text).values())
    assert {"fp8.sr_bits", "fp8.amax"} <= found


# ---------------------------------------------------------------------------
# compile counters
# ---------------------------------------------------------------------------

def test_compiles_rise_on_a_retrace_not_on_a_repeat_call():
    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x3, x5 = np.ones((3,), np.float32), np.ones((5,), np.float32)
    f(x3).block_until_ready()
    n, sec = obs_trace.compiles(), obs_trace.compile_seconds()
    f(x3).block_until_ready()
    assert obs_trace.compiles() == n
    f(x5).block_until_ready()     # a new shape retraces
    assert obs_trace.compiles() == n + 1
    after = obs_trace.compile_seconds()
    assert after["compile/backend_s"] > sec["compile/backend_s"]
    assert after["compile/trace_s"] > sec["compile/trace_s"]


def test_nested_compile_events_count_once():
    sp = obs_trace._Spans()
    sp.add(2.0, 3.0)          # an inner jit, traced inside the outer one
    sp.add(5.0, 6.0)
    sp.add(1.0, 7.0)          # the outer trace, reported when it ends
    sp.add(10.0, 11.0)
    assert sp.seconds() == pytest.approx(7.0)
    assert sp.seconds(until=10.5) == pytest.approx(6.5)
    sp.add(6.5, 10.2)         # overlaps both: merges them
    assert sp.seconds() == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# anomaly detectors (unit)
# ---------------------------------------------------------------------------

def _kinds(events):
    return [e["kind"] for e in events]


class TestHealthMonitor:
    def test_overflow_fires_on_increment_only(self):
        mon = HealthMonitor()
        assert mon.observe(0, {"overflow_count": 0, "loss_scale": 8.0}) == []
        assert mon.observe(1, {"overflow_count": 0, "loss_scale": 8.0}) == []
        evs = mon.observe(2, {"overflow_count": 1, "loss_scale": 4.0})
        assert _kinds(evs) == ["overflow"]
        # count flat again: no event
        assert mon.observe(3, {"overflow_count": 1, "loss_scale": 4.0}) == []

    def test_scale_floor_event(self):
        scaler = LossScaler(mode="enhanced", init_scale=2.0 ** 17,
                            min_scale_schedule=((2, 65536.0),))
        mon = HealthMonitor(scaler=scaler)
        mon.observe(0, {"overflow_count": 0, "loss_scale": 131072.0})
        # overflow at step 3 lands the scale exactly on the scheduled floor
        evs = mon.observe(3, {"overflow_count": 1, "loss_scale": 65536.0})
        assert _kinds(evs) == ["overflow", "scale_floor"]
        assert evs[1]["value"] == 65536.0

    def test_no_floor_event_above_schedule(self):
        scaler = LossScaler(mode="enhanced", init_scale=2.0 ** 20,
                            min_scale_schedule=((2, 65536.0),))
        mon = HealthMonitor(scaler=scaler)
        mon.observe(0, {"overflow_count": 0, "loss_scale": 2.0 ** 20})
        evs = mon.observe(3, {"overflow_count": 1, "loss_scale": 2.0 ** 19})
        assert _kinds(evs) == ["overflow"]

    def test_loss_scale_flapping(self):
        mon = HealthMonitor(HealthConfig(flap_window=12, flap_min_changes=6,
                                         cooldown=100))
        kinds = []
        for i in range(12):
            scale = 1024.0 if i % 2 else 2048.0
            kinds += _kinds(mon.observe(i, {"loss_scale": scale}))
        assert "loss_scale_flapping" in kinds

    def test_site_counter_events(self):
        mon = HealthMonitor()
        evs = mon.observe(0, {"health/a#A": [0.5, 0.0],
                              "health/b#E": [0.0, 0.99],
                              "health/c#G": [0.5, 0.99],
                              "health/scale_churn": 0.1})
        got = {(e["kind"], e["site"]) for e in evs}
        assert got == {("saturation", "a#A"), ("underflow", "b#E"),
                       ("range_overflow", "c#G")}

    def test_per_layer_vector_reduces_with_max(self):
        mon = HealthMonitor()
        evs = mon.observe(0, {"health/stack#A": [[0.0, 0.0], [0.9, 0.0]]})
        assert _kinds(evs) == ["saturation"]
        assert evs[0]["value"] == pytest.approx(0.9)

    def test_cooldown_suppresses_repeats(self):
        mon = HealthMonitor(HealthConfig(cooldown=10))
        assert _kinds(mon.observe(0, {"health/a#A": [0.5, 0.0]})) \
            == ["saturation"]
        assert mon.observe(5, {"health/a#A": [0.5, 0.0]}) == []
        assert _kinds(mon.observe(10, {"health/a#A": [0.5, 0.0]})) \
            == ["saturation"]

    def test_stuck_and_nan_amax(self):
        mon = HealthMonitor(HealthConfig(stuck_window=3),
                            site_names=["s0", "s1"])
        kinds = []
        for i in range(5):
            kinds += [(e["kind"], e.get("site")) for e in
                      mon.observe(i, {"health/amax_sites": [2.0, float(i)]})]
        assert ("stuck_amax", "s0") in kinds
        assert all(s != "s1" for _, s in kinds)
        evs = mon.observe(6, {"health/amax_sites": [2.0, float("nan")]})
        assert ("nan_amax", "s1") in [(e["kind"], e.get("site"))
                                      for e in evs]

    def test_straggler_streak(self):
        mon = HealthMonitor(HealthConfig(straggler_streak=3))
        kinds = []
        for i, n in enumerate([0, 1, 2, 3, 3]):
            kinds += _kinds(mon.observe(i, {"stragglers": n}))
        assert kinds.count("straggler_streak") == 1


# ---------------------------------------------------------------------------
# schema validation + rendering
# ---------------------------------------------------------------------------

GOOD = [{"v": SCHEMA_VERSION, "step": 0, "step_time_s": 0.5, "loss": 2.0,
         "stragglers": 0, "health/a#A": [0.1, 0.2],
         "health/scale_churn": 0.25, "health/amax_sites": [1.0, 2.0],
         "span/data_wait_s": 0.01},
        {"v": SCHEMA_VERSION, "step": 1, "step_time_s": 0.4, "loss": 1.9,
         "stragglers": 0, "health/a#A": [[0.1, 0.2], [0.3, 0.4]],
         "health_events": [{"step": 1, "kind": "saturation",
                            "site": "a#A", "value": 0.3}]}]


class TestValidateAndRender:
    def test_good_records_pass(self):
        assert healthdash.validate_records(
            GOOD, {"schema_version": SCHEMA_VERSION}) == []

    def test_corrupted_records_flagged(self):
        bad = [dict(GOOD[0]), dict(GOOD[1])]
        bad[0]["health/a#A"] = [0.1, 0.2, 0.3]   # not a pair
        bad[1]["step"] = 0                        # not increasing
        bad[1]["v"] = 99                          # wrong version
        errors = healthdash.validate_records(bad, {"schema_version": 2})
        assert len(errors) == 4
        errors2 = healthdash.validate_records(
            [{"v": SCHEMA_VERSION, "health_events": [{"site": "x"}]}])
        assert any("step" in e for e in errors2)
        assert any("health_event" in e for e in errors2)

    def test_render_markdown(self):
        md = healthdash.render(GOOD, {"arch": "t", "recipe": "hybrid",
                                      "sites": ["a#A"]},
                               serve_stats={"requests": 3, "finished": 2,
                                            "active": 1, "max_batch": 4,
                                            "kv_slot_occupancy": 0.5,
                                            "decode_tokens": 10,
                                            "decode_tokens_per_s": 100.0,
                                            "prefill_latency_s":
                                                {"p50": 0.1, "p99": 0.2}})
        assert "a#A" in md and "saturation" in md and "Serving" in md
        assert "data_wait" in md

    def test_render_empty(self):
        assert "Empty" in healthdash.render([])


# ---------------------------------------------------------------------------
# end-to-end: jitted train step, counters on vs off — bit parity
# ---------------------------------------------------------------------------

def _tiny_cfg(recipe, track):
    from repro.configs import paper_transformer
    from repro.scaling.calibrate import _delayed_quant_model
    cfg = paper_transformer.smoke().replace(
        n_layers=1, n_encoder_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
        d_ff=64, vocab_size=64, max_seq_len=16)
    cfg = _delayed_quant_model(cfg)
    q = dataclasses.replace(cfg.policy.quant, recipe=recipe,
                            track_health=track)
    return cfg.replace(policy=dataclasses.replace(cfg.policy, quant=q))


def _train_bits(recipe, track, n_steps=3):
    """(losses, master leaves, amax history, last metrics) after n jitted
    delayed-scaling steps."""
    from repro.models.transformer import init_lm
    from repro.scaling.calibrate import discover_lm_sites
    from repro.train.step import make_optimizer_for, make_train_step

    cfg = _tiny_cfg(recipe, track)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 8
    proto = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32),
             "enc_inputs": jnp.zeros((B, 4, cfg.d_model), jnp.float32)}
    registry = discover_lm_sites(cfg, params, proto)
    ds = DelayedScaling(registry, qcfg=cfg.policy.quant)
    opt = make_optimizer_for(cfg, learning_rate=1e-3)
    step = jax.jit(make_train_step(cfg, opt, scaling=ds))
    state, sstate = opt.init(params), ds.init()
    rng = np.random.default_rng(0)
    losses = []
    for i in range(n_steps):
        batch = {"tokens": jnp.asarray(rng.integers(0, 64, (B, S)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 64, (B, S)),
                                       jnp.int32),
                 "enc_inputs": jnp.asarray(
                     rng.normal(size=(B, 4, cfg.d_model)), jnp.float32)}
        (state, sstate), m = step(state, sstate, batch, jax.random.PRNGKey(i))
        losses.append(np.asarray(m["loss"]))
    master = [np.asarray(x) for x in jax.tree_util.tree_leaves(state.master)]
    return losses, master, np.asarray(sstate.amax_history), m


@pytest.mark.parametrize("recipe", RECIPES)
def test_train_step_counters_bit_parity(recipe):
    """THE law: track_health changes no computed bits — losses, master
    weights and amax histories bit-identical on vs off; health keys are
    emitted only when on."""
    losses_off, master_off, hist_off, m_off = _train_bits(recipe, False)
    losses_on, master_on, hist_on, m_on = _train_bits(recipe, True)
    for a, b in zip(losses_off, losses_on):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(master_off, master_on):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(hist_off, hist_on)
    health_on = sorted(k for k in m_on if k.startswith("health/"))
    assert not any(k.startswith("health/") for k in m_off)
    assert "health/scale_churn" in health_on
    assert "health/amax_sites" in health_on
    # per-site pairs present with sane fractions
    pairs = [k for k in health_on
             if k not in ("health/scale_churn", "health/amax_sites")]
    assert pairs
    for k in pairs:
        arr = np.asarray(m_on[k])
        assert arr.shape[-1] == 2
        assert (arr >= 0).all() and (arr <= 1).all()


# ---------------------------------------------------------------------------
# end-to-end: fused attention kernel, counters on vs off — bit parity
# ---------------------------------------------------------------------------

def _sdpa_run(cfg, q, k, v):
    from repro.core.qattention import fp8_sdpa
    keys = sc.attention_keys("s")
    reg = SiteRegistry(list(keys.values()), ("s",))
    ds = DelayedScaling(reg, qcfg=cfg)
    state = ds.init()

    def loss(q, k, v, tokens):
        with ds.collect(state, tokens):
            o = fp8_sdpa(q, k, v, key=jax.random.PRNGKey(7), cfg=cfg,
                         sm_scale=0.125, site="s")
            aux = sc.drain_aux()
        return o.astype(jnp.float32).sum(), (o, aux)

    (_, (o, aux)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3), has_aux=True)(q, k, v, ds.zero_tokens())
    return o, grads, dict(aux)


@pytest.mark.parametrize("recipe", RECIPES)
def test_fused_attention_counters_bit_parity(recipe):
    """Counters ride the kernels' existing stripe loops: outputs, all three
    grads, the amax observations and the token amax channels are
    bit-identical with counting on vs off."""
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 64, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 64, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 64, 64), jnp.bfloat16)
    base = QuantConfig(recipe=recipe, scaling="delayed",
                       backend="pallas_interpret")
    o_off, g_off, aux_off = _sdpa_run(
        dataclasses.replace(base, track_health=False), q, k, v)
    o_on, g_on, aux_on = _sdpa_run(
        dataclasses.replace(base, track_health=True), q, k, v)
    np.testing.assert_array_equal(np.asarray(o_off), np.asarray(o_on))
    for a, b in zip(g_off[:3], g_on[:3]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # amax observations bit-identical
    amax_off = {k2: v2 for k2, v2 in aux_off.items()
                if k2.startswith("amax/")}
    for k2, v2 in amax_off.items():
        np.testing.assert_array_equal(np.asarray(v2),
                                      np.asarray(aux_on[k2]))
    # token cotangents: the 5 amax channels match; health pairs ride behind
    tok_off = g_off[3]["s"]
    tok_on = g_on[3]["s"]
    np.testing.assert_array_equal(np.asarray(tok_off)[:5],
                                  np.asarray(tok_on)[:5])
    # health fracs present only when on, all in [0, 1]
    h_on = {k2: np.asarray(v2) for k2, v2 in aux_on.items()
            if k2.startswith("health/")}
    assert len(h_on) == 5  # q/k/v/s/p forward sites
    assert not any(k2.startswith("health/") for k2 in aux_off)
    for arr in h_on.values():
        assert arr.shape == (2,)
        assert (arr >= 0).all() and (arr <= 1).all()


# ---------------------------------------------------------------------------
# forced-saturation synthetic run -> events -> dashboard
# ---------------------------------------------------------------------------

def test_forced_saturation_emits_event_and_renders():
    """Huge activations under unit initial scales saturate the format; the
    counter sees it, the monitor emits, healthdash renders."""
    from repro.core.qlinear import qeinsum
    cfg = QuantConfig(recipe="paper_e5m2", scaling="delayed",
                      track_health=True)
    a = jax.random.normal(jax.random.PRNGKey(0), (16, 32)) * 1e6
    b = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    registry = SiteRegistry(sc.operand_keys("s", ("act", "weight")).values(),
                            ("s",))
    ds = DelayedScaling(registry, qcfg=cfg)
    with ds.collect(ds.init(), ds.zero_tokens()):
        qeinsum("mk,kn->mn", a, b, key=jax.random.PRNGKey(2), cfg=cfg,
                site="s")
        aux = sc.drain_aux()
    sat = np.asarray(aux["health/s#a.A"])
    assert sat[0] > 0.5  # most of `a` saturates e5m2 at unit scale
    record = {"step": 0, **{k2: jsonable(v2) for k2, v2 in aux.items()
                            if k2.startswith("health/")}}
    events = HealthMonitor().observe(0, record)
    assert any(e["kind"] in ("saturation", "range_overflow")
               and e["site"] == "s#a.A" for e in events)
    record["health_events"] = events
    md = healthdash.render([record])
    assert "s#a.A" in md


# ---------------------------------------------------------------------------
# forced-overflow loop run: exactly-once counting, events, vectors, schema
# ---------------------------------------------------------------------------

def _loop(tmp_path, total_steps, *, init_scale, metrics=None,
          n_microbatches=1, mode="dynamic", on_metrics=None, trace=False):
    from repro.data import DataConfig, synthetic_lm_batches
    from repro.models.registry import build_config
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.step import make_optimizer_for
    cfg = build_config("qwen2-1.5b", smoke=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=128, remat=False)
    opt = make_optimizer_for(cfg, name="adam", learning_rate=3e-3,
                             scaler=LossScaler(mode=mode,
                                               init_scale=init_scale))
    data = synthetic_lm_batches(DataConfig(
        vocab_size=128, seq_len=32, batch_size=8, seed=0))
    loop = LoopConfig(total_steps=total_steps, checkpoint_every=5,
                      checkpoint_dir=str(tmp_path / "ckpt"), log_every=100,
                      metrics_path=metrics, n_microbatches=n_microbatches,
                      trace_path=str(tmp_path / "trace") if trace else None)
    return TrainLoop(cfg, opt, data, loop, seed=0, on_metrics=on_metrics)


def test_forced_overflow_counts_once_and_emits(tmp_path):
    """init_scale 2^127 makes the scaled loss overflow f32: the jitted step
    increments overflow_count by EXACTLY one per overflowing step (not per
    microbatch), the monitor attaches an overflow event, the stream
    validates, and healthdash renders it."""
    mpath = str(tmp_path / "m.jsonl")
    _loop(tmp_path, 6, init_scale=2.0 ** 127, metrics=mpath,
          n_microbatches=2, on_metrics=lambda step, rec: None,
          trace=True).run()
    records, meta = healthdash.load_metrics(mpath)
    assert len(records) == 6
    # step 0 overflowed exactly once despite 2 microbatches
    assert records[0]["overflow_count"] == 1
    counts = [r["overflow_count"] for r in records]
    assert counts == sorted(counts)
    events = [e for r in records for e in r.get("health_events", [])]
    assert any(e["kind"] == "overflow" for e in events)
    # spans made it into the records (a step's record and on_metrics
    # spans land in the next record), with the compile counter
    assert all("span/step_dispatch_s" in r for r in records)
    assert all("span/record_s" in r and "span/on_metrics_s" in r
               for r in records[1:])
    compiles = [r["compiles"] for r in records]
    assert compiles == sorted(compiles) and compiles[0] >= 1
    assert healthdash.validate_records(records, meta) == []
    md = healthdash.render(records, meta)
    assert "overflow" in md
    # a profiler trace recorded alongside: every step's phases on the
    # profiler's clock, inside the step annotation, and a perfetto trace
    names = profiled_host_spans(tmp_path / "trace")
    for phase in ("data_wait", "step_dispatch", "device_sync", "record",
                  "on_metrics", "checkpoint"):
        assert f"repro.train.{phase}" in names, phase
    assert names.count("repro.train.step_dispatch") == 6
    assert names.count("train") == 6
    assert "repro.setup.init_state" in names
    assert glob.glob(str(tmp_path / "trace" / "**" /
                         "perfetto_trace.json.gz"), recursive=True)


def test_quant_loop_vector_metrics_and_schema(tmp_path):
    """Satellite-b regression through the REAL loop: track_health emits
    vector metrics (health/amax_sites, per-site pairs) — the logger must
    serialize them (the old float() coercion raised), the stream must
    validate, and on_metrics must see every serialized record."""
    from repro.data import DataConfig, synthetic_lm_batches
    from repro.models.registry import build_config  # noqa: F401
    from repro.models.transformer import init_lm
    from repro.scaling.calibrate import discover_lm_sites
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.step import make_optimizer_for

    cfg = _tiny_cfg("paper_e5m2", True)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 8
    proto = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32),
             "enc_inputs": jnp.zeros((B, 4, cfg.d_model), jnp.float32)}
    registry = discover_lm_sites(cfg, params, proto)
    del params
    ds = DelayedScaling(registry, qcfg=cfg.policy.quant)
    opt = make_optimizer_for(cfg, name="adam", learning_rate=1e-3,
                             scaler=LossScaler(mode="dynamic",
                                               init_scale=128.0))

    def data_at(step):
        it = synthetic_lm_batches(DataConfig(
            vocab_size=64, seq_len=S, batch_size=B, seed=0),
            start_step=step)
        for batch in it:
            yield {"tokens": batch["tokens"], "labels": batch["labels"],
                   "enc_inputs": jnp.zeros((B, 4, cfg.d_model), jnp.float32)}

    mpath = str(tmp_path / "m.jsonl")
    seen = []
    loop = LoopConfig(total_steps=2, checkpoint_every=10,
                      checkpoint_dir=str(tmp_path / "ckpt"), log_every=100,
                      metrics_path=mpath)
    TrainLoop(cfg, opt, data_at, loop, seed=0, scaling=ds,
              on_metrics=lambda s, r: seen.append((s, r))).run()
    records, meta = healthdash.load_metrics(mpath)
    assert len(records) == 2 and len(seen) == 2
    assert seen[0][1] == records[0]
    assert isinstance(records[0]["health/amax_sites"], list)
    assert meta["track_health"] is True
    assert meta["sites"] == list(registry.keys)
    assert healthdash.validate_records(records, meta) == []
    healthdash.render(records, meta)


# ---------------------------------------------------------------------------
# straggler EMA persists across checkpoint restarts (satellite c)
# ---------------------------------------------------------------------------

def test_straggler_state_survives_restart(tmp_path):
    import time
    lp = _loop(tmp_path, 6, init_scale=128.0)
    lp.loop.straggler_factor = 1.5
    orig = lp._step_fn
    calls = {"n": 0}

    def slow(*a):
        calls["n"] += 1
        if calls["n"] == 5:
            time.sleep(0.4)
        return orig(*a)

    lp._step_fn = slow
    out1 = lp.run()
    assert out1["stragglers"] >= 1
    extra = lp.ckpt.manifest(6).get("extra")
    assert extra["stragglers"] == out1["stragglers"]
    assert extra["straggler_ema"] > 0
    # resume: count carries over instead of resetting to zero, and no new
    # stragglers are flagged against the restored (healthy) baseline
    lp2 = _loop(tmp_path, 8, init_scale=128.0)
    lp2.loop.straggler_factor = 1.5
    out2 = lp2.run()
    assert out2["last_step"] == 8
    assert out2["stragglers"] == out1["stragglers"]
