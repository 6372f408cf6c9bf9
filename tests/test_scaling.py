"""Delayed per-tensor scaling subsystem (repro.scaling).

Covers: ring-buffer history semantics, scaling-mode config plumbing,
delayed-vs-jit amax equivalence on a constant-amax stream, the hot-path
guarantee (no full-tensor amax reduction when quantizing under delayed
scaling), end-to-end delayed training on the paper transformer, calibration
freeze -> deterministic serving, ScaleState checkpoint round-trip, and the
cross-replica amax sync."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quantize as Q
from repro.core.precision_policy import (DELAYED_FP8, PAPER_FP8, QuantConfig)
from repro.core.qlinear import qeinsum
from repro.scaling import context as sc
from repro.scaling.state import (DelayedScaling, ScaleState, ScalingConfig,
                                 SiteRegistry, amax_from_history,
                                 split_observations)

RNE_JIT = QuantConfig(scaling="jit_amax", act_rounding="rne",
                      error_rounding="rne", grad_rounding="rne",
                      saturate_bwd=True)
RNE_DELAYED = dataclasses.replace(RNE_JIT, scaling="delayed")


# ---------------------------------------------------------------------------
# config plumbing
# ---------------------------------------------------------------------------

class TestQuantConfigModes:
    def test_backcompat_shim(self):
        cfg = QuantConfig(amax_scale_fwd=True, amax_scale_bwd=True)
        assert cfg.scaling == "jit_amax"
        assert cfg.amax_for("act") and cfg.amax_for("error")

    def test_shim_respects_direction(self):
        cfg = QuantConfig(amax_scale_fwd=True)
        assert cfg.scaling == "jit_amax"
        assert cfg.amax_for("weight") and not cfg.amax_for("error")

    def test_delayed_never_jit_amax(self):
        assert not DELAYED_FP8.amax_for("act")
        assert DELAYED_FP8.delayed

    def test_paper_default_unchanged(self):
        assert PAPER_FP8.scaling == "none"
        assert not PAPER_FP8.amax_for("act")

    def test_unknown_mode_rejected(self):
        with pytest.raises(ValueError):
            QuantConfig(scaling="bogus")


# ---------------------------------------------------------------------------
# ring-buffer history
# ---------------------------------------------------------------------------

def _reg(keys=("s#a.A",), token_sites=()):
    return SiteRegistry(keys, token_sites)


class TestHistory:
    def test_ring_push_order(self):
        ds = DelayedScaling(_reg(), ScalingConfig(history_len=3, margin=1.0))
        st = ds.init()
        for v in [1.0, 2.0, 3.0, 4.0]:
            st = ds.update(st, {"s#a.A": jnp.float32(v)})
        np.testing.assert_array_equal(np.asarray(st.amax_history[0]),
                                      [4.0, 3.0, 2.0])
        assert int(st.step) == 4

    def test_policies(self):
        hist = jnp.asarray([[1.0, 4.0, 2.0]], jnp.float32)
        assert float(amax_from_history(
            hist, ScalingConfig(policy="max"))[0]) == 4.0
        assert float(amax_from_history(
            hist, ScalingConfig(policy="most_recent"))[0]) == 1.0
        ema = float(amax_from_history(
            hist, ScalingConfig(policy="ema", ema_decay=0.5))[0])
        assert 1.0 < ema < 4.0

    def test_scale_formula(self):
        ds = DelayedScaling(_reg(), ScalingConfig(history_len=2, margin=1.0),
                            qcfg=RNE_DELAYED)
        st = ds.update(ds.init(), {"s#a.A": jnp.float32(2.0)})
        assert float(st.scale[0]) == pytest.approx(2.0 / 57344.0)

    def test_unobserved_key_carries_forward(self):
        ds = DelayedScaling(_reg(("s#a.A", "s#b.W")),
                            ScalingConfig(history_len=2, margin=1.0))
        st = ds.update(ds.init(), {"s#a.A": jnp.float32(2.0),
                                   "s#b.W": jnp.float32(8.0)})
        st = ds.update(st, {"s#a.A": jnp.float32(2.0)})   # b unobserved
        np.testing.assert_array_equal(np.asarray(st.amax_history[1]),
                                      [8.0, 8.0])

    def test_empty_history_keeps_unit_scale(self):
        ds = DelayedScaling(_reg(("s#a.A", "s#b.W")),
                            ScalingConfig(history_len=2))
        st = ds.update(ds.init(), {"s#a.A": jnp.float32(2.0)})
        assert float(st.scale[1]) == 1.0     # never observed -> scale 1

    def test_overflow_guard_probes_upward(self):
        ds = DelayedScaling(_reg(("s#E",)),
                            ScalingConfig(history_len=2, margin=1.0,
                                          growth=2.0))
        st = ds.init()
        st = ds.update(st, {"s#E": jnp.float32(np.inf)})
        v = float(st.amax_history[0, 0])
        assert np.isfinite(v) and v == pytest.approx(2.0 * 57344.0)

    def test_saturation_growth(self):
        ds = DelayedScaling(_reg(), ScalingConfig(history_len=2, margin=1.0,
                                                  growth=2.0))
        st = ds.init()   # scale 1.0 -> cap 57344
        st = ds.update(st, {"s#a.A": jnp.float32(57344.0)})
        assert float(st.amax_history[0, 0]) == pytest.approx(2 * 57344.0)
        # carried-forward (unobserved) rows must NOT re-grow
        st2 = ds.update(st, {})
        np.testing.assert_allclose(np.asarray(st2.amax_history[0, 0]),
                                   np.asarray(st.amax_history[0, 0]))

    def test_state_is_pytree(self):
        st = ScaleState.create(3, 4)
        leaves = jax.tree_util.tree_leaves(st)
        assert len(leaves) == 3
        st2 = jax.tree_util.tree_map(lambda x: x, st)
        assert st2.amax_history.shape == (3, 4)


# ---------------------------------------------------------------------------
# delayed vs jit equivalence (constant-amax stream)
# ---------------------------------------------------------------------------

class TestDelayedVsJit:
    def test_bitwise_equal_after_warmup(self):
        # amaxes placed exactly on the fp8 grid, so the observed (quantized)
        # amax equals the true amax and one warmup step converges the
        # history-derived scale to the jit-amax scale exactly.
        a = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        a = a.at[0, 0].set(4.0)    # amax = 4.0 (on-grid)
        b = b.at[0, 0].set(8.0)
        key = jax.random.PRNGKey(2)

        y_jit = qeinsum("mk,kn->mn", a, b, key=key, cfg=RNE_JIT)

        reg = sc.operand_keys("site", ("act", "weight"))
        registry = SiteRegistry(reg.values(), ("site",))
        ds = DelayedScaling(registry, ScalingConfig(margin=1.0, policy="max"),
                            qcfg=RNE_DELAYED)
        state = ds.init()

        def run_collect(state):
            with ds.collect(state, ds.zero_tokens()):
                y = qeinsum("mk,kn->mn", a, b, key=key, cfg=RNE_DELAYED,
                            site="site")
                obs = sc.drain_aux()
            observed = split_observations(obs, {}, registry)
            return y, ds.update(state, observed)

        _, state = run_collect(state)       # warmup: history <- true amaxes
        y_delayed, _ = run_collect(state)   # scales now == jit-amax scales
        np.testing.assert_array_equal(np.asarray(y_delayed),
                                      np.asarray(y_jit))

    def test_token_cotangent_normalized_by_use_count(self):
        """A site used N times accumulates the SUM of N per-use E/G amaxes
        in its token cotangent; split_observations must divide by the
        trace-time use count so history records the mean, not the sum."""
        a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        b = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
        registry = SiteRegistry(sc.operand_keys("s", ("act", "weight"))
                                .values(), ("s",))
        ds = DelayedScaling(registry, qcfg=RNE_DELAYED)
        state = ds.init()

        def loss(a, tokens, n_uses):
            with ds.collect(state, tokens):
                total = 0.0
                for _ in range(n_uses):   # same site, n_uses identical uses
                    total = total + qeinsum("mk,kn->mn", a, b,
                                            key=jax.random.PRNGKey(7),
                                            cfg=RNE_DELAYED, site="s").sum()
                sc.drain_aux()
            return total

        obs = {}
        for n in (1, 3):
            _, tg = jax.value_and_grad(loss, argnums=(0, 1))(
                a, ds.zero_tokens(), n)
            assert registry.token_uses["s"] == n
            obs[n] = split_observations({}, tg[1], registry)["s#E"]
        # dY is all-ones at every use (sum() cotangent), so the normalized
        # per-use E amax must not scale with the number of uses.
        assert float(obs[3]) == pytest.approx(float(obs[1]))

    def test_observed_amax_matches_input_amax_on_grid(self):
        x = jnp.zeros((8, 8), jnp.float32).at[3, 3].set(-16.0)
        w = jnp.eye(8, dtype=jnp.float32)
        registry = SiteRegistry(sc.operand_keys("s", ("act", "weight"))
                                .values(), ("s",))
        ds = DelayedScaling(registry, qcfg=RNE_DELAYED)
        with ds.collect(ds.init(), ds.zero_tokens()):
            qeinsum("mk,kn->mn", x, w, key=jax.random.PRNGKey(0),
                    cfg=RNE_DELAYED, site="s")
            obs = sc.drain_aux()
        assert float(obs["amax/s#a.A"]) == 16.0
        assert float(obs["amax/s#b.W"]) == 1.0


# ---------------------------------------------------------------------------
# hot path: no full-tensor amax reduction under delayed scaling
# ---------------------------------------------------------------------------

# The canonical traversal lives in repro.analysis.jaxpr_walk; the lint
# passes and these tests assert through the same walker.
from repro.analysis.jaxpr_walk import walk_eqns as _walk_eqns


def _wide_reduce_max_count(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    wide = (jnp.float32, jnp.float16, jnp.bfloat16, jnp.float64)
    n = 0
    for eqn in _walk_eqns(jaxpr.jaxpr):
        if eqn.primitive.name == "reduce_max" and \
                any(getattr(v.aval, "dtype", None) in
                    [jnp.dtype(d) for d in wide] for v in eqn.invars):
            n += 1
    return n


class TestHotPath:
    def test_delayed_has_no_wide_amax_reduce(self):
        """The jit-amax path reduces over the full bf16/f32 operand per
        quantize; the delayed path must not (its observation reduces over
        the 1-byte fp8 payload only)."""
        a = jax.random.normal(jax.random.PRNGKey(0), (16, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
        key = jax.random.PRNGKey(2)
        registry = SiteRegistry(sc.operand_keys("s", ("act", "weight"))
                                .values(), ("s",))
        ds = DelayedScaling(registry, qcfg=RNE_DELAYED)
        state = ds.init()

        def delayed_fwd_bwd(a, b, tokens):
            with ds.collect(state, tokens):
                def f(a, b, tokens):
                    return qeinsum("mk,kn->mn", a, b, key=key,
                                   cfg=RNE_DELAYED, site="s").sum()
                return jax.grad(f, argnums=(0, 1, 2))(a, b, tokens)

        def jit_fwd_bwd(a, b):
            def f(a, b):
                return qeinsum("mk,kn->mn", a, b, key=key, cfg=RNE_JIT).sum()
            return jax.grad(f, argnums=(0, 1))(a, b)

        assert _wide_reduce_max_count(delayed_fwd_bwd, a, b,
                                      ds.zero_tokens()) == 0
        assert _wide_reduce_max_count(jit_fwd_bwd, a, b) > 0

    def test_inline_amax_scale_never_called(self, monkeypatch):
        """quantize.amax_scale is the just-in-time reduction; under delayed
        scaling it must never run during the traced step."""
        def boom(*a, **k):
            raise AssertionError("inline amax_scale called in delayed mode")
        monkeypatch.setattr(Q, "amax_scale", boom)
        a = jax.random.normal(jax.random.PRNGKey(0), (8, 16))
        b = jax.random.normal(jax.random.PRNGKey(1), (16, 4))
        registry = SiteRegistry(sc.operand_keys("s", ("act", "weight"))
                                .values(), ("s",))
        ds = DelayedScaling(registry, qcfg=RNE_DELAYED)
        with ds.collect(ds.init(), ds.zero_tokens()):
            y = qeinsum("mk,kn->mn", a, b, key=jax.random.PRNGKey(2),
                        cfg=RNE_DELAYED, site="s")
        assert np.isfinite(np.asarray(y)).all()


# ---------------------------------------------------------------------------
# end-to-end: paper transformer trains under delayed scaling
# ---------------------------------------------------------------------------

def _tiny_paper_cfg():
    from repro.configs import paper_transformer
    from repro.scaling.calibrate import _delayed_quant_model
    cfg = paper_transformer.smoke().replace(
        n_layers=2, n_encoder_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        d_ff=128, vocab_size=128, max_seq_len=32)
    return _delayed_quant_model(cfg)


class TestDelayedTraining:
    def test_paper_transformer_20_steps_finite(self, monkeypatch):
        from repro.models.transformer import init_lm
        from repro.scaling.calibrate import discover_lm_sites
        from repro.train.step import make_optimizer_for, make_train_step

        # Hot-path guarantee holds for the full model trace too.
        def boom(*a, **k):
            raise AssertionError("inline amax_scale called in delayed mode")
        monkeypatch.setattr(Q, "amax_scale", boom)

        cfg = _tiny_paper_cfg()
        assert cfg.policy.quant.scaling == "delayed"
        params = init_lm(jax.random.PRNGKey(0), cfg)
        B, S = 2, 16
        proto = {"tokens": jnp.zeros((B, S), jnp.int32),
                 "labels": jnp.zeros((B, S), jnp.int32),
                 "enc_inputs": jnp.zeros((B, 8, cfg.d_model), jnp.float32)}
        registry = discover_lm_sites(cfg, params, proto)
        assert len(registry) > 30 and len(registry.token_sites) > 10
        ds = DelayedScaling(registry, qcfg=cfg.policy.quant)
        opt = make_optimizer_for(cfg, learning_rate=1e-3)
        step = jax.jit(make_train_step(cfg, opt, scaling=ds))
        state, sstate = opt.init(params), ds.init()
        rng = np.random.default_rng(0)
        losses = []
        for i in range(20):
            batch = {
                "tokens": jnp.asarray(rng.integers(0, 128, (B, S)), jnp.int32),
                "labels": jnp.asarray(rng.integers(0, 128, (B, S)), jnp.int32),
                "enc_inputs": jnp.asarray(
                    rng.normal(size=(B, 8, cfg.d_model)), jnp.float32)}
            (state, sstate), m = step(state, sstate, batch,
                                      jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
        assert all(np.isfinite(losses)), losses
        assert int(sstate.step) == 20
        # scales actually adapted away from the unit default
        scales = np.asarray(sstate.scale)
        assert (scales != 1.0).sum() > len(scales) // 2
        # observations never leak into the logged metrics
        assert not any(k.startswith("amax/") for k in m)


# ---------------------------------------------------------------------------
# calibrate -> freeze -> deterministic serving
# ---------------------------------------------------------------------------

def _serve_cfg():
    from repro.models.config import ModelConfig
    from repro.core.precision_policy import PrecisionPolicy
    pol = PrecisionPolicy(kv_cache_format="e5m2")
    return ModelConfig(arch="tiny", n_layers=2, d_model=64, n_heads=2,
                       n_kv_heads=2, d_ff=128, vocab_size=128,
                       max_seq_len=64, policy=pol, scan_layers=False)


class TestCalibratedServing:
    def test_freeze_and_bitwise_deterministic_decode(self):
        from repro.models.transformer import init_lm
        from repro.scaling.calibrate import calibrate, freeze
        from repro.serve.engine import ServeConfig, ServeEngine

        cfg = _serve_cfg()
        params = init_lm(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(1)
        batches = [{"tokens": jnp.asarray(rng.integers(0, 128, (2, 16)),
                                          jnp.int32)} for _ in range(4)]
        ds, state = calibrate(params, cfg, batches,
                              scaling_cfg=ScalingConfig(margin=1.0))
        frozen = freeze(ds, state)
        # forward W/A sites and the FP8 KV-cache sites are all calibrated
        assert any(k.endswith("kv/k#A") for k in frozen)
        assert any("#b.W" in k for k in frozen)
        non_unit = [v for v in frozen.values() if v != 1.0]
        assert len(non_unit) > len(frozen) // 2
        assert all(np.isfinite(v) and v > 0 for v in frozen.values())

        def generate():
            eng = ServeEngine(cfg, params, ServeConfig(max_batch=2,
                                                       max_len=48),
                              frozen_scales=frozen)
            uid = eng.add_request(np.array([3, 5, 7], np.int32),
                                  max_new_tokens=8)
            out = eng.run_to_completion()
            return out[uid]

        first, second = generate(), generate()
        assert first == second            # bitwise deterministic
        assert len(first) == 8

    def test_frozen_scales_round_trip_json(self, tmp_path):
        from repro.scaling.calibrate import load_frozen, save_frozen
        scales = {"decoder/layer_0/attn/wq#a.A": 0.125,
                  "decoder/layer_0/kv/k#A": 3.5e-4}
        save_frozen(tmp_path, scales)
        assert load_frozen(tmp_path) == scales

    def test_frozen_formats_round_trip_json(self, tmp_path):
        from repro.scaling.calibrate import (load_frozen,
                                             load_frozen_formats,
                                             save_frozen)
        scales = {"decoder/layer_0/attn/wq#a.A": 0.125,
                  "decoder/layer_0/attn/kv/k#A": 3.5e-4}
        formats = {"decoder/layer_0/attn/wq#a.A": "e4m3",
                   "decoder/layer_0/attn/kv/k#A": "e5m2"}
        save_frozen(tmp_path, scales, formats)
        assert load_frozen(tmp_path) == scales
        assert load_frozen_formats(tmp_path) == formats

    def test_legacy_frozen_file_has_no_formats(self, tmp_path):
        from repro.scaling.calibrate import load_frozen_formats, save_frozen
        save_frozen(tmp_path, {"s#a.A": 1.0})
        assert load_frozen_formats(tmp_path) == {}

    def test_engine_refuses_format_mismatch(self):
        """A scale calibrated for the e4m3 grid served on e5m2 would be
        silently 128x off — the engine must refuse at construction."""
        from repro.serve.engine import ServeConfig, ServeEngine
        from repro.models.transformer import init_lm
        cfg = _serve_cfg()   # paper recipe (e5m2 W/A), e5m2 KV cache
        params = init_lm(jax.random.PRNGKey(0), cfg)
        scales = {"decoder/layer_0/attn/wq#a.A": 0.25}
        with pytest.raises(ValueError, match="calibrated under"):
            ServeEngine(cfg, params, ServeConfig(max_batch=1, max_len=16),
                        frozen_scales=scales,
                        frozen_formats={"decoder/layer_0/attn/wq#a.A":
                                        "e4m3"})
        # KV sites validate against the policy's kv_cache_format
        with pytest.raises(ValueError, match="kv"):
            ServeEngine(cfg, params, ServeConfig(max_batch=1, max_len=16),
                        frozen_scales=scales,
                        frozen_formats={"decoder/layer_0/attn/kv/k#A":
                                        "e4m3"})
        # matching formats construct fine
        ServeEngine(cfg, params, ServeConfig(max_batch=1, max_len=16),
                    frozen_scales=scales,
                    frozen_formats={"decoder/layer_0/attn/wq#a.A": "e5m2",
                                    "decoder/layer_0/attn/kv/k#A": "e5m2"})

    def test_freeze_with_formats_matches_recipe(self):
        from repro.scaling.calibrate import freeze_with_formats
        from repro.scaling.state import DelayedScaling, SiteRegistry
        from repro.core.precision_policy import HYBRID_DELAYED_FP8
        reg = SiteRegistry(["s#a.A", "s#b.W", "s#E",
                            "dec/attn/kv/k#A"])
        ds = DelayedScaling(reg, qcfg=HYBRID_DELAYED_FP8)
        scales, formats = freeze_with_formats(ds, ds.init(), _serve_cfg())
        assert formats["s#a.A"] == formats["s#b.W"] == "e4m3"
        assert formats["dec/attn/kv/k#A"] == "e5m2"   # from the KV policy
        assert "s#E" not in scales and "s#E" not in formats

    def test_kv_scales_refuse_uncalibrated_frozen_sites(self):
        """Frozen serving with an FP8 KV cache whose kv/* sites were never
        calibrated must REFUSE instead of silently quantizing the cache
        with unit scales (the bug: _kv_scales defaulted to 1.0, burning a
        wrong constant into the jitted program)."""
        from repro.models.attention import _kv_scales
        from repro.scaling import context as scale_ctx
        cfg = _serve_cfg()   # e5m2 KV cache policy
        # frozen context WITHOUT the kv sites -> raise, naming the sites
        ctx = scale_ctx.frozen_context({"decoder/wq#a.A": 0.25})
        with scale_ctx.activate(ctx), scale_ctx.scope("decoder"):
            with pytest.raises(ValueError, match="kv/k#A"):
                _kv_scales(cfg)
        # with the kv sites present the frozen constants flow through
        good = {"decoder/kv/k#A": 0.5, "decoder/kv/v#A": 0.25}
        with scale_ctx.activate(scale_ctx.frozen_context(good)), \
                scale_ctx.scope("decoder"):
            assert _kv_scales(cfg) == (0.5, 0.25)
        # no FP8 KV cache -> no constraint, whatever the context holds
        cfg_nokv = _serve_cfg()
        pol = dataclasses.replace(cfg_nokv.policy, kv_cache_format=None)
        cfg_nokv = cfg_nokv.replace(policy=pol)
        with scale_ctx.activate(scale_ctx.frozen_context({})), \
                scale_ctx.scope("decoder"):
            assert _kv_scales(cfg_nokv) == (1.0, 1.0)
        # calibration/collection contexts keep the permissive unit default
        with scale_ctx.activate(scale_ctx.collect_context({}, {})), \
                scale_ctx.scope("decoder"):
            assert _kv_scales(cfg) == (1.0, 1.0)


# ---------------------------------------------------------------------------
# checkpoint round-trip
# ---------------------------------------------------------------------------

class TestCheckpointRoundTrip:
    def test_scale_state_through_checkpointer(self, tmp_path):
        from repro.checkpoint import Checkpointer
        reg = SiteRegistry(("a#a.A", "a#E"), ("a",))
        ds = DelayedScaling(reg, ScalingConfig(history_len=4))
        st = ds.update(ds.init(), {"a#a.A": jnp.float32(2.0),
                                   "a#E": jnp.float32(128.0)})
        ck = Checkpointer(tmp_path, async_save=False)
        ck.save(7, {"scales": st}, extra={"scale_keys": list(reg.keys)})
        proto = jax.eval_shape(lambda s: s, {"scales": ds.init()})
        restored, step = ck.restore(proto)
        assert step == 7
        np.testing.assert_array_equal(np.asarray(st.amax_history),
                                      np.asarray(restored["scales"]
                                                 .amax_history))
        np.testing.assert_array_equal(np.asarray(st.scale),
                                      np.asarray(restored["scales"].scale))
        assert ck.manifest(7)["extra"]["scale_keys"] == list(reg.keys)


# ---------------------------------------------------------------------------
# distributed amax sync
# ---------------------------------------------------------------------------

class TestAmaxSync:
    def test_pmax_sync_under_pmap(self):
        from repro.distributed.amax_sync import make_amax_sync
        sync = make_amax_sync("d")
        obs = jnp.asarray([[1.0, 5.0, 2.0]], jnp.float32)  # 1 device
        out = jax.pmap(sync, axis_name="d")(obs)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(obs))

    def test_update_applies_sync_hook(self):
        calls = []

        def fake_sync(v):
            calls.append(v.shape)
            return v * 2.0
        ds = DelayedScaling(_reg(), ScalingConfig(history_len=2, margin=1.0))
        st = ds.update(ds.init(), {"s#a.A": jnp.float32(2.0)},
                       sync=fake_sync)
        assert calls == [(1,)]
        assert float(st.amax_history[0, 0]) == 4.0

    def test_none_axis_means_no_sync(self):
        from repro.distributed.amax_sync import make_amax_sync
        assert make_amax_sync(None) is None


# ---------------------------------------------------------------------------
# launch/specs: recipe + delayed-scaling knobs reach the dry-run cells
# ---------------------------------------------------------------------------

class TestSpecsDelayedCell:
    def test_build_cell_accepts_recipe_and_delayed_knobs(self, monkeypatch):
        """build_cell with {'policy.quant.recipe': 'hybrid',
        'policy.quant.scaling': 'delayed'} discovers the site registry,
        threads a ScaleState arg through the step, and shape-infers the
        whole step (the same abstract proof the dry-run lowers)."""
        import repro.launch.specs as S
        import repro.models.registry as R
        from repro.launch.mesh import make_mesh
        from repro.scaling.state import ScaleState

        orig = R.build_config
        monkeypatch.setattr(
            R, "build_config",
            lambda a, smoke=False, **kw: orig(a, smoke=True, **kw))
        monkeypatch.setattr(S, "build_config", R.build_config)
        monkeypatch.setitem(S.SHAPES, "tiny_train",
                            dict(seq=64, batch=8, mode="train"))
        S._cfg_for_cell.cache_clear()
        try:
            mesh = make_mesh((1, 1), ("data", "model"))
            with jax.set_mesh(mesh):
                cell = S.build_cell(
                    "qwen2-1.5b", "tiny_train", mesh,
                    overrides={"policy.quant.recipe": "hybrid",
                               "policy.quant.scaling": "delayed"})
        finally:
            S._cfg_for_cell.cache_clear()
        assert cell["meta"]["recipe"] == "hybrid"
        assert cell["meta"]["scaling"] == "delayed"
        assert cell["meta"]["scale_rows"] > 0
        # step signature: (state, scale_state, batch, key)
        assert len(cell["args"]) == 4
        assert isinstance(cell["args"][1], ScaleState)
        assert cell["donate_argnums"] == (0, 1)
        # scale-state rows match the discovered registry
        assert cell["args"][1].scale.shape == (cell["meta"]["scale_rows"],)

    def test_build_cell_default_unchanged(self, monkeypatch):
        import repro.launch.specs as S
        import repro.models.registry as R
        from repro.launch.mesh import make_mesh
        orig = R.build_config
        monkeypatch.setattr(
            R, "build_config",
            lambda a, smoke=False, **kw: orig(a, smoke=True, **kw))
        monkeypatch.setattr(S, "build_config", R.build_config)
        monkeypatch.setitem(S.SHAPES, "tiny_train",
                            dict(seq=64, batch=8, mode="train"))
        S._cfg_for_cell.cache_clear()
        try:
            mesh = make_mesh((1, 1), ("data", "model"))
            with jax.set_mesh(mesh):
                cell = S.build_cell("qwen2-1.5b", "tiny_train", mesh)
        finally:
            S._cfg_for_cell.cache_clear()
        assert cell["meta"]["scaling"] == "none"
        assert len(cell["args"]) == 3


# ---------------------------------------------------------------------------
# fused kernel amax epilogue (interpret mode)
# ---------------------------------------------------------------------------

class TestFusedAmaxEpilogue:
    def test_with_amax_matches_reference(self):
        from repro.kernels.fused_quant_matmul import ops
        a = jax.random.normal(jax.random.PRNGKey(0), (64, 128)) \
            .astype(jnp.float8_e5m2)
        b = jax.random.normal(jax.random.PRNGKey(1), (128, 64)) \
            .astype(jnp.float8_e5m2)
        key = jax.random.PRNGKey(2)
        scale = jnp.asarray([2.0], jnp.float32)
        out, amax = ops.fused_quant_matmul(a, b, key, scale, rounding="rne",
                                           with_amax=True, interpret=True)
        out_ref = ops.fused_quant_matmul(a, b, key, scale, rounding="rne",
                                         interpret=True)
        np.testing.assert_array_equal(
            np.asarray(out).view(np.uint8), np.asarray(out_ref).view(np.uint8))
        expect = float(jnp.max(jnp.abs(out.astype(jnp.float32))) * 2.0)
        assert float(amax) == pytest.approx(expect)
