"""Fused quantize-in-epilogue GEMM path: lowering + bit-for-bit parity.

Locks the three guarantees of the fused qeinsum path (backend="pallas*" +
delayed scaling):

  1. Routing: fwd, dgrad and wgrad all lower to Pallas calls — no silent
     XLA fallback (the bug this PR fixes: the adjoint specs were rejected
     by _pallas_matmul_spec and fell back to jnp.einsum, plus a separate
     _fake_quant_grad pass over HBM).
  2. Numerics: fused output + grads bit-match the unfused
     quantize->matmul composition (the ref oracle) under both recipes.
  3. Observations: the fused-epilogue amaxes bit-match the `_observe`
     bit-pattern reduction over the (identical) materialized payloads, and
     are invariant to the (bm, bk, bn) tiling choice.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.precision_policy import ACT, ERROR, GRAD, WEIGHT, QuantConfig
from repro.core.qlinear import (N_SCALES, _fused_epilogue, _quant_operand,
                                qeinsum)
from repro.core.quantize import fp8_amax_bits
from repro.kernels.fused_quant_matmul import (fused_quant_matmul,
                                              fused_quant_matmul_ref)
from repro.scaling import context as sc
from repro.scaling.state import (DelayedScaling, ScalingConfig, SiteRegistry,
                                 split_observations)

jax.config.update("jax_platform_name", "cpu")


def _cfg(recipe):
    return QuantConfig(recipe=recipe, scaling="delayed",
                       backend="pallas_interpret")


def _site_bundle(cfg, classes=("act", "weight")):
    keys = sc.operand_keys("s", classes)
    fkeys = sc.fused_output_keys("s", classes)
    reg = SiteRegistry(list(keys.values()) + list(fkeys.values()), ("s",))
    ds = DelayedScaling(reg, ScalingConfig(), qcfg=cfg)
    return keys, fkeys, reg, ds


def _run_step(ds, cfg, a, b, key, *, spec="bsk,kn->bsn"):
    """One fused training step through qeinsum; returns (y, grads,
    observations)."""
    def loss(a, b, tokens):
        with ds.collect(ds_state, tokens):
            y = qeinsum(spec, a, b, key=key, cfg=cfg, site="s")
            aux = sc.drain_aux()
        return y.astype(jnp.float32).sum(), (y, aux)

    ds_state = _run_step.state
    (_, (y, aux)), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2), has_aux=True)(a, b, ds.zero_tokens())
    obs = split_observations(dict(aux), grads[2], ds.registry)
    return y, grads[:2], obs


# ---------------------------------------------------------------------------
# 1. routing: all three GEMMs lower to Pallas, none to XLA dots
# ---------------------------------------------------------------------------

# The canonical traversal lives in repro.analysis.jaxpr_walk; the lint
# passes and these tests assert through the same walker.
from repro.analysis.jaxpr_walk import count_prims as _count_prims


class TestFusedLowering:
    @pytest.mark.parametrize("recipe", ["paper_e5m2", "hybrid"])
    def test_three_pallas_calls_no_xla_dots(self, recipe):
        cfg = _cfg(recipe)
        _, _, reg, ds = _site_bundle(cfg)
        a = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.1
        state = ds.init()

        def step(a, b, tokens):
            def loss(a, b, tokens):
                with ds.collect(state, tokens):
                    y = qeinsum("bsk,kn->bsn", a, b,
                                key=jax.random.PRNGKey(2), cfg=cfg, site="s")
                    sc.drain_aux()
                return y.astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(a, b, tokens)

        counts = _count_prims(jax.make_jaxpr(step)(
            a, b, ds.zero_tokens()).jaxpr)
        assert counts["pallas"] == 3, counts   # fwd nn + dgrad nt + wgrad tn
        assert counts["outside_dot"] == 0, counts

    def test_unfused_delayed_pallas_falls_back_for_adjoints(self):
        """With fuse_epilogue=False the fwd GEMM still runs the plain
        fp8_matmul kernel but both adjoints fall back to XLA dots — the
        regression this PR fixes; kept as documentation of the off switch."""
        cfg = dataclasses.replace(_cfg("paper_e5m2"), fuse_epilogue=False)
        _, _, reg, ds = _site_bundle(cfg)
        a = jax.random.normal(jax.random.PRNGKey(0), (4, 8, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.1
        state = ds.init()

        def step(a, b, tokens):
            def loss(a, b, tokens):
                with ds.collect(state, tokens):
                    y = qeinsum("bsk,kn->bsn", a, b,
                                key=jax.random.PRNGKey(2), cfg=cfg, site="s")
                    sc.drain_aux()
                return y.astype(jnp.float32).sum()
            return jax.grad(loss, argnums=(0, 1, 2))(a, b, tokens)

        counts = _count_prims(jax.make_jaxpr(step)(
            a, b, ds.zero_tokens()).jaxpr)
        assert counts["pallas"] == 1, counts
        assert counts["outside_dot"] >= 2, counts

    def test_attention_specs_not_fused(self):
        cfg = _cfg("paper_e5m2")
        assert not _fused_epilogue("bhqd,bhkd->bhqk", ("act", "act"), cfg)
        assert _fused_epilogue("bsk,kn->bsn", ("act", "weight"), cfg)
        assert not _fused_epilogue(
            "bsk,kn->bsn", ("act", "weight"),
            dataclasses.replace(cfg, scaling="none"))
        assert not _fused_epilogue(
            "bsk,kn->bsn", ("act", "weight"),
            dataclasses.replace(cfg, backend="xla"))


# ---------------------------------------------------------------------------
# 2 + 3. bit parity with the unfused composition; observations == _observe
# ---------------------------------------------------------------------------

def _bits(x):
    return np.asarray(x).view(np.uint8)


class TestFusedParity:
    @pytest.mark.parametrize("recipe", ["paper_e5m2", "hybrid"])
    def test_qeinsum_bit_matches_unfused_composition(self, recipe):
        """Fused fwd/dgrad/wgrad outputs, grads and amax observations all
        bit-match the quantize->matmul composition (ref oracle) built from
        the same operands, scales and SR bits."""
        cfg = _cfg(recipe)
        keys_, fkeys, reg, ds = _site_bundle(cfg)
        a = jax.random.normal(jax.random.PRNGKey(0), (3, 8, 32))
        b = jax.random.normal(jax.random.PRNGKey(1), (32, 16)) * 0.1
        key = jax.random.PRNGKey(7)

        state = ds.init()
        _run_step.state = state
        _, _, obs0 = _run_step(ds, cfg, a, b, key)   # warmup: scales <- amax
        state = ds.update(state, obs0)
        _run_step.state = state
        y, (ga, gb), obs = _run_step(ds, cfg, a, b, key)
        scales = ds.scales_dict(state)

        # ---- reference: unfused quantize -> matmul -> quantize composition
        k_a, k_b, k_bwd, k_y = jax.random.split(key, 4)
        k_e, k_da, k_db = jax.random.split(k_bwd, 3)
        s_a, s_b = scales[keys_["a"]], scales[keys_["b"]]
        s_e, s_g = scales[keys_["E"]], scales[keys_["G"]]
        s_y, s_err = scales[fkeys["y"]], scales[fkeys["err"]]
        qa = _quant_operand(a, ACT, cfg, k_a, scale=jnp.float32(s_a))
        qb = _quant_operand(b, WEIGHT, cfg, k_b, scale=jnp.float32(s_b))
        a2 = qa.data.reshape((-1, 32))
        m = a2.shape[0]

        def ref_gemm(x8, w8, sx, sw, s_out, rkey, cls, dims, mn):
            kscale = jnp.float32(s_out) / (sx * sw).astype(jnp.float32)
            rand8 = jax.random.bits(rkey, mn, jnp.uint8) \
                if cfg.rounding_for(cls) == "sr" \
                else jnp.zeros(mn, jnp.uint8)
            q, amax = fused_quant_matmul_ref(
                x8, w8, rand8, kscale.reshape((1,)), dims=dims,
                out_format=cfg.format_for(cls),
                rounding=cfg.rounding_for(cls),
                saturate=cfg.saturate_for(cls), with_amax=True)
            deq = (q.astype(jnp.float32) * jnp.float32(s_out)) \
                .astype(jnp.bfloat16)
            return q, deq, amax * jnp.float32(s_out)

        # fwd: Y = Q_A(A.W)
        y8, y_ref, amax_y = ref_gemm(a2, qb.data, qa.scale, qb.scale, s_y,
                                     k_y, ACT, "nn", (m, 16))
        np.testing.assert_array_equal(
            _bits(y), _bits(y_ref.reshape(y.shape)))
        # bwd: dy = ones (cotangent of .sum()); E-quantized as usual
        dy = jnp.ones((3, 8, 16), jnp.bfloat16)
        qdy = _quant_operand(dy, ERROR, cfg, k_e, scale=jnp.float32(s_e))
        dy2 = qdy.data.reshape((-1, 16))
        # dgrad: dA = Q_E(dY . W^T)
        da8, da_ref, amax_da = ref_gemm(dy2, qb.data, qdy.scale, qb.scale,
                                        s_err, k_da, ERROR, "nt", (m, 32))
        np.testing.assert_array_equal(
            _bits(ga), _bits(da_ref.reshape(a.shape).astype(a.dtype)))
        # wgrad: dW = Q_G(A^T . dY)
        db8, db_ref, amax_g = ref_gemm(a2, dy2, qa.scale, qdy.scale, s_g,
                                       k_db, GRAD, "tn", (32, 16))
        np.testing.assert_array_equal(
            _bits(gb), _bits(db_ref.astype(b.dtype)))

        # ---- observations: fused epilogue == _observe bit-pattern reduce
        # over the (bit-identical) materialized payloads. Exact f32 equality.
        expect = {
            fkeys["y"]: fp8_amax_bits(y8) * jnp.float32(s_y),
            fkeys["err"]: fp8_amax_bits(da8) * jnp.float32(s_err),
            keys_["G"]: fp8_amax_bits(db8) * jnp.float32(s_g),
            keys_["E"]: fp8_amax_bits(qdy.data) * qdy.scale,
            keys_["a"]: fp8_amax_bits(qa.data) * qa.scale,
            keys_["b"]: fp8_amax_bits(qb.data) * qb.scale,
        }
        for k, v in expect.items():
            assert np.float32(obs[k]).tobytes() == np.float32(v).tobytes(), k
        # and the fused-epilogue amaxes agree with the ref-side epilogue
        for got, want in [(obs[fkeys["y"]], amax_y),
                          (obs[fkeys["err"]], amax_da),
                          (obs[keys_["G"]], amax_g)]:
            assert float(got) == float(want)

    def test_weight_on_lhs(self):
        """classes=(weight, act): the error output flows to operand b
        ("#db.E") and the weight grad to operand a."""
        cfg = _cfg("hybrid")
        classes = ("weight", "act")
        fkeys = sc.fused_output_keys("s", classes)
        assert fkeys["err"] == "s#db.E"
        keys_ = sc.operand_keys("s", classes)
        reg = SiteRegistry(list(keys_.values()) + list(fkeys.values()),
                           ("s",))
        ds = DelayedScaling(reg, ScalingConfig(), qcfg=cfg)
        w = jax.random.normal(jax.random.PRNGKey(0), (8, 32)) * 0.1
        x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
        state = ds.init()

        def loss(w, x, tokens):
            with ds.collect(state, tokens):
                y = qeinsum("mk,kn->mn", w, x, key=jax.random.PRNGKey(2),
                            cfg=cfg, classes=classes, site="s")
                aux = sc.drain_aux()
            return y.astype(jnp.float32).sum(), aux

        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(w, x, ds.zero_tokens())
        obs = split_observations(dict(aux), grads[2], reg)
        assert "s#db.E" in obs and "s#G" in obs and "s#y.A" in obs
        assert all(np.isfinite(np.asarray(v)).all() for v in obs.values())


# ---------------------------------------------------------------------------
# ops-level: tiling invariance of SR bits + masked amax (padding bugfix)
# ---------------------------------------------------------------------------

class TestTilingInvariance:
    @pytest.mark.parametrize("dims,ash,bsh", [
        ("nn", (40, 200), (200, 130)),
        ("nt", (40, 200), (130, 200)),
        ("tn", (200, 40), (200, 130)),
    ])
    @pytest.mark.parametrize("rounding", ["rne", "sr"])
    def test_output_and_amax_invariant_to_blocks(self, dims, ash, bsh,
                                                 rounding):
        """Padding used to draw SR bits over the PADDED shape and scan dead
        tiles in the amax epilogue, making results depend on (bm, bk, bn).
        Now rand bits are drawn on the logical (m, n) and padding is masked
        out of the amax."""
        a = (jax.random.normal(jax.random.PRNGKey(0), ash) * 0.25).astype(
            jnp.float8_e5m2)
        b = (jax.random.normal(jax.random.PRNGKey(1), bsh) * 0.1).astype(
            jnp.float8_e5m2)
        key = jax.random.PRNGKey(2)
        outs = []
        for blocks in [(32, 128, 128), (64, 256, 256), (8, 512, 128)]:
            bm, bk, bn = blocks
            y, amax = fused_quant_matmul(
                a, b, key, jnp.array([2.0]), dims=dims, bm=bm, bk=bk, bn=bn,
                rounding=rounding, with_amax=True, amax_units="grid",
                interpret=True)
            outs.append((np.asarray(y).view(np.uint8), float(amax)))
        for o, am in outs[1:]:
            np.testing.assert_array_equal(o, outs[0][0])
            assert am == outs[0][1]

    def test_sr_bits_match_logical_draw(self):
        """The SR bits consumed for logical cells are exactly
        jax.random.bits(key, (m, n)) — independent of padding — so the
        fused output bit-matches the ref composition on awkward shapes."""
        m, k, n = 36, 130, 70
        a = (jax.random.normal(jax.random.PRNGKey(0), (m, k)) * 0.25).astype(
            jnp.float8_e5m2)
        b = (jax.random.normal(jax.random.PRNGKey(1), (k, n)) * 0.1).astype(
            jnp.float8_e5m2)
        key = jax.random.PRNGKey(3)
        y = fused_quant_matmul(a, b, key, jnp.array([1.5]), bm=32, bk=128,
                               bn=128, rounding="sr", interpret=True)
        rand8 = jax.random.bits(key, (m, n), jnp.uint8)
        ref = fused_quant_matmul_ref(a, b, rand8, jnp.array([1.5]),
                                     rounding="sr")
        np.testing.assert_array_equal(np.asarray(y).view(np.uint8),
                                      np.asarray(ref).view(np.uint8))


# ---------------------------------------------------------------------------
# operand widening: bf16 operands on the fp8 grid, and the shape rule
# ---------------------------------------------------------------------------

def _special_payload(shape, fmt_name, key):
    """fp8 payload of random values with the format's edge cases planted:
    ±min_subnormal, a mid subnormal, ±max_normal, NaN and (e5m2) ±inf."""
    from repro.core.fp8_formats import get_format
    fmt = get_format(fmt_name)
    x = jax.random.normal(key, shape, jnp.float32) * 0.5
    specials = [fmt.min_subnormal, -fmt.min_subnormal,
                3 * fmt.min_subnormal, fmt.max_normal, -fmt.max_normal,
                float("nan")]
    if fmt_name == "e5m2":
        specials += [float("inf"), float("-inf")]
    rows = np.arange(len(specials)) * 3 % shape[0]
    cols = np.arange(len(specials)) * 7 % shape[1]
    x = x.at[rows, cols].set(jnp.array(specials, jnp.float32))
    return x.astype(fmt.dtype)


class TestOperandWidening:
    # (m, k, n) logical GEMM on a (2, 2, 2) grid at (32, 128, 128) tiles.
    M, K, N = 64, 256, 256
    SHAPES = {"nn": ((M, K), (K, N)), "nt": ((M, K), (N, K)),
              "tn": ((K, M), (K, N))}

    @pytest.mark.parametrize("widen", ["neither", "a", "b", "both"])
    @pytest.mark.parametrize("fmt", ["e4m3", "e5m2"])
    @pytest.mark.parametrize("dims", ["nn", "nt", "tn"])
    def test_bf16_operands_bit_match_fp8(self, dims, fmt, widen):
        """An operand handed to the kernel widened to bf16 gives the same
        output bytes, grid amax and health counts as the fp8 payload:
        bf16 holds every fp8 value (subnormals, ±max, inf, NaN)."""
        from repro.kernels.fused_quant_matmul import kernel as fk
        ash, bsh = self.SHAPES[dims]
        a8 = _special_payload(ash, fmt, jax.random.PRNGKey(0))
        b8 = _special_payload(bsh, fmt, jax.random.PRNGKey(1))
        rand8 = jax.random.bits(jax.random.PRNGKey(2), (self.M, self.N),
                                jnp.uint8)
        scale = jnp.array([4.0], jnp.float32)

        def run(a, b):
            return fk.fused_quant_matmul_kernel(
                a, b, rand8, scale, dims=dims, bm=32, bk=128, bn=128,
                out_format="e5m2", rounding="sr", saturate=False,
                with_amax=True, with_counts=True, interpret=True)

        a = a8.astype(jnp.bfloat16) if widen in ("a", "both") else a8
        b = b8.astype(jnp.bfloat16) if widen in ("b", "both") else b8
        got, want = run(a, b), run(a8, b8)
        q, q0 = np.asarray(got[0]), np.asarray(want[0])
        # The edge cases reach the output: NaN rows, and inf from e5m2 inf.
        assert np.isnan(q0.astype(np.float32)).any()
        assert np.isinf(q0.astype(np.float32)).any() == (fmt == "e5m2")
        np.testing.assert_array_equal(q.view(np.uint8), q0.view(np.uint8))
        for g, w in zip(got[1:], want[1:]):            # amax, sat, flush
            np.testing.assert_array_equal(
                np.asarray(g).view(np.uint32), np.asarray(w).view(np.uint32))

    @pytest.mark.parametrize("case,dims,ash,bsh,want", [
        # training: A read once per column block, B once per row block
        ("train", "nn", (4096, 1536), (1536, 8960), (True, True)),
        # decode: M = 8 <= bm, so the weight is read once and stays fp8
        ("decode", "nn", (8, 1536), (1536, 8960), (True, False)),
        # k/v projection: N = 256 = bn, so A is read once and stays fp8
        ("kv", "nn", (4096, 1536), (1536, 256), (False, True)),
    ])
    def test_shape_rule_picks_widened_operands(self, case, dims, ash, bsh,
                                               want):
        """The operands enter pallas_call as bf16 exactly where the grid
        reads their tiles more than once, widened by a convert under the
        `fp8.quant` scope."""
        from repro.analysis.jaxpr_walk import iter_jaxprs
        from repro.kernels.fused_quant_matmul.ops import \
            fused_quant_matmul as fqm
        f8 = jnp.float8_e4m3fn
        jaxpr = jax.make_jaxpr(functools.partial(
            fqm, dims=dims, out_format="e4m3", with_amax=True))(
            jax.ShapeDtypeStruct(ash, f8), jax.ShapeDtypeStruct(bsh, f8),
            jax.ShapeDtypeStruct((2,), jnp.uint32),
            jax.ShapeDtypeStruct((1,), jnp.float32))
        calls = [(e, jx) for jx, _ in iter_jaxprs(jaxpr) for e in jx.eqns
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 1, case
        call, jx = calls[0]
        a_in, b_in = (v.aval.dtype for v in call.invars[:2])
        assert (a_in == jnp.bfloat16, b_in == jnp.bfloat16) == want, case
        assert {a_in, b_in} <= {jnp.dtype(jnp.bfloat16), jnp.dtype(f8)}
        producer = {id(o): e for e in jx.eqns for o in e.outvars}
        for v in call.invars[:2]:
            if v.aval.dtype == jnp.bfloat16:
                e = producer[id(v)]
                assert e.primitive.name == "convert_element_type", case
                assert "fp8.quant" in str(e.source_info.name_stack), case
