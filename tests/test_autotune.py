"""Block-size autotuner: winners-table round-trip, resolution precedence
(explicit > table > defaults), and bit-parity of every candidate block
config against the unfused oracles — tuning must only ever move wall-clock,
never a single bit of any observation site."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at
from repro.kernels.fp8_attention import (fp8_attention_bwd,
                                         fp8_attention_bwd_ref,
                                         fp8_attention_fwd,
                                         fp8_attention_fwd_ref)
from repro.kernels.fused_quant_matmul import (fused_quant_matmul,
                                              fused_quant_matmul_ref)


def _gemm_operands(m, k, n, fmt=jnp.float8_e5m2):
    a = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(fmt)
    b = jax.random.normal(jax.random.PRNGKey(1), (k, n)).astype(fmt)
    return a, b, jax.random.PRNGKey(2)


def _attn_operands(s, d, b=1, h=1):
    q8, k8, v8 = [(jax.random.normal(jax.random.PRNGKey(i), (b, h, s, d))
                   * 0.3).astype(jnp.float8_e5m2) for i in range(3)]
    do8 = (jax.random.normal(jax.random.PRNGKey(4), (b, h, s, d))
           * 0.2).astype(jnp.float8_e5m2)
    return q8, k8, v8, do8


# ---------------------------------------------------------------------------
# winners table: keys, persistence, cache
# ---------------------------------------------------------------------------

class TestTable:
    def test_bucket_keys_pow2(self):
        # Shapes bucket to the next power of two (min 8) so near-miss
        # shapes share an entry instead of each missing the table.
        assert at.gemm_key("nn", 100, 300, 130, "e5m2") == \
            at.gemm_key("nn", 128, 512, 256, "e5m2")
        assert at.attn_key("fwd", "causal", 200, 200, 64) == \
            at.attn_key("fwd", "causal", 256, 256, 64)
        assert at.gemm_key("nn", 64, 128, 128, "e5m2") != \
            at.gemm_key("nt", 64, 128, 128, "e5m2")

    def test_save_load_round_trip(self, tmp_path):
        p = tmp_path / "table.json"
        table = {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                 {"bm": 32, "bk": 128, "bn": 128}}
        at.save_table(p, table)
        assert at.load_table(p) == table
        # save invalidates the mtime cache: a second save is visible.
        table2 = dict(table)
        table2[at.attn_key("fwd", "causal", 256, 256, 64)] = \
            {"block_q": 64, "block_kv": 128}
        at.save_table(p, table2)
        assert at.load_table(p) == table2

    def test_malformed_table_ignored(self, tmp_path):
        p = tmp_path / "broken.json"
        p.write_text("{not json")
        assert at.load_table(p) == {}
        bm, bk, bn = at.resolve_gemm_blocks(
            "nn", 64, 128, 128, out_format="e5m2",
            autotune=str(p), defaults=(256, 512, 256))
        assert (bm, bk, bn) == (256, 512, 256)

    def test_env_var_points_resolution_at_table(self, tmp_path,
                                                monkeypatch):
        p = tmp_path / "env_table.json"
        at.save_table(p, {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                          {"bm": 32, "bk": 128, "bn": 128}})
        monkeypatch.setenv(at.ENV_VAR, str(p))
        assert at.table_path("table") == p
        assert at.resolve_gemm_blocks(
            "nn", 64, 128, 128, out_format="e5m2", autotune="table",
            defaults=(256, 512, 256)) == (32, 128, 128)


# ---------------------------------------------------------------------------
# resolution precedence: explicit > table > defaults, per knob
# ---------------------------------------------------------------------------

class TestResolvePrecedence:
    def test_gemm_explicit_beats_table(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                          {"bm": 32, "bk": 128, "bn": 128}})
        # Explicit bm wins; unset bk/bn still come from the table.
        assert at.resolve_gemm_blocks(
            "nn", 64, 128, 128, out_format="e5m2", bm=64,
            autotune=str(p), defaults=(256, 512, 256)) == (64, 128, 128)

    def test_gemm_off_pins_defaults(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                          {"bm": 32, "bk": 128, "bn": 128}})
        assert at.resolve_gemm_blocks(
            "nn", 64, 128, 128, out_format="e5m2", autotune="off",
            defaults=(256, 512, 256)) == (256, 512, 256)

    def test_gemm_invalid_table_entry_ignored(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                          {"bm": "huge", "bk": -4, "bn": 128}})
        assert at.resolve_gemm_blocks(
            "nn", 64, 128, 128, out_format="e5m2", autotune=str(p),
            defaults=(256, 512, 256)) == (256, 512, 128)

    def test_gemm_explicit_invalid_raises(self):
        with pytest.raises(ValueError):
            at.resolve_gemm_blocks("nn", 64, 128, 128, out_format="e5m2",
                                   bm=0, autotune="off",
                                   defaults=(256, 512, 256))

    def test_attn_fwd_table_consulted(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.attn_key("fwd", "causal", 256, 256, 64):
                          {"block_q": 64, "block_kv": 128}})
        assert at.resolve_attn_blocks(
            "fwd", "causal", 256, 256, 64, autotune=str(p)) == (64, 128)
        # Explicit knobs beat the table per-knob.
        assert at.resolve_attn_blocks(
            "fwd", "causal", 256, 256, 64, block_q=128,
            autotune=str(p)) == (128, 128)

    def test_attn_bwd_invalid_table_entry_ignored(self, tmp_path):
        # A table entry the bwd kernel cannot honor (block_q not a TQ
        # multiple) silently falls back to the default — table contents
        # must never make a launch raise.
        p = tmp_path / "t.json"
        at.save_table(p, {at.attn_key("bwd", "causal", 256, 256, 64):
                          {"block_q": 192, "block_kv": 128}})
        bq, bkv = at.resolve_attn_blocks("bwd", "causal", 256, 256, 64,
                                         autotune=str(p))
        assert bq == at.TQ and bkv == 128

    def test_attn_bwd_explicit_sub_tq_raises(self):
        # The silent `max(TQ, block_q)` clamp is gone: an explicit
        # request the kernel cannot honor is an error.
        with pytest.raises(ValueError, match="multiple of TQ"):
            at.resolve_attn_blocks("bwd", "causal", 256, 256, 64,
                                   block_q=64, autotune="off")

    def test_attn_fwd_explicit_invalid_raises(self):
        with pytest.raises(ValueError):
            at.resolve_attn_blocks("fwd", "causal", 256, 256, 64,
                                   block_q=192, autotune="off")


# ---------------------------------------------------------------------------
# ops consult the table; explicit knobs win; results are bit-invariant
# ---------------------------------------------------------------------------

class TestOpsConsultTable:
    def test_gemm_table_blocks_bit_match_explicit(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.gemm_key("nn", 64, 128, 128, "e5m2"):
                          {"bm": 32, "bk": 128, "bn": 128}})
        a, b, key = _gemm_operands(64, 128, 128)
        y_t, am_t = fused_quant_matmul(a, b, key, autotune=str(p),
                                       with_amax=True, interpret=True)
        y_e, am_e = fused_quant_matmul(a, b, key, bm=32, bk=128, bn=128,
                                       autotune="off", with_amax=True,
                                       interpret=True)
        y_d, am_d = fused_quant_matmul(a, b, key, autotune="off",
                                       with_amax=True, interpret=True)
        np.testing.assert_array_equal(np.asarray(y_t).view(np.uint8),
                                      np.asarray(y_e).view(np.uint8))
        np.testing.assert_array_equal(np.asarray(y_t).view(np.uint8),
                                      np.asarray(y_d).view(np.uint8))
        assert float(am_t) == float(am_e) == float(am_d)

    def test_attention_table_blocks_bit_match_default(self, tmp_path):
        p = tmp_path / "t.json"
        at.save_table(p, {at.attn_key("fwd", "causal", 256, 256, 64):
                          {"block_q": 64, "block_kv": 128}})
        q8, k8, v8, _ = _attn_operands(256, 64)
        scal = jnp.array([1.0, 1.0, 1.0, 1.0], jnp.float32)
        o_t, as_t, ap_t = fp8_attention_fwd(q8, k8, v8, 7, scal,
                                            autotune=str(p),
                                            interpret=True)
        o_d, as_d, ap_d = fp8_attention_fwd(q8, k8, v8, 7, scal,
                                            autotune="off",
                                            interpret=True)
        np.testing.assert_array_equal(np.asarray(o_t).view(np.uint16),
                                      np.asarray(o_d).view(np.uint16))
        assert float(as_t) == float(as_d) and float(ap_t) == float(ap_d)

    def test_sweep_winner_feeds_ops(self, tmp_path):
        # End to end: a (synthetic) sweep result saved via save_table is
        # what resolve hands the ops layer on the next call.
        p = tmp_path / "t.json"
        table = dict(at.load_table(p))
        table[at.gemm_key("nn", 256, 256, 256, "e5m2")] = \
            {"bm": 128, "bk": 256, "bn": 128, "wall_us": 1.0}
        at.save_table(p, table)
        assert at.resolve_gemm_blocks(
            "nn", 256, 256, 256, out_format="e5m2", autotune=str(p),
            defaults=(256, 512, 256)) == (128, 256, 128)


# ---------------------------------------------------------------------------
# parity sweep: every candidate bit-matches the oracle at every
# observation site (out/amax/health x fwd/bwd), both recipes
# ---------------------------------------------------------------------------

class TestCandidateParity:
    @pytest.mark.parametrize("out_format", ["e5m2", "e4m3"])
    def test_gemm_candidates_bit_match_oracle(self, out_format):
        m, k, n = 256, 256, 256
        a, b, key = _gemm_operands(m, k, n)
        scale = jnp.asarray([2.0], jnp.float32)
        rand8 = jax.random.bits(key, (m, n), jnp.uint8)
        ref, ref_amax = fused_quant_matmul_ref(
            a, b, rand8, scale, out_format=out_format, with_amax=True)
        cands = at.gemm_candidates(m, k, n, defaults=(256, 512, 256),
                                   smoke=True)
        assert len(cands) >= 2
        for bm, bk, bn in cands:
            out, amax, health = fused_quant_matmul(
                a, b, key, scale, bm=bm, bk=bk, bn=bn, autotune="off",
                out_format=out_format, with_amax=True, with_counts=True,
                interpret=True)
            np.testing.assert_array_equal(
                np.asarray(out).view(np.uint8),
                np.asarray(ref).view(np.uint8),
                err_msg=f"blocks ({bm},{bk},{bn})")
            assert float(amax) == pytest.approx(float(ref_amax) * 2.0)
            assert health.shape == (2,) and float(health[0]) >= 0.0

    @pytest.mark.parametrize("fmt", ["e5m2", "e4m3"])
    def test_attn_fwd_candidates_bit_match_oracle(self, fmt):
        s, d = 256, 64
        q8, k8, v8, _ = _attn_operands(s, d)
        scal = jnp.array([0.5, 2.0, 8.0, 0.125], jnp.float32)
        kw = dict(mask_mode="causal", fmt_s=fmt, fmt_p=fmt,
                  rounding_s="sr", rounding_p="sr")
        ro, ras, rap, _, _ = fp8_attention_fwd_ref(q8, k8, v8, 7, scal,
                                                   **kw)
        cands = at.attn_candidates("fwd", s, s, smoke=True)
        assert len(cands) >= 2
        for bq, bkv in cands:
            o, a_s, a_p, hs, hp = fp8_attention_fwd(
                q8, k8, v8, 7, scal, block_q=bq, block_kv=bkv,
                autotune="off", with_counts=True, interpret=True, **kw)
            np.testing.assert_array_equal(
                np.asarray(o).view(np.uint16),
                np.asarray(ro).view(np.uint16),
                err_msg=f"blocks (q={bq}, kv={bkv})")
            assert float(a_s) == float(ras) and float(a_p) == float(rap)
            assert hs.shape == (2,) and hp.shape == (2,)

    @pytest.mark.parametrize("fmt", ["e5m2", "e4m3"])
    def test_attn_bwd_candidates_bit_match_oracle(self, fmt):
        s, d = 256, 64
        q8, k8, v8, do8 = _attn_operands(s, d)
        scal = jnp.array([0.5, 2.0, 8.0, 0.125, 0.7, 1.5, 0.3, 0.8, 0.9,
                          0.05], jnp.float32)
        kw = dict(mask_mode="causal", fmt_s=fmt, fmt_p=fmt, fmt_e="e5m2",
                  rounding_s="sr", rounding_p="sr", rounding_e="sr",
                  saturate_e=False)
        refs = fp8_attention_bwd_ref(q8, k8, v8, do8, 7, scal, **kw)
        cands = at.attn_candidates("bwd", s, s, smoke=True)
        assert len(cands) >= 1
        for bq, bkv in cands:
            outs = fp8_attention_bwd(
                q8, k8, v8, do8, 7, scal, block_q=bq, block_kv=bkv,
                autotune="off", with_counts=True, interpret=True, **kw)
            for g, r, name in zip(outs[:3], refs[:3], ("dq", "dk", "dv")):
                np.testing.assert_array_equal(
                    np.asarray(g), np.asarray(r),
                    err_msg=f"{name} blocks (q={bq}, kv={bkv})")
            assert float(outs[3]) == float(refs[3])
            assert float(outs[4]) == float(refs[4])
            assert outs[5].shape == (2,) and outs[6].shape == (2,)


# ---------------------------------------------------------------------------
# policy knob + launch meta
# ---------------------------------------------------------------------------

class TestPolicyWiring:
    def test_quantconfig_autotune_off_bit_matches_table(self):
        # The policy-level autotune knob reaches the attention kernel and
        # never changes bits — only schedule.
        import dataclasses

        from repro.core.precision_policy import QuantConfig
        cfg = QuantConfig(recipe="paper_e5m2")
        assert cfg.autotune == "table"
        off = dataclasses.replace(cfg, autotune="off")
        assert off.attn_block_q is None and off.attn_block_kv is None

    def test_build_cell_meta_records_resolved_blocks(self, monkeypatch):
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
                cell_off = S.build_cell(
                    "qwen2-1.5b", "tiny_train", mesh,
                    overrides={"policy.quant.autotune": "off"})
        finally:
            S._cfg_for_cell.cache_clear()
        # Resolved schedule is visible in the launch meta for both paths.
        assert cell["meta"]["autotune"] == "table"
        assert cell["meta"]["attn_block_q"] >= 1
        assert cell["meta"]["attn_block_kv"] % 128 == 0
        assert cell_off["meta"]["autotune"] == "off"
        assert cell_off["meta"]["attn_block_q"] >= 1
