"""Precision-flow static analyzer: jaxpr traversal, VMEM model, lint
passes, and their wiring into the autotuner and spec builder.

The negative paths matter most here — a lint that can't fail is
decoration.  Each pass gets a test that plants the defect it exists to
catch (unfused fallback, unregistered/dead scale site, double-rounding
chain, oversized blocks) and asserts the expected finding comes out.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jaxpr_walk as jw
from repro.analysis import precision_lint as pl
from repro.analysis import vmem as vm


# ---------------------------------------------------------------------------
# jaxpr_walk: the canonical traversal
# ---------------------------------------------------------------------------

class TestJaxprWalk:
    def test_counts_through_scan(self):
        def f(x):
            return jax.lax.scan(lambda c, _: (c @ c, None), x, None,
                                length=3)[0]
        counts = jw.count_prims(jax.make_jaxpr(f)(jnp.ones((4, 4))))
        assert counts == {"pallas": 0, "outside_dot": 1}

    def test_all_eqns_sees_nested(self):
        def f(x):
            return jax.lax.cond(x.sum() > 0, lambda v: v * 2,
                                lambda v: v + 1, x)
        names = [e.primitive.name
                 for e in jw.all_eqns(jax.make_jaxpr(f)(jnp.ones(3)))]
        assert "cond" in names
        assert "mul" in names and "add" in names   # branch bodies walked

    def test_is_f8_rejects_uint8(self):
        assert jw.is_f8(jnp.float8_e5m2)
        assert jw.is_f8(jnp.float8_e4m3fn)
        assert not jw.is_f8(jnp.uint8)
        assert not jw.is_f8(jnp.bfloat16)

    def test_dtype_census(self):
        def f(x):
            return x.astype(jnp.float8_e5m2)
        census = jw.dtype_census(jax.make_jaxpr(f)(jnp.ones(8)))
        assert census["float8_e5m2"] == 1


# ---------------------------------------------------------------------------
# vmem: the analytic model
# ---------------------------------------------------------------------------

class TestVmemModel:
    def test_monotone_in_blocks(self):
        small = vm.gemm_vmem(128, 128, 128).total_bytes
        big = vm.gemm_vmem(256, 512, 256).total_bytes
        assert big > small
        assert vm.attn_vmem("fwd", 128, 512, 64).total_bytes \
            < vm.attn_vmem("fwd", 128, 1024, 64).total_bytes

    def test_defaults_fit(self):
        """Every built-in default block config must fit the model — the
        autotuner never prunes the default, so the model has to agree."""
        from repro.kernels.fused_quant_matmul import kernel as fk
        assert vm.gemm_vmem(fk.DEFAULT_BM, fk.DEFAULT_BK,
                            fk.DEFAULT_BN).fits
        for kind in ("fwd", "bwd"):
            assert vm.attn_vmem(kind, 128, 512, 128).fits

    def test_bwd_is_worst_case_over_kernels(self):
        est = vm.attn_vmem("bwd", 128, 512, 128)
        parts = (vm.attn_bwd_dq_vmem(128, 512, 128),
                 vm.attn_bwd_dkv_vmem(128, 512, 128))
        assert est.total_bytes == max(p.total_bytes for p in parts)

    def test_check_raises_with_modeled_footprint(self):
        with pytest.raises(ValueError) as ei:
            vm.check_attn_blocks(128, 32768, 128)
        msg = str(ei.value)
        est = vm.attn_fwd_vmem(128, 32768, 128)
        assert str(est.total_bytes) in msg      # the modeled bytes
        assert "attn_block_kv" in msg           # and the knob to shrink

    def test_prune_records_what_and_why(self):
        kept, pruned = vm.prune_attn_candidates(
            "bwd", [(128, 128), (128, 32768)], 128)
        assert kept == [(128, 128)]
        assert len(pruned) == 1
        assert pruned[0]["blocks"] == [128, 32768]
        assert pruned[0]["vmem_bytes"] > pruned[0]["budget_bytes"]
        assert "reason" in pruned[0]

    def test_budget_override(self):
        assert not vm.gemm_vmem(256, 512, 256, budget=1024).fits
        assert vm.gemm_vmem(256, 512, 256).fits

    def test_gemm_counts_each_operand_at_its_width(self):
        """A bf16 operand block costs twice its fp8 bytes, in both DMA
        buffers; the rest of the footprint does not move."""
        base = vm.gemm_vmem(256, 512, 128)
        wa = vm.gemm_vmem(256, 512, 128, a_bytes=2)
        wb = vm.gemm_vmem(256, 512, 128, b_bytes=2)
        both = vm.gemm_vmem(256, 512, 128, a_bytes=2, b_bytes=2)
        assert wa.total_bytes - base.total_bytes == vm.DMA_BUF * 256 * 512
        assert wb.total_bytes - base.total_bytes == vm.DMA_BUF * 512 * 128
        assert both.total_bytes - base.total_bytes \
            == vm.DMA_BUF * (256 * 512 + 512 * 128)
        assert both.parts["out_blocks_x2"] == base.parts["out_blocks_x2"]

    @pytest.mark.parametrize("mn,blocks,want", [
        ((4096, 8960), (256, 256), (2, 2)),    # both read many times
        ((8, 8960), (8, 256), (2, 1)),         # decode: weight read once
        ((4096, 256), (256, 256), (1, 2)),     # k/v: A read once
        ((256, 256), (256, 256), (1, 1)),      # a single output tile
    ])
    def test_operand_bytes_follow_the_widening_rule(self, mn, blocks, want):
        assert vm.gemm_operand_bytes(*mn, *blocks) == want

    def test_prune_sees_widened_tiles(self):
        """A candidate that fits with fp8 operands but not once both are
        widened is pruned when the sweep passes the call's (m, n)."""
        cand = (512, 1024, 512)
        fp8 = vm.gemm_vmem(*cand).total_bytes
        wide = vm.gemm_vmem(*cand, a_bytes=2, b_bytes=2).total_bytes
        budget = (fp8 + wide) // 2
        kept, _ = vm.prune_gemm_candidates([cand], budget=budget)
        assert kept == [cand]
        kept, pruned = vm.prune_gemm_candidates([cand], mn=(4096, 4096),
                                                budget=budget)
        assert kept == [] and pruned[0]["vmem_bytes"] == wide


# ---------------------------------------------------------------------------
# autotune wiring: the sweep never times a pruned candidate
# ---------------------------------------------------------------------------

class TestAutotunePrefilter:
    def test_sweep_skips_pruned_candidates(self, monkeypatch):
        """With a tiny budget every non-default candidate is pruned: the
        report row records them and the timed `candidates` dict contains
        only the default."""
        from repro.kernels import autotune as at
        monkeypatch.setattr(vm, "VMEM_BYTES", 1)
        timed = []
        monkeypatch.setattr(
            at, "_bench", lambda fn, *a, **k: timed.append(1) or 1.0)
        table, report = at.sweep_gemm(shapes=[(256, 256, 256)],
                                      dims_list=("nn",), smoke=True,
                                      parity=False, log=lambda *a: None)
        row = report[0]
        assert len(row["candidates"]) == 1          # default only
        assert len(timed) == 1                      # one timing, not N
        assert row["pruned"], "pruned candidates must be recorded"
        for p in row["pruned"]:
            assert p["vmem_bytes"] > p["budget_bytes"] == 1
            blocks = "x".join(str(b) for b in p["blocks"])
            assert blocks not in row["candidates"]

    def test_sweep_attention_records_pruned(self, monkeypatch):
        from repro.kernels import autotune as at
        monkeypatch.setattr(vm, "VMEM_BYTES", 1)
        monkeypatch.setattr(at, "_bench", lambda fn, *a, **k: 1.0)
        monkeypatch.setattr(at, "_attn_parity",
                            lambda *a, **k: None)
        table, report = at.sweep_attention(shapes=[(256, 64)],
                                           kinds=("fwd",), smoke=True,
                                           parity=False,
                                           log=lambda *a: None)
        row = report[0]
        assert row["pruned"]                        # everything pruned
        assert list(row["candidates"]) \
            == [f"q{row['block_q']}_kv{row['block_kv']}"]  # default only

    def test_normal_budget_prunes_nothing_small(self):
        from repro.kernels import autotune as at
        kept, pruned = vm.prune_gemm_candidates(
            at.gemm_candidates(256, 256, 256,
                               defaults=(256, 512, 256), smoke=True))
        assert not pruned


# ---------------------------------------------------------------------------
# spec builder: oversized explicit knobs rejected at build time
# ---------------------------------------------------------------------------

def _smoke_specs(monkeypatch):
    import repro.launch.specs as S
    import repro.models.registry as R
    orig = R.build_config
    monkeypatch.setattr(
        R, "build_config",
        lambda a, smoke=False, **kw: orig(a, smoke=True, **kw))
    monkeypatch.setattr(S, "build_config", R.build_config)
    monkeypatch.setitem(S.SHAPES, "tiny_train",
                        dict(seq=64, batch=8, mode="train"))
    S._cfg_for_cell.cache_clear()
    return S


class TestSpecsVmemGate:
    def test_oversized_explicit_bkv_rejected(self, monkeypatch):
        S = _smoke_specs(monkeypatch)
        from repro.launch.mesh import make_mesh
        # resolve_block_kv caps bkv at the (padded) seq len, so shrink
        # the budget instead of inflating the knob past the cap.
        monkeypatch.setattr(vm, "VMEM_BYTES", 1)
        try:
            mesh = make_mesh((1, 1), ("data", "model"))
            with pytest.raises(ValueError, match="VMEM"):
                S.build_cell("qwen2-1.5b", "tiny_train", mesh,
                             overrides={"policy.quant.attn_block_kv": 128})
        finally:
            S._cfg_for_cell.cache_clear()

    def test_resolved_defaults_not_gated(self, monkeypatch):
        """No explicit knobs -> no VMEM gate on the resolved schedule
        (the autotuner table owns those; the lint's vmem_fit pass still
        checks them)."""
        S = _smoke_specs(monkeypatch)
        from repro.launch.mesh import make_mesh
        monkeypatch.setattr(vm, "VMEM_BYTES", 1)
        try:
            mesh = make_mesh((1, 1), ("data", "model"))
            with jax.set_mesh(mesh):
                cell = S.build_cell("qwen2-1.5b", "tiny_train", mesh)
            assert "attn_block_q" in cell["meta"]
        finally:
            S._cfg_for_cell.cache_clear()

    def test_cell_config_matches_build_overrides(self, monkeypatch):
        S = _smoke_specs(monkeypatch)
        try:
            cfg = S.cell_config(
                "qwen2-1.5b", "tiny_train",
                overrides={"policy.quant.recipe": "hybrid",
                           "policy.quant.scaling": "delayed"})
            assert cfg.policy.quant.recipe == "hybrid"
            assert cfg.policy.quant.scaling == "delayed"
        finally:
            S._cfg_for_cell.cache_clear()


# ---------------------------------------------------------------------------
# lint passes: negative paths
# ---------------------------------------------------------------------------

def _tiny_lint_setup(monkeypatch):
    S = _smoke_specs(monkeypatch)
    from repro.launch.mesh import make_mesh
    return S, make_mesh((1, 1), ("data", "model"))


BASE_OV = {"policy.quant.scaling": "delayed",
           "policy.quant.backend": "pallas"}


class TestLintPasses:
    def test_clean_cell_no_errors(self, monkeypatch):
        """The tiny delayed cell lints clean under both recipes — the
        same invariant the CI gate enforces over the full zoo."""
        S, mesh = _tiny_lint_setup(monkeypatch)
        try:
            for recipe in ("paper_e5m2", "hybrid"):
                fs = pl.lint_cell(
                    "qwen2-1.5b", "tiny_train", mesh,
                    overrides={**BASE_OV, "policy.quant.recipe": recipe})
                errs = [f for f in fs if f.severity == "error"]
                assert not errs, [f.message for f in errs]
        finally:
            S._cfg_for_cell.cache_clear()

    def test_fuse_epilogue_off_yields_fallback_finding(self, monkeypatch):
        S, mesh = _tiny_lint_setup(monkeypatch)
        try:
            fs = pl.lint_cell(
                "qwen2-1.5b", "tiny_train", mesh,
                overrides={**BASE_OV, "policy.quant.recipe": "hybrid",
                           "policy.quant.fuse_epilogue": False})
        finally:
            S._cfg_for_cell.cache_clear()
        hits = [f for f in fs if f.pass_name == "fused_coverage"
                and "fuse_epilogue" in f.message]
        assert hits and hits[0].severity == "warning"

    def test_tampered_registry_fails_bijection(self, monkeypatch):
        """Dropping a registered site and adding a bogus one must each
        produce a site_bijection error."""
        S, mesh = _tiny_lint_setup(monkeypatch)
        import repro.launch.specs as _S
        from repro.scaling.calibrate import discover_lm_sites
        from repro.scaling.state import SiteRegistry
        try:
            cfg = S.cell_config(
                "qwen2-1.5b", "tiny_train",
                overrides={**BASE_OV, "policy.quant.recipe": "hybrid"})
            info = S.SHAPES["tiny_train"]
            from repro.models.transformer import init_lm
            params_s = jax.eval_shape(
                lambda: init_lm(jax.random.PRNGKey(0), cfg))
            batch_s = _S._token_batch(cfg, info["batch"], info["seq"],
                                      labels=True)
            good = discover_lm_sites(cfg, params_s, batch_s)
            fwd = [k for k in good.keys
                   if good.class_letter(k) in ("W", "A")]
            keys = [k for k in good.keys if k != fwd[0]] + ["bogus#siteW"]
            bad = SiteRegistry(
                keys, token_sites=good.token_sites,
                site_layers={k: n for k, n in good.n_rows.items()
                             if k in keys},
                token_site_layers=good.token_site_layers)
            fs = pl.site_passes(cfg, params_s, batch_s, "tampered",
                                registry=bad)
        finally:
            S._cfg_for_cell.cache_clear()
        msgs = [f.message for f in fs if f.pass_name == "site_bijection"
                and f.severity == "error"]
        assert any("unregistered" in m and fwd[0] in m for m in msgs), msgs
        assert any("dead" in m and "bogus#siteW" in m for m in msgs), msgs

    def test_double_rounding_detected(self):
        def bad(x):
            return x.astype(jnp.bfloat16).astype(jnp.float8_e5m2)
        jaxpr = jax.make_jaxpr(bad)(jnp.ones((8,), jnp.float32))
        fs = pl.double_rounding_pass(jaxpr, "toy")
        assert len(fs) == 1 and fs[0].severity == "error"
        assert fs[0].data["chain"] == ["float32", "bfloat16",
                                       "float8_e5m2"]

    def test_quantizer_is_single_rounding(self):
        """The real quantizer must NOT trip the double-rounding pass."""
        from repro.core.fp8_formats import E5M2
        from repro.core.quantize import quantize_rne
        jaxpr = jax.make_jaxpr(
            lambda x: quantize_rne(x, E5M2))(jnp.ones((8, 8), jnp.float32))
        assert pl.double_rounding_pass(jaxpr, "quantize_rne") == []

    def test_vmem_fit_flags_oversized_meta(self):
        from repro.launch.specs import cell_config
        cfg = cell_config("paper-transformer", "train_4k",
                          overrides={**BASE_OV,
                                     "policy.quant.recipe": "hybrid"})
        meta = {"mode": "train", "fuse_attention": True,
                "attn_block_q": 128, "attn_block_kv": 32768,
                "head_dim": 128, "seq": 4096, "batch": 32,
                "n_microbatches": 4, "d_model": cfg.d_model,
                "d_ff": cfg.d_ff}
        fs = pl.vmem_fit_pass(cfg, meta, "toy")
        assert any(f.pass_name == "vmem_fit" and f.severity == "error"
                   for f in fs)


# ---------------------------------------------------------------------------
# suppressions + report plumbing
# ---------------------------------------------------------------------------

class TestSuppressions:
    def test_downgrade_and_mark(self):
        f = pl.Finding("fused_coverage", "error", "a/b@hybrid", "boom x1")
        rules = [{"pass": "fused_coverage", "cell": "a/*",
                  "match": "boom", "max_severity": "warning",
                  "reason": "known fallback"}]
        out = pl.apply_suppressions([f], rules)
        assert out[0].severity == "warning" and out[0].suppressed
        assert out[0].suppressed_by == "known fallback"

    def test_never_upgrades_and_respects_cell_glob(self):
        f1 = pl.Finding("p", "info", "a/b@x", "m")
        f2 = pl.Finding("p", "error", "other/b@x", "m")
        rules = [{"pass": "p", "cell": "a/*", "max_severity": "warning",
                  "reason": "r"}]
        out = pl.apply_suppressions([f1, f2], rules)
        assert out[0].severity == "info" and not out[0].suppressed
        assert out[1].severity == "error" and not out[1].suppressed

    def test_rule_without_reason_rejected(self, tmp_path):
        p = tmp_path / "sup.json"
        p.write_text(json.dumps({"rules": [{"pass": "p"}]}))
        with pytest.raises(ValueError, match="reason"):
            pl.load_suppressions(p)

    def test_shipped_suppressions_load(self):
        for r in pl.load_suppressions():
            assert r["reason"]

    def test_markdown_report(self):
        fs = [pl.Finding("f8_payload", "error", "a/b@hybrid", "msg|pipe")]
        md = pl.to_markdown(fs)
        assert "a/b@hybrid" in md and "msg\\|pipe" in md
        assert "1 error(s)" in md
