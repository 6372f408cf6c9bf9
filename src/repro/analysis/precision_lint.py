"""Precision-flow lint over the jitted train/serve step jaxprs of a cell.

The paper's recipe only works if *every* W/A/E/G tensor actually flows
through the FP8 quantize/scale machinery — a single silent XLA-dot
fallback or unregistered scale site degrades to bf16 training without
any test failing (PR 3 found exactly this: both projection adjoints fell
back silently).  These passes turn the invariants the test suite proves
on toy steps into repo-wide checked laws over every config-zoo cell:

  fused_coverage   no `dot_general` outside `pallas_call` when the fused
                   predicates hold; remaining outside-dots are classified
                   (logits head / MoE experts / recurrent blocks /
                   unfused-by-config) and anything unexplained is an
                   ERROR.
  f8_payload       every pallas_call touches a real f8 dtype (uint8
                   bit-carriers don't count); the recipe's formats
                   actually appear (hybrid => e4m3fn AND e5m2; paper =>
                   e5m2 only); fp8-wire cells carry f8 payloads on their
                   collectives.
  site_bijection   quantize-site <-> SiteRegistry bijection: every
                   observation in the collect-mode aux maps to a
                   registered site and every registered site is
                   observed (no unregistered or dead sites).
  token_width      backward-observation tokens carry exactly
                   `scale_ctx.token_width(track_health)` channels.
  double_rounding  no f32 -> bf16/f16 -> fp8 convert chains (two
                   rounding steps where the quantizer contract is one).
  vmem_fit         the cell's resolved attention/GEMM block configs fit
                   the analytic VMEM model (`analysis.vmem`).

Severities: `error` gates CI; `warning` marks known, ROADMAP-tracked
fallbacks; `info` is context.  A suppression file
(`lint_suppressions.json`, overridable via the CLI) downgrades findings
by (pass, cell-glob, message-substring) — every suppression carries a
reason and shows up in the report, so nothing is silently waived.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import json
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.analysis import jaxpr_walk as jw
from repro.analysis import vmem as vm

SEVERITY_RANK = {"error": 0, "warning": 1, "info": 2}
DEFAULT_SUPPRESSIONS = Path(__file__).with_name("lint_suppressions.json")


@dataclasses.dataclass
class Finding:
    pass_name: str
    severity: str
    cell: str
    message: str
    data: Dict[str, Any] = dataclasses.field(default_factory=dict)
    suppressed: bool = False
    suppressed_by: Optional[str] = None

    def to_dict(self) -> dict:
        out = dataclasses.asdict(self)
        return {k: v for k, v in out.items() if v not in (None, {})}


# ------------------------------------------------------------ suppressions
def load_suppressions(path=None) -> List[dict]:
    """Suppression rules: [{"pass": name-or-*, "cell": glob, "match":
    message-substring, "max_severity": downgrade-to, "reason": why}]."""
    p = Path(path) if path is not None else DEFAULT_SUPPRESSIONS
    if not p.exists():
        return []
    data = json.loads(p.read_text())
    rules = data.get("rules", []) if isinstance(data, dict) else data
    for r in rules:
        if "reason" not in r:
            raise ValueError(f"suppression rule without a reason: {r}")
    return rules


def apply_suppressions(findings: Sequence[Finding],
                       rules: Sequence[dict]) -> List[Finding]:
    """Downgrade matching findings to the rule's max_severity (default
    info) and mark them suppressed; never upgrades."""
    for f in findings:
        for r in rules:
            if r.get("pass", "*") not in ("*", f.pass_name):
                continue
            if not fnmatch.fnmatch(f.cell, r.get("cell", "*")):
                continue
            if r.get("match") and r["match"] not in f.message:
                continue
            cap = r.get("max_severity", "info")
            if SEVERITY_RANK[cap] > SEVERITY_RANK[f.severity]:
                f.severity = cap
                f.suppressed = True
                f.suppressed_by = r["reason"]
            break
    return list(findings)


# ------------------------------------------------------------------ passes
def _fused_gemm_on(q) -> bool:
    return bool(q.enabled and q.scaling == "delayed" and q.fuse_epilogue
                and q.backend.startswith("pallas"))


def _fused_attn_on(q) -> bool:
    from repro.core.qattention import fuse_attention
    return fuse_attention(q)


def _classify_outside_dot(eqn, cfg, q):
    """(kind, severity, why) for one dot_general outside any pallas
    kernel.  Known, policy- or ROADMAP-explained fallbacks classify as
    info/warning; anything unexplained is an error."""
    shapes = [tuple(v.aval.shape) for v in eqn.invars
              if hasattr(getattr(v, "aval", None), "shape")]
    dims_all = {d for s in shapes for d in s}
    if cfg.padded_vocab_size in dims_all:
        return ("logits_head", "info",
                "unquantized embedding/logits head "
                "(policy.quantize_logits_head=False — the paper keeps "
                "first/last layers at 16-bit)")
    if cfg.n_experts > 1 and cfg.n_experts in dims_all:
        return ("moe_expert_gemm", "warning",
                "MoE router/expert GEMM not yet on the fused FP8 path "
                "(ROADMAP: grouped/ragged FP8 expert GEMM)")
    if cfg.family in ("ssm", "hybrid"):
        return ("recurrent_inner_product", "warning",
                "recurrent-block inner product still unfused "
                "(ROADMAP: route rglru/mlstm through the fused kernels)")
    if not _fused_attn_on(q):
        return ("unfused_attention", "warning",
                "attention GEMM outside pallas (fuse_attention disabled "
                "or predicates unmet for this cell)")
    return ("unfused_gemm", "error",
            "dot_general outside pallas_call with the fused epilogue "
            "path enabled — a silent XLA fallback")


def fused_coverage_pass(jaxpr, cfg, meta, cell: str) -> List[Finding]:
    q = cfg.policy.quant
    findings: List[Finding] = []
    counts = jw.count_prims(jaxpr)
    if not _fused_gemm_on(q):
        if q.enabled and q.scaling == "delayed" and not q.fuse_epilogue:
            findings.append(Finding(
                "fused_coverage", "warning", cell,
                "fuse_epilogue=False: projection GEMMs and both adjoints "
                "run the unfused quantize->XLA-dot fallback "
                f"({counts['outside_dot']} dots outside pallas)",
                {"counts": counts}))
        return findings
    by_kind: Dict[str, Dict[str, Any]] = {}
    for eqn, inside in jw.iter_eqns(jaxpr):
        if inside or eqn.primitive.name != "dot_general":
            continue
        kind, sev, why = _classify_outside_dot(eqn, cfg, q)
        slot = by_kind.setdefault(kind, {"severity": sev, "why": why,
                                         "count": 0, "shapes": []})
        slot["count"] += 1
        if len(slot["shapes"]) < 4:
            slot["shapes"].append(
                [list(v.aval.shape) for v in eqn.invars
                 if hasattr(getattr(v, "aval", None), "shape")])
    for kind, slot in sorted(by_kind.items()):
        findings.append(Finding(
            "fused_coverage", slot["severity"], cell,
            f"{slot['count']} dot_general(s) outside pallas_call "
            f"[{kind}]: {slot['why']}",
            {"kind": kind, "count": slot["count"],
             "example_shapes": slot["shapes"]}))
    if counts["pallas"] == 0:
        findings.append(Finding(
            "fused_coverage", "error", cell,
            "fused predicates hold but the step contains no pallas_call "
            "at all — the entire cell fell back to XLA",
            {"counts": counts}))
    return findings


def f8_payload_pass(jaxpr, cfg, meta, cell: str) -> List[Finding]:
    q = cfg.policy.quant
    findings: List[Finding] = []
    for eqn, _ in jw.iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        if not jw.touches_f8(eqn):
            findings.append(Finding(
                "f8_payload", "error", cell,
                "pallas_call with no real f8-dtype operand or output — "
                "an FP8 kernel whose payloads are not actually FP8",
                {"out_dtypes": [str(v.aval.dtype) for v in eqn.outvars
                                if hasattr(getattr(v, "aval", None),
                                           "dtype")]}))
    if meta.get("mode") == "train" and q.enabled \
            and q.scaling == "delayed":
        census = jw.dtype_census(jaxpr)
        e4 = census.get("float8_e4m3fn", 0)
        e5 = census.get("float8_e5m2", 0)
        if q.recipe == "hybrid":
            if not e4:
                findings.append(Finding(
                    "f8_payload", "error", cell,
                    "hybrid recipe but no e4m3fn (W/A) payloads appear "
                    "in the train step", {"census_e4m3fn": e4}))
            if not e5:
                findings.append(Finding(
                    "f8_payload", "error", cell,
                    "hybrid recipe but no e5m2 (E/G) payloads appear "
                    "in the train step", {"census_e5m2": e5}))
        elif q.recipe == "paper_e5m2":
            if not e5:
                findings.append(Finding(
                    "f8_payload", "error", cell,
                    "paper_e5m2 recipe but no e5m2 payloads appear in "
                    "the train step", {"census_e5m2": e5}))
            if e4:
                findings.append(Finding(
                    "f8_payload", "error", cell,
                    "paper_e5m2 recipe lowered e4m3fn payloads — the "
                    "recipe label and the executed formats disagree",
                    {"census_e4m3fn": e4}))
    if meta.get("wire_bytes"):
        wire_prims = ("psum", "ppermute", "all_gather", "all_to_all",
                      "psum_scatter", "reduce_scatter")
        n_f8 = sum(1 for eqn, _ in jw.iter_eqns(jaxpr)
                   if eqn.primitive.name in wire_prims
                   and jw.touches_f8(eqn))
        if n_f8 == 0:
            findings.append(Finding(
                "f8_payload", "error", cell,
                "fp8-wire cell (dist.wire=fp8_ef) but no collective "
                "carries a real f8 payload", {"wire_prims": wire_prims}))
        else:
            findings.append(Finding(
                "f8_payload", "info", cell,
                f"{n_f8} collective(s) carry real f8 wire payloads",
                {"count": n_f8}))
    return findings


def double_rounding_pass(jaxpr, cell: str) -> List[Finding]:
    """Flag convert chains f32/f64 -> bf16/f16 -> fp8: the intermediate
    16-bit rounding loses mantissa bits before the fp8 rounding, so the
    result can differ from the single-rounding quantizer contract
    (core/quantize grids wide inputs in f32 precisely to avoid this)."""
    findings: List[Finding] = []
    wide = {"float32", "float64"}
    mid = {"bfloat16", "float16"}
    for jx, _ in jw.iter_jaxprs(jaxpr):
        producers = {}
        for eqn in jx.eqns:
            for ov in eqn.outvars:
                producers[ov] = eqn
        for eqn in jx.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            out_dt = eqn.outvars[0].aval.dtype
            if not jw.is_f8(out_dt):
                continue
            prod = producers.get(eqn.invars[0])
            if prod is None \
                    or prod.primitive.name != "convert_element_type":
                continue
            src_aval = getattr(prod.invars[0], "aval", None)
            if src_aval is None:
                continue
            src_dt, mid_dt = str(src_aval.dtype), str(
                prod.outvars[0].aval.dtype)
            if src_dt in wide and mid_dt in mid:
                findings.append(Finding(
                    "double_rounding", "error", cell,
                    f"double-rounding chain {src_dt} -> {mid_dt} -> "
                    f"{out_dt}: the 16-bit intermediate rounds before "
                    f"the fp8 rounding",
                    {"chain": [src_dt, mid_dt, str(out_dt)]}))
    return findings


def vmem_fit_pass(cfg, meta, cell: str) -> List[Finding]:
    """The cell's resolved kernel block configs must fit the analytic
    VMEM model — the same model the autotuner prunes candidates with and
    `launch/specs.py` rejects explicit knobs with."""
    q = cfg.policy.quant
    findings: List[Finding] = []
    if meta.get("fuse_attention") and "attn_block_q" in meta:
        bq, bkv, d = (meta["attn_block_q"], meta["attn_block_kv"],
                      meta["head_dim"])
        for kind in ("fwd", "bwd") if meta.get("mode") == "train" \
                else ("fwd",):
            est = vm.attn_vmem(kind, bq, bkv, d)
            if not est.fits:
                findings.append(Finding(
                    "vmem_fit", "error", cell,
                    f"resolved attention blocks do not fit: "
                    f"{est.describe()}", est.to_dict()))
    if meta.get("mode") == "train" and _fused_gemm_on(q):
        from repro.kernels import autotune as at
        from repro.kernels.fused_quant_matmul import kernel as _fk
        defaults = (_fk.DEFAULT_BM, _fk.DEFAULT_BK, _fk.DEFAULT_BN)
        tokens = meta["seq"] * meta["batch"] \
            // max(1, meta.get("n_microbatches", 1))
        for (m, k, n), dims in (((tokens, meta["d_model"], meta["d_ff"]),
                                 "nn"),
                                ((tokens, meta["d_ff"], meta["d_model"]),
                                 "nt"),
                                ((meta["d_model"], tokens, meta["d_ff"]),
                                 "tn")):
            bm, bk, bn = at.resolve_gemm_blocks(
                dims, m, k, n, autotune=q.autotune, defaults=defaults)
            bm, bk, bn = (min(bm, max(8, m)), min(bk, max(128, k)),
                          min(bn, max(128, n)))
            a_bytes, b_bytes = vm.gemm_operand_bytes(m, n, bm, bn)
            est = vm.gemm_vmem(bm, bk, bn, dims=dims, a_bytes=a_bytes,
                               b_bytes=b_bytes)
            if not est.fits:
                findings.append(Finding(
                    "vmem_fit", "error", cell,
                    f"resolved GEMM blocks for the {dims} projection "
                    f"shape ({m}, {k}, {n}) do not fit: "
                    f"{est.describe()}", est.to_dict()))
    return findings


def site_passes(cfg, params_s, batch_s, cell: str, *,
                registry=None) -> List[Finding]:
    """site_bijection + token_width over a delayed-scaling train cell.

    `registry` defaults to a fresh discovery trace (what build_cell
    runs with); tests inject a tampered registry to prove the pass
    fails on unregistered / dead sites."""
    from repro.models.transformer import lm_loss
    from repro.scaling import context as sc
    from repro.scaling.calibrate import discover_lm_sites
    from repro.scaling.state import DelayedScaling

    findings: List[Finding] = []
    fresh = discover_lm_sites(cfg, params_s, batch_s)
    reg = fresh if registry is None else registry
    for k in sorted(set(fresh.keys) - set(reg.keys)):
        findings.append(Finding(
            "site_bijection", "error", cell,
            f"quantize site observed in the step but absent from the "
            f"SiteRegistry (unregistered site): {k}", {"site": k}))
    for k in sorted(set(reg.keys) - set(fresh.keys)):
        findings.append(Finding(
            "site_bijection", "error", cell,
            f"registered site never observed by the step (dead site): "
            f"{k}", {"site": k}))

    ds = DelayedScaling(reg, qcfg=cfg.policy.quant)
    state = ds.init()
    tokens = ds.zero_tokens()

    def probe(p, t, b):
        with ds.collect(state, t):
            _, metrics = lm_loss(p, b, cfg=cfg, qkey=jax.random.PRNGKey(0))
        return metrics

    try:
        metrics_s = jax.eval_shape(probe, params_s, tokens, batch_s)
    except Exception as e:  # noqa: BLE001 — a failed collect trace IS a finding
        findings.append(Finding(
            "site_bijection", "error", cell,
            f"collect-mode trace failed: {type(e).__name__}: {e}"))
        return findings

    amax_keys = {k[len(sc.AMAX_PREFIX):] for k in metrics_s
                 if k.startswith(sc.AMAX_PREFIX)}
    fwd_reg = {k for k in reg.keys if reg.class_letter(k) in ("W", "A")}
    for k in sorted(amax_keys - fwd_reg):
        findings.append(Finding(
            "site_bijection", "error", cell,
            f"forward amax observation for a site the registry does not "
            f"carry (unregistered site): {k}", {"site": k}))
    for k in sorted(fwd_reg - amax_keys):
        findings.append(Finding(
            "site_bijection", "error", cell,
            f"registered forward site produced no amax observation "
            f"(dead site): {k}", {"site": k}))
    for s in sorted(reg.token_sites):
        if reg.token_uses.get(s, 0) <= 0:
            findings.append(Finding(
                "site_bijection", "error", cell,
                f"backward-observation token never used by the trace "
                f"(dead token site): {s}", {"site": s}))

    want = sc.token_width(cfg.policy.quant.track_health)
    for s, tok in sorted(tokens.items()):
        if tok.shape[-1] != want:
            findings.append(Finding(
                "token_width", "error", cell,
                f"token for site {s} carries {tok.shape[-1]} channels, "
                f"expected {want} "
                f"(track_health={cfg.policy.quant.track_health})",
                {"site": s, "width": int(tok.shape[-1]),
                 "expected": int(want)}))
    return findings


# ------------------------------------------------------------- cell driver
def lint_cell(arch: str, shape: str, mesh, *,
              overrides: Optional[Dict[str, Any]] = None,
              cell_id: Optional[str] = None) -> List[Finding]:
    """Build one (arch, shape) cell, trace its step jaxpr, and run every
    applicable pass.  A build or trace failure is itself an error
    finding — the lint never crashes the sweep."""
    from repro.launch import specs as S
    from repro.models.transformer import init_lm

    cell = cell_id or f"{arch}/{shape}"
    findings: List[Finding] = []
    with jax.set_mesh(mesh):
        try:
            built = S.build_cell(arch, shape, mesh, overrides=overrides)
        except Exception as e:  # noqa: BLE001
            return [Finding("build", "error", cell,
                            f"cell failed to build: "
                            f"{type(e).__name__}: {e}")]
        cfg = S.cell_config(arch, shape, overrides=overrides)
        meta = built["meta"]
        try:
            jaxpr = jax.make_jaxpr(built["fn"])(*built["args"])
        except Exception as e:  # noqa: BLE001
            return [Finding("trace", "error", cell,
                            f"step trace failed: "
                            f"{type(e).__name__}: {e}")]
        findings += fused_coverage_pass(jaxpr, cfg, meta, cell)
        findings += f8_payload_pass(jaxpr, cfg, meta, cell)
        findings += double_rounding_pass(jaxpr, cell)
        findings += vmem_fit_pass(cfg, meta, cell)
        if meta.get("mode") == "train" \
                and cfg.policy.quant.scaling == "delayed":
            info = S.SHAPES[shape]
            params_s = jax.eval_shape(
                lambda: init_lm(jax.random.PRNGKey(0), cfg))
            batch_s = S._token_batch(cfg, info["batch"], info["seq"],
                                     labels=True)
            findings += site_passes(cfg, params_s, batch_s, cell)
    return findings


def summarize(findings: Sequence[Finding]) -> Dict[str, int]:
    out = {"error": 0, "warning": 0, "info": 0, "suppressed": 0}
    for f in findings:
        out[f.severity] += 1
        out["suppressed"] += int(f.suppressed)
    return out


def to_markdown(findings: Sequence[Finding],
                summary: Optional[dict] = None) -> str:
    """Human-readable report next to the JSON artifact."""
    lines = ["# Precision lint report", ""]
    s = summary or summarize(findings)
    lines.append(f"**{s['error']} error(s), {s['warning']} warning(s), "
                 f"{s['info']} info, {s['suppressed']} suppressed.**")
    lines.append("")
    by_cell: Dict[str, List[Finding]] = {}
    for f in findings:
        by_cell.setdefault(f.cell, []).append(f)
    for cell in sorted(by_cell):
        lines.append(f"## {cell}")
        lines.append("")
        lines.append("| severity | pass | finding |")
        lines.append("|---|---|---|")
        for f in sorted(by_cell[cell],
                        key=lambda x: SEVERITY_RANK[x.severity]):
            msg = f.message.replace("|", "\\|")
            if f.suppressed:
                msg += f" _(suppressed: {f.suppressed_by})_"
            lines.append(f"| {f.severity} | {f.pass_name} | {msg} |")
        lines.append("")
    if not by_cell:
        lines.append("No findings.")
    return "\n".join(lines) + "\n"
