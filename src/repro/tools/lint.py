import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Precision-flow lint CLI: run `repro.analysis.precision_lint` over the
config zoo and emit structured JSON findings + a markdown report.

Each cell is built exactly as `launch.dryrun` builds it (same mesh, same
override path), its step jaxpr is traced — never compiled — and the lint
passes check the FP8 invariants the test suite proves on toy steps:
fused-path coverage, real-f8 payloads, site-registry bijection,
token-channel widths, double-rounding chains, and analytic VMEM fit.

Usage:
  # CI tier-1 gate: the two paper configs, both recipes
  PYTHONPATH=src python -m repro.tools.lint --arch paper-transformer \
      --arch paper-resnet --shape train_4k

  # nightly: full zoo, both recipes, artifacts next to BENCH_*.json
  PYTHONPATH=src python -m repro.tools.lint --all \
      --out experiments/lint/findings.json --md experiments/lint/report.md

Exit status 1 iff any unsuppressed error-severity finding remains.

NOTE: the two os.environ lines above MUST stay the first statements — jax
locks the device count at first initialization.
"""
import argparse
import json
import time
from pathlib import Path

from repro.analysis import precision_lint as pl
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (GRID_ARCHS, SHAPES, cell_supported,
                                parse_overrides)

PAPER_ARCHS = ("paper-transformer", "paper-resnet")

# The two recipes under which every cell must lint clean: the paper's
# all-e5m2 recipe and the hybrid (e4m3fn fwd / e5m2 bwd) recipe, both on
# the delayed-scaling fused-pallas path the lint's laws are about.
RECIPES = ("paper_e5m2", "hybrid")


def recipe_overrides(recipe: str) -> dict:
    return {"policy.quant.scaling": "delayed",
            "policy.quant.backend": "pallas",
            "policy.quant.recipe": recipe}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None,
                    help="arch to lint (repeatable); default: the two "
                         "paper configs")
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true",
                    help="full config zoo (grid archs + paper configs), "
                         "every shape")
    ap.add_argument("--recipe", default="both",
                    choices=list(RECIPES) + ["both"])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi"])
    ap.add_argument("--set", nargs="*", default=[], dest="overrides",
                    help="extra key=value overrides layered on top of "
                         "the recipe overrides")
    ap.add_argument("--suppressions", default=None,
                    help="suppression-rule JSON (default: the shipped "
                         "src/repro/analysis/lint_suppressions.json)")
    ap.add_argument("--out", default="experiments/lint/findings.json")
    ap.add_argument("--md", default="experiments/lint/report.md")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    if args.all:
        archs = list(GRID_ARCHS) + [a for a in PAPER_ARCHS
                                    if a not in GRID_ARCHS]
    else:
        archs = args.arch or list(PAPER_ARCHS)
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    recipes = RECIPES if args.recipe == "both" else (args.recipe,)
    user_overrides = parse_overrides(args.overrides)
    rules = pl.load_suppressions(args.suppressions)

    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    findings = []
    cells = []
    t0 = time.time()
    for arch in archs:
        for shape in shapes:
            ok, why = cell_supported(arch, shape)
            if not ok:
                cells.append(dict(arch=arch, shape=shape,
                                  status="skipped", reason=why))
                print(f"[lint] SKIP {arch:24s} {shape:12s}: {why}")
                continue
            for recipe in recipes:
                cell_id = f"{arch}/{shape}@{recipe}"
                overrides = {**recipe_overrides(recipe), **user_overrides}
                t1 = time.time()
                fs = pl.lint_cell(arch, shape, mesh, overrides=overrides,
                                  cell_id=cell_id)
                fs = pl.apply_suppressions(fs, rules)
                findings.extend(fs)
                s = pl.summarize(fs)
                cells.append(dict(arch=arch, shape=shape, recipe=recipe,
                                  cell=cell_id, status="ok", **s,
                                  wall_s=round(time.time() - t1, 1)))
                badge = "FAIL" if s["error"] else "ok  "
                print(f"[lint] {badge} {cell_id:44s} "
                      f"errors={s['error']} warnings={s['warning']} "
                      f"info={s['info']} suppressed={s['suppressed']} "
                      f"({cells[-1]['wall_s']}s)")

    summary = pl.summarize(findings)
    summary["cells"] = len(cells)
    report = dict(generated_by="repro.tools.lint",
                  mesh=args.mesh, recipes=list(recipes),
                  wall_s=round(time.time() - t0, 1),
                  summary=summary, cells=cells,
                  findings=[f.to_dict() for f in findings])
    out = Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(report, indent=1))
    md = Path(args.md)
    md.parent.mkdir(parents=True, exist_ok=True)
    md.write_text(pl.to_markdown(findings, summary))
    print(f"[lint] {summary['error']} error(s), {summary['warning']} "
          f"warning(s), {summary['info']} info, "
          f"{summary['suppressed']} suppressed across {len(cells)} "
          f"cell(s) -> {out} / {md}")
    raise SystemExit(1 if summary["error"] else 0)


if __name__ == "__main__":
    main()
