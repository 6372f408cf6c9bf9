import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Perf-iteration runner for §Perf hillclimbing.

Compiles a named VARIANT of a dry-run cell (a dict of ModelConfig /
PrecisionPolicy overrides), derives the roofline terms, and appends the
record to experiments/perf/<arch>_<shape>.jsonl — the raw material for the
hypothesis -> change -> measure log. The roofline summary of each variant
is also merged into the repo-root BENCH_perf_<arch>_<shape>.json trajectory
file (one entry per variant) so fused-vs-unfused style A/B pairs are
directly comparable across PRs.

  PYTHONPATH=src python -m repro.launch.perf --arch mistral-large-123b \
      --shape decode_32k --variant kv_fp8 --set policy.kv_cache_format=e5m2

Fused-epilogue A/B (the quantize-in-epilogue GEMM path of core.qlinear):

  ... --variant fused   --set policy.quant.backend=pallas \
                              policy.quant.scaling=delayed
  ... --variant unfused --set policy.quant.backend=pallas \
                              policy.quant.scaling=delayed \
                              policy.quant.fuse_epilogue=false
"""
import argparse
import json
import time
from pathlib import Path

import jax

from repro.launch.dryrun import parse_collectives
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import build_cell, parse_overrides
from repro.roofline.analysis import analyze_record


def _update_bench_trajectory(arch: str, shape: str, variant: str, rec: dict):
    """Merge one successful variant's roofline summary into the repo-root
    BENCH_perf_<arch>_<shape>.json (keyed by variant — re-running a variant
    overwrites its entry, so the file tracks the latest number per variant)."""
    path = Path(__file__).resolve().parents[3] \
        / f"BENCH_perf_{arch}_{shape}.json"
    try:
        current = json.loads(path.read_text()) if path.exists() else {}
    except (OSError, ValueError):
        current = {}
    r = rec["roofline"]
    current[variant] = dict(
        compute_s=r["compute_s"], memory_s=r["memory_s"],
        collective_s=r["collective_s"], dominant=r["dominant"],
        peak_gib=r["peak_gib"], overrides=rec.get("overrides", {}))
    path.write_text(json.dumps(current, indent=1) + "\n")


def run_variant(arch: str, shape: str, variant: str, overrides: dict, *,
                unroll: bool = False, out_dir: str = "experiments/perf"):
    mesh = make_production_mesh()
    rec = dict(arch=arch, shape=shape, mesh="single", variant=variant,
               overrides=overrides, unroll=unroll,
               n_devices=mesh.devices.size, status="pending")
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            cell = build_cell(arch, shape, mesh, unroll_layers=unroll,
                              overrides=overrides)
            rec["meta"] = cell["meta"]
            compiled = jax.jit(
                cell["fn"],
                in_shardings=cell["in_shardings"],
                out_shardings=cell["out_shardings"],
                donate_argnums=cell.get("donate_argnums", ()),
            ).lower(*cell["args"]).compile()
            ma = compiled.memory_analysis()
            rec["memory"] = dict(
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                peak_bytes=int(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes))
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):   # older jax: one dict/device
                ca = ca[0]
            rec["cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed",
                                    "transcendentals")}
            rec["collectives"] = parse_collectives(compiled.as_text())
            rec["status"] = "ok"
            rec["roofline"] = analyze_record(rec)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
    rec["total_s"] = round(time.time() - t0, 2)
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    with open(out / f"{arch}_{shape}.jsonl", "a") as f:
        f.write(json.dumps(rec) + "\n")
    if rec["status"] == "ok":
        _update_bench_trajectory(arch, shape, variant, rec)
        r = rec["roofline"]
        print(f"[perf] {arch} {shape} {variant}: compute={r['compute_s']:.3e}"
              f" memory={r['memory_s']:.3e} coll={r['collective_s']:.3e}"
              f" dom={r['dominant']} peak={r['peak_gib']:.1f}GiB")
    else:
        print(f"[perf] {arch} {shape} {variant}: {rec['error'][:150]}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--unroll", action="store_true")
    ap.add_argument("--set", nargs="*", default=[],
                    help="key=value ModelConfig/policy overrides")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    overrides = parse_overrides(args.set)
    run_variant(args.arch, args.shape, args.variant, overrides,
                unroll=args.unroll)


if __name__ == "__main__":
    main()
