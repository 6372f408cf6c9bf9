import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: jax.jit(step).lower(**ShapeDtypeStructs).compile() must succeed on
the single-pod (16 data x 16 model = 256 chips) mesh AND the multi-pod
(2 pods x 16 x 16 = 512 chips) mesh for every supported cell. The compiled
artifact supplies memory_analysis() (proves the cell fits per-device HBM)
and cost_analysis() + the HLO collective schedule for §Roofline.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
      --shape train_4k --mesh single --out experiments/dryrun
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

NOTE: the two os.environ lines above MUST stay the first statements — jax
locks the device count at first initialization.
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.launch.mesh import make_production_mesh
from repro.launch.specs import (GRID_ARCHS, SHAPES, build_cell,
                               cell_supported, parse_overrides)

COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\b")


def parse_collectives(hlo_text: str) -> dict:
    """Sum operand bytes per collective kind from compiled HLO text.

    Counts each op at its definition site (the `-start` line for async ops;
    plain form otherwise) and parses the output shape on the lhs, e.g.
      %ag = bf16[16,512,128]{...} all-gather(...)
    For while-loop bodies (scan-over-layers), ops inside loop computations
    are counted once — multiply by trip count in the analysis layer (the
    roofline path uses the UNROLLED lowering, where this is exact).
    """
    kinds = {}
    shape_re = re.compile(
        r"=\s+(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\]")
    dtype_bytes = {"f32": 4, "bf16": 2, "f16": 2, "f8e5m2": 1, "f8e4m3fn": 1,
                   "s32": 4, "u32": 4, "s8": 1, "u8": 1, "pred": 1,
                   "f64": 8, "s64": 8, "u64": 8, "s16": 2, "u16": 2}
    for line in hlo_text.splitlines():
        m = COLLECTIVE_RE.search(line)
        if not m:
            continue
        if "-done" in line:
            continue                       # count -start only for async pairs
        kind = m.group(1)
        sm = shape_re.search(line)
        if not sm:
            continue
        dt, dims = sm.group(1), sm.group(2)
        nbytes = dtype_bytes.get(dt, 4)
        numel = 1
        if dims:
            for d in dims.split(","):
                numel *= int(d)
        ent = kinds.setdefault(kind, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += numel * nbytes
    return kinds


def run_cell(arch: str, shape: str, mesh_kind: str, *,
             unroll: bool = False, out_dir: Path,
             probe_groups: int = 0, overrides: dict = None) -> dict:
    """probe_groups > 0: compile an UNROLLED variant with that many pattern
    groups of layers (n_layers = groups * len(pattern)) — two probes give
    per-group cost deltas that the roofline analysis extrapolates to full
    depth (full-depth unrolled compiles are infeasible on one CPU core)."""
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec = dict(arch=arch, shape=shape, mesh=mesh_kind, unroll=unroll,
               probe_groups=probe_groups,
               n_devices=mesh.devices.size, status="pending")
    t0 = time.time()
    overrides = dict(overrides or {})
    rec["overrides"] = overrides
    if probe_groups:
        from repro.models.registry import build_config
        full = build_config(arch)
        plen = len(full.pattern())
        overrides["n_layers"] = probe_groups * plen
        if full.is_encoder_decoder:
            overrides["n_encoder_layers"] = probe_groups
        unroll = True
        rec["unroll"] = True
    overrides = overrides or None
    try:
        with jax.set_mesh(mesh):
            cell = build_cell(arch, shape, mesh, unroll_layers=unroll,
                              overrides=overrides)
            rec["meta"] = cell["meta"]
            lowered = jax.jit(
                cell["fn"],
                in_shardings=cell["in_shardings"],
                out_shardings=cell["out_shardings"],
                donate_argnums=cell.get("donate_argnums", ()),
            ).lower(*cell["args"])
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            ma = compiled.memory_analysis()
            rec["memory"] = dict(
                argument_bytes=int(ma.argument_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                alias_bytes=int(ma.alias_size_in_bytes),
                peak_bytes=int(ma.argument_size_in_bytes
                               + ma.output_size_in_bytes
                               + ma.temp_size_in_bytes
                               - ma.alias_size_in_bytes),
            )
            ca = compiled.cost_analysis()
            rec["cost"] = {k: float(v) for k, v in ca.items()
                           if k in ("flops", "bytes accessed",
                                    "transcendentals")
                           or k.startswith("bytes accessed")}
            hlo = compiled.as_text()
            rec["collectives"] = parse_collectives(hlo)
            rec["status"] = "ok"
            print(f"[dryrun] OK   {arch:24s} {shape:12s} {mesh_kind:6s} "
                  f"unroll={unroll} compile={rec['compile_s']}s "
                  f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
                  f"flops={rec['cost'].get('flops', 0):.3e}")
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
        print(f"[dryrun] FAIL {arch:24s} {shape:12s} {mesh_kind:6s}: "
              f"{rec['error'][:200]}")
    rec["total_s"] = round(time.time() - t0, 2)
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}_{shape}_{mesh_kind}"
    if probe_groups:
        tag += f"_probe{probe_groups}"
    elif unroll:
        tag += "_unroll"
    (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--unroll", action="store_true",
                    help="unrolled-layers lowering (roofline cost numbers)")
    ap.add_argument("--probe", action="store_true",
                    help="compile 1-group and 2-group unrolled probes "
                         "(roofline extrapolation inputs)")
    ap.add_argument("--set", nargs="*", default=[], dest="overrides",
                    help="key=value ModelConfig/policy overrides, e.g. "
                         "policy.quant.recipe=hybrid "
                         "policy.quant.scaling=delayed")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()
    from repro.launch.cache import use_compile_cache
    use_compile_cache()

    overrides = parse_overrides(args.overrides)

    out_dir = Path(args.out)
    archs = GRID_ARCHS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    results = []
    for arch in archs:
        for shape in shapes:
            ok, why = cell_supported(arch, shape)
            if not ok:
                print(f"[dryrun] SKIP {arch:24s} {shape:12s}: {why}")
                rec = dict(arch=arch, shape=shape, status="skipped",
                           reason=why)
                out_dir.mkdir(parents=True, exist_ok=True)
                (out_dir / f"{arch}_{shape}_skip.json").write_text(
                    json.dumps(rec, indent=1))
                continue
            for mk in meshes:
                if args.probe:
                    for g in (1, 2):
                        results.append(run_cell(arch, shape, mk,
                                                probe_groups=g,
                                                out_dir=out_dir,
                                                overrides=overrides))
                else:
                    results.append(run_cell(arch, shape, mk,
                                            unroll=args.unroll,
                                            out_dir=out_dir,
                                            overrides=overrides))
    n_ok = sum(r["status"] == "ok" for r in results)
    print(f"[dryrun] {n_ok}/{len(results)} cells compiled")
    if results and n_ok < len(results):
        raise SystemExit(1)


if __name__ == "__main__":
    main()
