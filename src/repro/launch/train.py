"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --steps 100 --smoke          # CPU-scale
  # The fused FP8 path (Pallas kernels, hybrid formats, delayed scaling):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
      --set policy.quant.backend=pallas --set policy.quant.recipe=hybrid \
      --set policy.quant.scaling=delayed
  # On a real fleet the same entry point runs under your cluster launcher
  # (one process per host); jax.distributed.initialize() is called when
  # COORDINATOR_ADDRESS is set, and the mesh comes from launch.mesh.

`--set key=value` takes the same overrides as the dry-run
(`launch.specs.apply_overrides`). `train_config` and `make_train_loop` are
the launcher's two steps as functions; `chip_smoke.py` calls them too.
"""
import argparse
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp


def train_config(arch: str, *, smoke: bool = False,
                 overrides: Sequence[str] = ()):
    """ModelConfig for `arch`, with `--set` style `key=value` overrides
    applied. Returns (cfg, n_microbatches override or None)."""
    from repro.launch.specs import apply_overrides, parse_overrides
    from repro.models.registry import build_config

    cfg = build_config(arch, smoke=smoke)
    if smoke:
        cfg = cfg.replace(remat=False)
    cfg, n_microbatches, force_sp, serve_kw = apply_overrides(
        cfg, parse_overrides(overrides))
    if force_sp is not None or serve_kw:
        raise ValueError("force_sequence_parallel and serve.* overrides "
                         "apply to dry-run cells, not to training")
    return cfg, n_microbatches


def make_train_loop(cfg, *, steps: int, batch: int, seq: int,
                    lr: float = 1e-3, seed: int = 0,
                    ckpt_dir: Optional[str] = None, microbatches: int = 1,
                    plan=None, log_every: int = 10):
    """TrainLoop over `synthetic_lm_batches(seed)` with FP16 master weights
    (the policy's), Adam and enhanced loss scaling. Under
    `policy.quant.scaling == "delayed"` the loop gets a DelayedScaling
    bundle over the sites of one abstract trace of the loss."""
    from repro.core.loss_scale import LossScaler
    from repro.data import DataConfig, synthetic_lm_batches
    from repro.models.transformer import init_lm
    from repro.obs.trace import setup_span
    from repro.scaling import DelayedScaling, discover_lm_sites
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.step import make_optimizer_for

    opt = make_optimizer_for(cfg, name="adam", learning_rate=lr,
                             scaler=LossScaler(mode="enhanced",
                                               init_scale=2.0**13))
    data = synthetic_lm_batches(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, batch_size=batch, seed=seed))
    scaling = None
    if cfg.policy.quant.scaling == "delayed":
        params = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(seed), cfg))
        tokens = jax.ShapeDtypeStruct((batch // microbatches, seq), jnp.int32)
        proto = {"tokens": tokens, "labels": tokens}
        with setup_span("discover_sites"):
            sites = discover_lm_sites(cfg, params, proto)
        scaling = DelayedScaling(sites, qcfg=cfg.policy.quant)
    loop = LoopConfig(
        total_steps=steps, checkpoint_every=max(10, steps // 4),
        checkpoint_dir=ckpt_dir, log_every=log_every,
        metrics_path=f"{ckpt_dir}/metrics.jsonl" if ckpt_dir else None,
        n_microbatches=microbatches)
    return TrainLoop(cfg, opt, data, loop, seed=seed, scaling=scaling,
                     plan=plan)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="config override, as in the dry-run (e.g. "
                         "policy.quant.backend=pallas, "
                         "policy.dist.wire=fp8_ef)")
    args = ap.parse_args()

    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    if os.environ.get("COORDINATOR_ADDRESS"):
        jax.distributed.initialize()   # multi-host fleet entry

    cfg, nmb = train_config(args.arch, smoke=args.smoke, overrides=args.set)
    plan = None
    n_dev = jax.device_count()
    if n_dev > 1:
        # Pure data-parallel launcher mesh; the full pod/data/model grids
        # come from launch.mesh.make_production_mesh under the dry-run.
        from repro.distributed.strategy import ParallelPlan
        from repro.launch.mesh import make_mesh
        plan = ParallelPlan.build(make_mesh((n_dev,), ("data",)),
                                  cfg.policy.dist)
        print(f"[train] parallel plan: {plan.describe()}")
    loop = make_train_loop(cfg, steps=args.steps, batch=args.batch,
                           seq=args.seq, lr=args.lr, ckpt_dir=args.ckpt_dir,
                           microbatches=nmb or args.microbatches, plan=plan)
    loop.install_signal_handlers()
    out = loop.run()
    print(f"finished step {out['last_step']} loss="
          f"{out['metrics'].get('loss', float('nan')):.4f}")


if __name__ == "__main__":
    main()
