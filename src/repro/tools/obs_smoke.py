"""Nightly metrics-enabled training smoke for the obs subsystem.

  PYTHONPATH=src python -m repro.tools.obs_smoke [out_dir] [--steps N]

Runs a short delayed-scaling FP8 training with precision-health counters ON
(QuantConfig.track_health): per-site saturation/flush fractions flow from
the payload-bit readers and kernel epilogues through the metrics pipeline,
phase spans and health events land in the jsonl, and a JAX profiler trace
of the run (host spans and device ops on one clock) is recorded next to it.
Artifacts (uploaded by CI, consumed by healthdash):

  <out_dir>/nightly_smoke.jsonl            one record per step
  <out_dir>/nightly_smoke.jsonl.meta.json  schema version + run meta
  <out_dir>/nightly_smoke_trace/           profiler trace (xplane.pb and
                                           perfetto_trace.json.gz)
"""
from __future__ import annotations

import argparse
import sys
import tempfile
from pathlib import Path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out_dir", nargs="?", default="experiments/obs")
    ap.add_argument("--steps", type=int, default=12)
    args = ap.parse_args(argv)
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    out = Path(args.out_dir)
    out.mkdir(parents=True, exist_ok=True)

    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import paper_transformer
    from repro.core.loss_scale import LossScaler
    from repro.data import DataConfig, synthetic_lm_batches
    from repro.scaling.calibrate import (_delayed_quant_model,
                                         discover_lm_sites)
    from repro.scaling.state import DelayedScaling
    from repro.models.transformer import init_lm
    from repro.train.loop import LoopConfig, TrainLoop
    from repro.train.step import make_optimizer_for

    cfg = paper_transformer.smoke().replace(
        n_layers=2, n_encoder_layers=2, d_model=64, n_heads=2, n_kv_heads=2,
        d_ff=128, vocab_size=128, max_seq_len=32)
    cfg = _delayed_quant_model(cfg)
    q = dataclasses.replace(cfg.policy.quant, track_health=True)
    cfg = cfg.replace(policy=dataclasses.replace(cfg.policy, quant=q))

    params = init_lm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    proto = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32),
             "enc_inputs": jnp.zeros((B, 8, cfg.d_model), jnp.float32)}
    registry = discover_lm_sites(cfg, params, proto)
    del params
    scaling = DelayedScaling(registry, qcfg=cfg.policy.quant)
    # A deliberately huge init scale forces early overflow back-off events,
    # so the nightly artifact always exercises the overflow detector.
    opt = make_optimizer_for(cfg, name="adam", learning_rate=1e-3,
                             scaler=LossScaler(mode="dynamic",
                                               init_scale=2.0 ** 30))

    def data_at(step: int):
        it = synthetic_lm_batches(DataConfig(
            vocab_size=128, seq_len=S, batch_size=B, seed=0),
            start_step=step)
        for batch in it:
            yield {"tokens": batch["tokens"], "labels": batch["labels"],
                   "enc_inputs": jnp.zeros((B, 8, cfg.d_model), jnp.float32)}

    with tempfile.TemporaryDirectory() as ckpt_dir:
        loop = LoopConfig(
            total_steps=args.steps, checkpoint_every=max(4, args.steps // 2),
            checkpoint_dir=ckpt_dir, log_every=5,
            metrics_path=str(out / "nightly_smoke.jsonl"),
            trace_path=str(out / "nightly_smoke_trace"))
        result = TrainLoop(cfg, opt, data_at, loop, seed=0,
                           scaling=scaling).run()
    rec = result["metrics"]
    n_health = sum(k.startswith("health/") for k in rec)
    print(f"[obs_smoke] {result['last_step']} steps, "
          f"{n_health} health keys in the final record, "
          f"loss={rec.get('loss'):.4f}")
    if n_health < 3:
        print("[obs_smoke] FAIL: expected per-site health counters in the "
              "metrics record (track_health on)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
