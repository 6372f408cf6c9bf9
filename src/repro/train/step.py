"""Jittable step functions: the units the dry-run lowers and the train loop
runs.

train_step implements the paper's full Fig. 1b pipeline per step:
  compute params (fp16 master -> bf16) -> FP8 forward/backward (loss scaled)
  -> overflow probe -> unscale in f32 -> optimizer update in f32 -> fp16
  master store -> loss-scale update.

Optional gradient accumulation (n_microbatches) runs the loss/grad pass in a
scan with f32 accumulators — the standard large-batch memory lever.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.loss_scale import LossScaler
from repro.core.master_weights import MixedPrecisionOptimizer, MixedPrecisionState
from repro.models.config import ModelConfig
from repro.models.transformer import encode, forward, lm_loss
from repro.obs.trace import scope
from repro.optim import make_optimizer
from repro.scaling import context as scale_ctx
from repro.scaling.context import AMAX_PREFIX, HEALTH_PREFIX
from repro.scaling.state import DelayedScaling, ScaleState, split_observations

Array = jax.Array


def make_optimizer_for(cfg: ModelConfig, *, name: str = "adam",
                       scaler: Optional[LossScaler] = None,
                       learning_rate: float = 1e-4) -> MixedPrecisionOptimizer:
    from repro.optim.optimizers import make_leafwise
    init, update = make_optimizer(name, learning_rate=learning_rate)
    names, leaf = make_leafwise(name, learning_rate=learning_rate)
    return MixedPrecisionOptimizer(
        inner_init=init, inner_update=update,
        scaler=scaler or LossScaler(mode="enhanced"),
        master_dtype=cfg.policy.master_weight_dtype,
        update_dtype=cfg.policy.update_dtype,
        compute_dtype=cfg.policy.activation_dtype,
        accum_names=names, leaf_update=leaf)


def make_train_step(cfg: ModelConfig, optimizer: MixedPrecisionOptimizer, *,
                    n_microbatches: int = 1,
                    scaling: Optional[DelayedScaling] = None,
                    amax_sync=None, plan=None):
    """Returns train_step(state, batch, step_key) -> (state, metrics).

    plan: optional distributed.strategy.ParallelPlan. Supplies the gradient
    shardings (grads / the f32 accumulator constrained to the ZeRO-1 master
    layout instead of ballooning to a model-sharded-only copy) and, when
    `plan.compresses` (policy.dist.wire == "fp8_ef" on a >1-device wire
    axis), reroutes the DP gradient reduction through the e5m2-compressed
    error-feedback all-reduce: the loss/grad pass then runs inside an
    explicit shard_map over the dp axes and the step signature grows the
    residual pytree,

        train_step(state, [scale_state,] err, batch, step_key)
            -> ((state, [scale_state,] err), metrics)

    with `err` created by plan.init_wire_state(state.master) and
    checkpointed next to ScaleState by the train loop.

    scaling: optional DelayedScaling bundle. When given, the returned step is
        train_step(state, scale_state, batch, step_key)
            -> ((state, scale_state), metrics)
    — the ScaleState pytree rides through the jitted step next to
    LossScaleState: per-site scales feed the quantize sites via the scaling
    context, forward amax observations come back through the loss aux,
    error/grad observations through the cotangents of per-site tokens, and
    the history is updated post-step (optionally cross-replica-synced via
    `amax_sync`, e.g. distributed.amax_sync.make_amax_sync('data')). In
    wire-compressed mode amax_sync is ignored: observations are already
    cross-device-combined (pmax) inside the shard_map body.
    """
    wire = plan is not None and plan.compresses
    # XLA cannot partition a Pallas kernel, so under a Pallas backend a
    # data-parallel step without the fp8 wire still runs its loss/grad pass
    # inside the wire path's explicit shard_map over the dp axes, and
    # reduces the grads there in full precision.
    manual_dp = (plan is not None and not wire and plan.dp_size > 1
                 and cfg.policy.quant.backend.startswith("pallas"))

    def constrain_grads(g):
        if plan is None:
            return g
        from jax.sharding import NamedSharding
        specs = plan.grad_specs(g)
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(
                x, NamedSharding(plan.mesh, s)),
            g, specs)

    def loss_fn(params, tokens, batch, step_key, scale, scale_state):
        if scaling is None:
            return lm_loss(params, batch, cfg=cfg, qkey=step_key,
                           loss_scale=scale)
        with scaling.collect(scale_state, tokens):
            return lm_loss(params, batch, cfg=cfg, qkey=step_key,
                           loss_scale=scale)

    @scope("train.grads")
    def _grads_and_metrics(params, batch, step_key, scale, scale_state,
                           constrain=None):
        constrain = constrain_grads if constrain is None else constrain
        tokens = scaling.zero_tokens() if scaling is not None else {}

        if n_microbatches <= 1:
            (loss, metrics), (grads, tok_grads) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, tokens, batch, step_key, scale, scale_state)
            return loss, metrics, constrain(grads), tok_grads

        def reshape_mb(x):
            return x.reshape((n_microbatches,
                              x.shape[0] // n_microbatches) + x.shape[1:])
        mb_batch = jax.tree_util.tree_map(reshape_mb, batch)

        def mb_body(carry, mb):
            acc, tacc, i = carry
            mkey = jax.random.fold_in(step_key, i)
            (l, m), (g, tg) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                    params, tokens, mb, mkey, scale, scale_state)
            acc = jax.tree_util.tree_map(
                lambda a, gg: a + gg.astype(jnp.float32) / n_microbatches,
                acc, g)
            tacc = jax.tree_util.tree_map(lambda a, gg: jnp.maximum(a, gg),
                                          tacc, tg)
            return (constrain(acc), tacc, i + 1), (l, m)

        zero = constrain(jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params))
        tzero = jax.tree_util.tree_map(jnp.zeros_like, tokens)
        (grads, tok_grads, _), (losses, metricses) = jax.lax.scan(
            mb_body, (zero, tzero, 0), mb_batch)
        loss = losses.mean()
        # Microbatch reduction: amax observations by max, losses by mean —
        # over the MICROBATCH axis only (axis 0): per-layer scanned-stack
        # observations are (n_groups,) vectors whose layer axis must
        # survive the reduction.
        metrics = {k: (v.max(axis=0)
                       if k.startswith((AMAX_PREFIX, HEALTH_PREFIX))
                       else v.mean())
                   for k, v in metricses.items()}
        return loss, metrics, grads, tok_grads

    def _combine_tokens(tok, axes):
        """Cross-device combine of token cotangents: amax channels by pmax
        (matching amax_sync semantics), the optional (sat, flush) health
        tail by pmean (they are per-batch fractions)."""
        c = scale_ctx.TOKEN_CHANNELS
        if tok.ndim and tok.shape[-1] > c:
            return jnp.concatenate(
                [jax.lax.pmax(tok[..., :c], axes),
                 jax.lax.pmean(tok[..., c:], axes)], axis=-1)
        return jax.lax.pmax(tok, axes)

    def _wire_grads_and_metrics(params, batch, step_key, scale, scale_state,
                                reduce_all=False):
        """The fp8-on-the-wire gradient pass: loss/grads computed locally
        inside an explicit shard_map over the dp axes (so the cross-device
        reduction is OURS, not an XLA-inserted all-reduce), full-precision
        pmean over the fast intra-pod axes, then the e5m2 error-feedback
        collective over the wire axis. Returns stacked per-wire-device f32
        grads (leading axis = wire device) ready for plan.dp_allreduce —
        or, with `reduce_all`, the grads pmean-reduced over every dp axis
        in the body (the manual_dp path)."""
        from jax.sharding import PartitionSpec as P

        from repro.distributed import sharding as shmod

        dp = plan.dp_axes
        inner = plan.inner_dp_axes

        def local_body(*args):
            if scaling is None:
                params_, batch_, key_, scale_ = args
                sstate_ = None
            else:
                params_, batch_, key_, scale_, sstate_ = args
            # Logical activation constraints naming the manually-mapped dp
            # axes are meaningless inside the body — drop them.
            with shmod.manual_axes(dp):
                loss, metrics, grads, tok_grads = _grads_and_metrics(
                    params_, batch_, key_, scale_, sstate_,
                    constrain=lambda g: g)
            with scope("train.allreduce"):
                if inner or reduce_all:
                    grads = jax.tree_util.tree_map(
                        lambda g: jax.lax.pmean(
                            g, dp if reduce_all else inner), grads)
                loss = jax.lax.pmean(loss, dp)
                metrics = {k: (jax.lax.pmax(v, dp)
                               if k.startswith((AMAX_PREFIX, HEALTH_PREFIX))
                               else jax.lax.pmean(v, dp))
                           for k, v in metrics.items()}
                tok_grads = {k: _combine_tokens(v, dp)
                             for k, v in tok_grads.items()}
            if not reduce_all:
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32)[None], grads)
            return loss, metrics, grads, tok_grads

        bspecs = plan.batch_specs(batch)
        operands = (params, batch, step_key, scale)
        in_specs = (P(), bspecs, P(), P())
        if scaling is not None:
            operands += (scale_state,)
            in_specs += (P(),)
        grad_spec = P() if reduce_all else P(plan.wire_axis)
        return plan.shard_map(
            local_body, in_specs, (P(), P(), grad_spec, P()))(*operands)

    def _local_grads(params, batch, step_key, scale, scale_state):
        if not manual_dp:
            return _grads_and_metrics(params, batch, step_key, scale,
                                      scale_state)
        loss, metrics, grads, tok_grads = _wire_grads_and_metrics(
            _gather(params), batch, step_key, scale, scale_state,
            reduce_all=True)
        return loss, metrics, constrain_grads(grads), tok_grads

    @scope("train.allreduce")
    def _gather(params):
        return plan.gather_params(params)

    @scope("train.optimizer")
    def _compute_params(state):
        return optimizer.compute_params(state)

    @scope("train.optimizer")
    def _finish(state, grads, loss, metrics, scale):
        new_state, opt_metrics = optimizer.apply_gradients(state, grads)
        inv = 1.0 / jnp.maximum(scale, 1e-9)
        out = {"loss": loss.astype(jnp.float32) * inv,
               "grad_norm": optax_safe_norm(grads) * inv,
               **{k: v for k, v in metrics.items()}, **opt_metrics}
        return new_state, out

    @scope("train.scaling")
    def _update_scales(scale_state, metrics, tok_grads, sync):
        """The history update from this step's observations; under
        track_health also the scale-churn rate (fraction of registry rows
        whose derived scale moved this step) and the dense freshest-amax
        vector (registry row order — the logger meta carries the matching
        site list) for the stuck/NaN-amax detectors."""
        observed = split_observations(metrics, tok_grads, scaling.registry)
        new = scaling.update(scale_state, observed, sync=sync)
        health = {}
        if scaling.qcfg.track_health:
            health["health/scale_churn"] = jnp.mean(
                (scale_state.scale != new.scale).astype(jnp.float32))
            health["health/amax_sites"] = new.amax_history[:, 0]
        return new, health

    def train_step(state: MixedPrecisionState, batch: Dict[str, Array],
                   step_key: Array) -> Tuple[MixedPrecisionState, Dict]:
        params = _compute_params(state)
        scale = state.loss_scale.scale
        loss, metrics, grads, _ = _local_grads(
            params, batch, step_key, scale, None)
        return _finish(state, grads, loss, metrics, scale)

    def train_step_scaled(state: MixedPrecisionState, scale_state: ScaleState,
                          batch: Dict[str, Array], step_key: Array):
        params = _compute_params(state)
        scale = state.loss_scale.scale
        loss, metrics, grads, tok_grads = _local_grads(
            params, batch, step_key, scale, scale_state)
        # (manual_dp observations are already pmax-combined in the body.)
        new_scale_state, health = _update_scales(
            scale_state, metrics, tok_grads,
            None if manual_dp else amax_sync)
        new_state, out = _finish(state, grads, loss, metrics, scale)
        return (new_state, new_scale_state), {**out, **health}

    def train_step_wire(state: MixedPrecisionState, err,
                        batch: Dict[str, Array], step_key: Array):
        params = _compute_params(state)
        params = _gather(params)
        scale = state.loss_scale.scale
        loss, metrics, stacked, _ = _wire_grads_and_metrics(
            params, batch, step_key, scale, None)
        with scope("train.allreduce"):
            reduced, new_err = plan.dp_allreduce()(stacked, err)
        new_state, out = _finish(state, constrain_grads(reduced),
                                 loss, metrics, scale)
        return (new_state, new_err), out

    def train_step_wire_scaled(state: MixedPrecisionState,
                               scale_state: ScaleState, err,
                               batch: Dict[str, Array], step_key: Array):
        params = _compute_params(state)
        params = _gather(params)
        scale = state.loss_scale.scale
        loss, metrics, stacked, tok_grads = _wire_grads_and_metrics(
            params, batch, step_key, scale, scale_state)
        with scope("train.allreduce"):
            reduced, new_err = plan.dp_allreduce()(stacked, err)
        # No amax_sync here: observations were pmax-combined across devices
        # inside the shard_map body already.
        new_scale_state, health = _update_scales(
            scale_state, metrics, tok_grads, None)
        new_state, out = _finish(state, constrain_grads(reduced),
                                 loss, metrics, scale)
        return (new_state, new_scale_state, new_err), {**out, **health}

    if wire:
        return train_step_wire if scaling is None else train_step_wire_scaled
    return train_step if scaling is None else train_step_scaled


def make_loss_eval(cfg: ModelConfig, optimizer: MixedPrecisionOptimizer, *,
                   scaling: Optional[DelayedScaling] = None):
    """loss_eval(state, scale_state, batch, step_key) -> the loss a train
    step on the same operands reports (unscaled f32), from the forward pass
    alone: no gradients, so it fits where the whole step does not.
    `scale_state` is ignored without `scaling`."""
    def loss_eval(state: MixedPrecisionState, scale_state, batch, step_key):
        params = optimizer.compute_params(state)
        scale = state.loss_scale.scale
        if scaling is None:
            loss, _ = lm_loss(params, batch, cfg=cfg, qkey=step_key,
                              loss_scale=scale)
        else:
            with scaling.collect(scale_state, scaling.zero_tokens()):
                loss, _ = lm_loss(params, batch, cfg=cfg, qkey=step_key,
                                  loss_scale=scale)
        return loss.astype(jnp.float32) * (1.0 / jnp.maximum(scale, 1e-9))

    return loss_eval


def optax_safe_norm(tree) -> Array:
    sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
             for x in jax.tree_util.tree_leaves(tree))
    return jnp.sqrt(sq)


# ---------------------------------------------------------------------------
# serving steps (deterministic eval: RNE, saturating)
# ---------------------------------------------------------------------------

def _eval_cfg(cfg: ModelConfig, frozen_scales=None) -> ModelConfig:
    quant = cfg.policy.quant.eval_mode()
    if frozen_scales is not None:
        # Calibrated serving: per-site scales come from the frozen dict
        # (python floats burned into the jitted program as constants).
        quant = dataclasses.replace(quant, scaling="delayed")
    pol = dataclasses.replace(cfg.policy, quant=quant)
    return cfg.replace(policy=pol)


def _maybe_frozen(frozen_scales):
    if frozen_scales is None:
        import contextlib
        return contextlib.nullcontext()
    return scale_ctx.activate(scale_ctx.frozen_context(frozen_scales))


def make_serve_prefill(cfg: ModelConfig, frozen_scales=None):
    """frozen_scales: optional {site_key: scale} dict from
    scaling.calibrate.freeze — enables deterministic calibrated FP8
    inference (including FP8 KV-cache scales)."""
    ecfg = _eval_cfg(cfg, frozen_scales)

    def prefill(params, batch, states):
        with _maybe_frozen(frozen_scales):
            enc_out = None
            if ecfg.is_encoder_decoder:
                enc_out = encode(params, batch["enc_inputs"], cfg=ecfg)
            logits, new_states, _ = forward(
                params, batch["tokens"], cfg=ecfg, mode="prefill",
                states=states, extra_embeds=batch.get("extra_embeds"),
                enc_out=enc_out, last_only=True)
        return logits, new_states

    return prefill


def make_serve_decode(cfg: ModelConfig, frozen_scales=None):
    ecfg = _eval_cfg(cfg, frozen_scales)

    def decode(params, batch, states):
        with _maybe_frozen(frozen_scales):
            enc_out = batch.get("enc_out")
            logits, new_states, _ = forward(
                params, batch["tokens"], cfg=ecfg, mode="decode",
                states=states, positions=batch["positions"], enc_out=enc_out)
        return logits[:, -1:], new_states

    return decode


def make_serve_chunk(cfg: ModelConfig, frozen_scales=None):
    """Paged chunked serving step over a block-table KV pool: each batch
    row carries either a prompt chunk or a single decode token through ONE
    fixed-shape program (mode='chunk' attention with a gather plan and
    per-row [start, n_valid] ragged bounds). `serve.engine.PagedServeEngine`
    builds its jitted step on the same forward call plus on-device
    sampling; this plain-logits variant is what the launch grid dry-runs.

    batch keys: tokens/positions/write_slots (B, T) int32,
    read_slots/slot_pos (B, C) int32, chunk_pos (B, 2) int32,
    last_row (B,) int32. Returns (logits (B, 1, V), new_states)."""
    ecfg = _eval_cfg(cfg, frozen_scales)

    def chunk_step(params, batch, states):
        with _maybe_frozen(frozen_scales):
            page = {k: batch[k] for k in
                    ("write_slots", "read_slots", "slot_pos", "chunk_pos")}
            logits, new_states, _ = forward(
                params, batch["tokens"], cfg=ecfg, mode="chunk",
                states=states, positions=batch["positions"], page=page,
                gather_rows=batch["last_row"])
        return logits, new_states

    return chunk_step
