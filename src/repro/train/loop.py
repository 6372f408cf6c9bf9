"""Fault-tolerant training loop.

Production behaviors for the 1000-node regime, exercised at CPU scale:
 * checkpoint/restart — periodic async checkpoints (atomic commit), restore
   on start from the newest committed step; a killed-and-relaunched run
   resumes bit-identically (the data pipeline is a pure function of step).
 * preemption handling — SIGTERM/SIGINT installs a "stop after this step"
   flag; the loop checkpoints and exits cleanly (the standard TPU-preemption
   contract).
 * straggler mitigation — per-step wall-time EMA; steps slower than
   `straggler_factor` x EMA are counted and surfaced through metrics and the
   `on_straggler` hook (at fleet scale the hook triggers host replacement /
   data re-sharding; here it logs and optionally checkpoints so the restart
   lands on a healthy machine). EMA and straggler count ride the checkpoint
   manifest, so a resumed run keeps its timing baseline instead of
   re-learning it (and mis-flagging the first post-restore steps).
 * observability — each step runs inside a profiler
   `StepTraceAnnotation("train", step_num=step)`, and its phases inside
   `obs.trace.Tracer` spans (data_wait / step_dispatch / device_sync /
   checkpoint / record / on_metrics), each timed into the record and marked
   `repro.train.<name>` on the profiler's clock; the compiled step is
   scoped by phase (`obs.trace.SCOPES`). With `LoopConfig.trace_path` set,
   `run()` records a JAX profiler trace (xplane plus a perfetto trace) in
   that directory. Metrics stream through `obs.metrics.MetricsLogger`
   (versioned-schema jsonl; vector metrics such as per-layer amax
   trajectories serialize as lists), each record carrying the process's
   cumulative `compiles`, and `obs.health.HealthMonitor` attaches
   structured `health_events` (overflow, loss-scale flapping, per-site FP8
   saturation/underflow, stuck amax, straggler streaks) to the record that
   triggered them. The `on_metrics` hook sees every serialized record.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import signal
import time
from typing import Any, Callable, Dict, Iterator, Optional

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import Checkpointer
from repro.core.master_weights import MixedPrecisionOptimizer
from repro.distributed.sharding import replicated
from repro.models.config import ModelConfig
from repro.models.transformer import init_lm
from repro.obs import trace as obs_trace
from repro.obs.health import HealthConfig, HealthMonitor
from repro.obs.metrics import MetricsLogger, jsonable
from repro.scaling.state import DelayedScaling
from repro.train.step import make_train_step

Array = jax.Array


@dataclasses.dataclass
class LoopConfig:
    total_steps: int = 100
    checkpoint_every: int = 50
    checkpoint_dir: Optional[str] = "/tmp/repro_ckpt"   # None: no checkpoints
    keep_last_k: int = 3
    log_every: int = 10
    metrics_path: Optional[str] = None
    trace_path: Optional[str] = None    # directory for a profiler trace
    metrics_window: int = 64
    straggler_factor: float = 3.0
    straggler_ema: float = 0.95
    n_microbatches: int = 1


class TrainLoop:
    def __init__(self, cfg: ModelConfig, optimizer: MixedPrecisionOptimizer,
                 data: Iterator[Dict[str, np.ndarray]],
                 loop: LoopConfig, *, seed: int = 0,
                 on_straggler: Optional[Callable[[int, float], None]] = None,
                 on_metrics: Optional[
                     Callable[[int, Dict[str, Any]], None]] = None,
                 health: Optional[HealthConfig] = None,
                 scaling: Optional[DelayedScaling] = None,
                 amax_sync=None, plan=None):
        """scaling: optional DelayedScaling bundle (delayed per-tensor FP8
        scaling). Its ScaleState rides through the jitted step and is
        checkpointed/restored next to the optimizer state.

        plan: optional distributed.strategy.ParallelPlan. Supplies gradient
        shardings to the step; when plan.compresses (policy.dist.wire ==
        "fp8_ef") the DP reduction runs over the fp8 error-feedback
        collective — the residual pytree then rides the step like
        ScaleState does (checkpointed under "wire_error", restored on
        resume) and the loop emits comm/* metrics; the reduction runs
        inside the step under the `train.allreduce` scope.

        on_metrics(step, record): called with every serialized metrics
        record (the exact dict written to the jsonl sink, health_events
        included) — the seam for external sinks (wandb, fleet telemetry)."""
        self.cfg = cfg
        self.optimizer = optimizer
        self.data = data
        self.loop = loop
        self.seed = seed
        self.on_straggler = on_straggler
        self.on_metrics = on_metrics
        self.scaling = scaling
        self.plan = plan
        self.wire = plan is not None and plan.compresses
        self.ckpt = None if loop.checkpoint_dir is None else Checkpointer(
            loop.checkpoint_dir, keep_last_k=loop.keep_last_k)
        self._stop = False
        # The carried state (train state, then ScaleState and the wire
        # residuals when present) is donated: the step's outputs replace
        # it, so a step holds one copy of the state, not two.
        n_carried = 1 + (scaling is not None) + self.wire
        self._step_fn = jax.jit(make_train_step(
            cfg, optimizer, n_microbatches=loop.n_microbatches,
            scaling=scaling, amax_sync=amax_sync, plan=plan),
            donate_argnums=tuple(range(n_carried)))
        self._step_args = None   # ShapeDtypeStructs of the last call
        self._comm: Dict[str, float] = {}
        self.tracer = obs_trace.Tracer("repro.train")
        self.monitor = HealthMonitor(
            health,
            site_names=list(scaling.registry.keys) if scaling else None,
            scaler=optimizer.scaler)

    def _logger_meta(self) -> Dict[str, Any]:
        meta: Dict[str, Any] = {
            "arch": self.cfg.arch,
            "n_microbatches": self.loop.n_microbatches,
            "total_steps": self.loop.total_steps,
        }
        pol = getattr(self.cfg, "policy", None)
        if pol is not None and getattr(pol, "quant", None) is not None:
            meta["recipe"] = pol.quant.recipe
            meta["track_health"] = bool(pol.quant.track_health)
        if self.scaling is not None:
            # Row order of the dense health/amax_sites vector.
            meta["sites"] = list(self.scaling.registry.keys)
        if self.plan is not None:
            meta["dist"] = self.plan.describe()
        return meta

    # -- preemption ----------------------------------------------------------
    def install_signal_handlers(self):
        def handler(signum, frame):  # noqa: ARG001
            print(f"[train] signal {signum}: will checkpoint and stop "
                  f"after the current step")
            self._stop = True
        signal.signal(signal.SIGTERM, handler)
        signal.signal(signal.SIGINT, handler)

    # -- main -----------------------------------------------------------------
    def _pack(self, state, scale_state, err=None):
        if self.scaling is None and not self.wire:
            return state
        tree = {"train": state}
        if self.scaling is not None:
            tree["amax_scales"] = scale_state
        if self.wire:
            tree["wire_error"] = err
        return tree

    def _unpack(self, tree):
        if self.scaling is None and not self.wire:
            return tree, None, None
        return (tree["train"], tree.get("amax_scales"),
                tree.get("wire_error"))

    def step_key(self, step: int) -> Array:
        """The quantization (SR) key of `step`."""
        return jax.random.fold_in(jax.random.PRNGKey(self.seed + 17), step)

    def _shard(self, tree, specs):
        """Place `tree` on the plan's mesh per a PartitionSpec tree."""
        return jax.device_put(tree, jax.tree_util.tree_map(
            lambda s: NamedSharding(self.plan.mesh, s), specs,
            is_leaf=lambda x: isinstance(x, P)))

    def place_batch(self, batch):
        """`batch` as the step receives it: split over the plan's dp axes
        (unchanged without a plan)."""
        if self.plan is None:
            return batch
        return self._shard(batch, self.plan.batch_specs(batch))

    def step_text(self) -> str:
        """Optimized HLO text of the jitted step for the operands of its
        last call, lowered from ShapeDtypeStructs (no donated buffer is
        touched). Map it to phases with `obs.trace.op_scopes`."""
        if self._step_args is None:
            raise RuntimeError("the loop has not run a step yet")
        return _compiled_text(self._step_fn, self._step_args)

    def run(self) -> Dict[str, Any]:
        # Under a plan the step runs with its mesh installed, so the model's
        # activation constraints apply, and every operand is placed by the
        # plan: state per its ZeRO-1 layout, each batch over the dp axes.
        mesh = jax.set_mesh(self.plan.mesh) if self.plan is not None \
            else contextlib.nullcontext()
        profile = jax.profiler.trace(self.loop.trace_path,
                                     create_perfetto_trace=True) \
            if self.loop.trace_path else contextlib.nullcontext()
        with mesh, profile, MetricsLogger(
                self.loop.metrics_path, meta=self._logger_meta(),
                window=self.loop.metrics_window) as logger:
            return self._run(logger)

    def _run(self, logger: MetricsLogger) -> Dict[str, Any]:
        with obs_trace.setup_span("init_state"):
            state = self.optimizer.init(
                init_lm(jax.random.PRNGKey(self.seed), self.cfg))
            if self.plan is not None:
                # Shard before anything else is allocated: the whole state
                # was just made on one device.
                state = self._shard(state,
                                    self.plan.train_state_specs(state))
            scale_state = self.scaling.init() if self.scaling else None
            err = self.plan.init_wire_state(state.master) if self.wire \
                else None
        if self.wire:
            self._comm = {f"comm/{k}": v for k, v in
                          self.plan.wire_bytes(state.master).items()
                          if isinstance(v, (int, float))}
        start_step = 0
        ema = None
        stragglers = 0
        if self.ckpt is not None and self.ckpt.latest_step() is not None:
            proto = jax.eval_shape(lambda s: s,
                                   self._pack(state, scale_state, err))
            tree, start_step = self.ckpt.restore(proto)
            state, scale_state, err = self._unpack(tree)
            # Straggler baseline survives restarts: a resumed run otherwise
            # re-learns the EMA from scratch and both forgets its count and
            # risks flagging warm steps against a cold baseline.
            extra = self.ckpt.manifest(start_step).get("extra", {}) or {}
            ema = extra.get("straggler_ema")
            stragglers = int(extra.get("stragglers", 0))
            print(f"[train] restored checkpoint at step {start_step}")
            # Fast-forward the data stream so a resumed run consumes exactly
            # the batches an uninterrupted run would have (bit-identical
            # restart). Callable data sources seek directly.
            if callable(self.data):
                self.data = self.data(start_step)
            else:
                for _ in range(start_step):
                    next(self.data)
        elif callable(self.data):
            self.data = self.data(0)
        if self.plan is not None:
            state = self._shard(state, self.plan.train_state_specs(state))
            if scale_state is not None:
                scale_state = self._shard(scale_state,
                                          replicated(scale_state))
            if err is not None:
                err = self._shard(err, self.plan.wire_state_specs(err))

        last_metrics: Dict[str, Any] = {}
        step = start_step
        batch = step_key = None
        for step in range(start_step, self.loop.total_steps):
            with jax.profiler.StepTraceAnnotation("train", step_num=step):
                t0 = time.time()
                with self.tracer.span("data_wait"):
                    batch = self.place_batch(next(self.data))
                with self.tracer.span("step_dispatch"):
                    step_key = self.step_key(step)
                    if self.wire and self.scaling is None:
                        (state, err), metrics = self._step_fn(
                            state, err, batch, step_key)
                    elif self.wire:
                        (state, scale_state, err), metrics = self._step_fn(
                            state, scale_state, err, batch, step_key)
                    elif self.scaling is None:
                        state, metrics = self._step_fn(state, batch, step_key)
                    else:
                        (state, scale_state), metrics = self._step_fn(
                            state, scale_state, batch, step_key)
                with self.tracer.span("device_sync"):
                    metrics = jax.block_until_ready(metrics)
                dt = time.time() - t0
                # straggler detection (skip the compile step)
                if step > start_step:
                    slow = self.loop.straggler_factor * (ema or 0.0)
                    if ema is not None and dt > slow:
                        stragglers += 1
                        print(f"[train] straggler step {step}: {dt:.3f}s vs "
                              f"EMA {ema:.3f}s")
                        if self.on_straggler:
                            self.on_straggler(step, dt)
                    ema = dt if ema is None else \
                        self.loop.straggler_ema * ema \
                        + (1 - self.loop.straggler_ema) * dt

                done = step + 1 >= self.loop.total_steps
                save = self.ckpt is not None and (
                    self._stop or done
                    or (step + 1) % self.loop.checkpoint_every == 0)
                if save:
                    with self.tracer.span("checkpoint"):
                        self.ckpt.save(
                            step + 1, self._pack(state, scale_state, err),
                            extra={"straggler_ema": ema,
                                   "stragglers": stragglers})

                with self.tracer.span("record"):
                    # Serialize first (scalar/vector-aware), then let the
                    # health detectors see the exact record, so events land
                    # ON the record whose metrics triggered them. This
                    # step's record and on_metrics spans are timed into the
                    # next record.
                    record = {k: jsonable(v) for k, v in metrics.items()}
                    record.update(step=step, step_time_s=round(dt, 4),
                                  stragglers=stragglers,
                                  compiles=obs_trace.compiles(), **self._comm,
                                  **self.tracer.durations())
                    events = self.monitor.observe(step, record)
                    if events:
                        record["health_events"] = events
                    record = logger.log(record)
                if self.on_metrics:
                    with self.tracer.span("on_metrics"):
                        self.on_metrics(step, record)
            last_metrics = record
            if step % self.loop.log_every == 0:
                # non-finite metrics serialize as strings ("inf"/"nan")
                loss = record.get("loss", 0)
                scale = record.get("loss_scale", 0)
                loss = f"{loss:.4f}" if isinstance(loss, float) else loss
                scale = f"{scale:.0f}" if isinstance(scale, float) else scale
                print(f"[train] step {step} loss={loss} scale={scale} "
                      f"t={dt:.3f}s")
            if self._stop:
                if save:
                    print(f"[train] preempted: checkpointed at {step + 1}")
                break
        if batch is not None:
            self._keep_step_args(state, scale_state, err, batch, step_key)
        if self.ckpt is not None:
            self.ckpt.wait()
        return {"state": state, "scale_state": scale_state,
                "wire_error": err, "last_step": step + 1,
                "metrics": last_metrics, "stragglers": stragglers}

    def _keep_step_args(self, state, scale_state, err, batch, step_key):
        """Note the step's operand shapes (the carried state as the last
        step returned it: what the next call would take) for `step_text`,
        and keep the step's text in `obs.trace` (`last_step_text`)."""
        carried = (state,)
        if self.scaling is not None:
            carried += (scale_state,)
        if self.wire:
            carried += (err,)
        self._step_args = jax.tree_util.tree_map(
            _spec, carried + (batch, step_key))
        obs_trace.keep_step(functools.partial(
            _compiled_text, self._step_fn, self._step_args))


def _spec(x) -> jax.ShapeDtypeStruct:
    """A step operand as the call saw it. A one-device placement is left
    out, as the call leaves it out of the program: the lowering is then the
    call's own, and compiles to the executable the call runs."""
    if not isinstance(x, jax.Array):
        x = np.asarray(x)
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    one = isinstance(x.sharding, jax.sharding.SingleDeviceSharding)
    return jax.ShapeDtypeStruct(x.shape, x.dtype, weak_type=x.weak_type,
                                sharding=None if one else x.sharding)


def _compiled_text(step_fn, args) -> str:
    return step_fn.lower(*args).compile().as_text()
