"""Composable parallelism strategies -> one ParallelPlan.

The launch/train layers used to hand-roll their sharding decisions
(`zero1_specs` calls, inline `sizes.get("pod") * sizes.get("data")`
arithmetic) at every call site. This module turns that mesh code into
config: three small strategy objects —

  * DataParallel   — batch over ('pod', 'data'), gradient mean-reduction
  * ZeRO1Sharded   — master weights + optimizer moments over 'data'
  * TensorParallel — Megatron-style param sharding over 'model'

— compose into a `ParallelPlan` built from (mesh, policy.dist). The plan
owns every PartitionSpec the launch specs and the train step need, plus the
collective implementations, including the wire-format knob:

  policy.dist.wire = "full" | "fp8_ef"
      "fp8_ef" routes the DP gradient reduction through the e5m2-compressed
      error-feedback all-reduce (grad_compress) over the *slowest* dp link
      (the 'pod' axis when present); the remaining dp axes pre-reduce in
      full precision (fast intra-pod ICI).
  policy.dist.wire_zero_gather = "full" | "fp8"
      "fp8" moves the ZeRO-1 weight all-gather leg as e4m3 payloads with a
      shared per-leaf scale (1 byte/element for the frozen-format shards).

Limit: the fp8 wire formats run the dp reduction inside an explicit
shard_map that leaves the 'model' axis to the XLA partitioner. A Pallas
kernel in that body cannot be partitioned over 'model' (XLA never
partitions a Mosaic kernel), and the combination has no test on the XLA
backend, so `ParallelPlan.build` refuses fp8 wire formats on meshes with a
model axis > 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.fp8_formats import E4M3, E5M2
from repro.core.precision_policy import DistConfig
from repro.core.quantize import quantize_rne
from repro.distributed import sharding
from repro.distributed.grad_compress import (make_compressed_dp_allreduce,
                                             make_full_dp_allreduce,
                                             wire_bytes_model)

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class DataParallel:
    """Batch-dim parallelism over the given mesh axes (outermost first)."""
    axes: Tuple[str, ...] = ("pod", "data")


@dataclasses.dataclass(frozen=True)
class ZeRO1Sharded:
    """ZeRO stage 1: master weights + optimizer moments sharded over one
    data-parallel axis (largest divisible dim per leaf)."""
    axis: str = "data"


@dataclasses.dataclass(frozen=True)
class TensorParallel:
    """Megatron tensor parallelism (column/row/vocab/expert rules from
    sharding._RULES) over one mesh axis."""
    axis: str = "model"


@dataclasses.dataclass(frozen=True)
class ParallelPlan:
    """The composed plan for one mesh: which strategies are active, every
    PartitionSpec they imply, and the wire-format collectives."""
    mesh: Any
    dist: DistConfig
    dp: Optional[DataParallel]
    zero1: Optional[ZeRO1Sharded]
    tp: Optional[TensorParallel]

    # -- construction --------------------------------------------------------
    @classmethod
    def build(cls, mesh, dist: DistConfig = DistConfig()) -> "ParallelPlan":
        names = set(mesh.axis_names)
        sizes = dict(mesh.shape)
        dp = DataParallel(tuple(a for a in DataParallel.axes
                                if a in names)) if dist.dp else None
        if dp is not None and not dp.axes:
            dp = None
        zero1 = ZeRO1Sharded() if (dist.zero1 and sizes.get("data", 1) > 1) \
            else None
        tp = TensorParallel() if (dist.tp and sizes.get("model", 1) > 1) \
            else None
        plan = cls(mesh=mesh, dist=dist, dp=dp, zero1=zero1, tp=tp)
        if (dist.wire == "fp8_ef" or dist.wire_zero_gather == "fp8") \
                and plan.tp_size > 1:
            raise NotImplementedError(
                "fp8 wire formats run the dp reduction in a shard_map that "
                "leaves the model axis to the XLA partitioner, which cannot "
                "partition the Pallas kernels, and fp8 wire with tensor "
                "parallelism is untested on the XLA backend. Use a pure "
                "data-parallel mesh or policy.dist.wire='full'.")
        if dist.wire_axis is not None and dist.wire_axis not in names:
            raise ValueError(f"wire_axis {dist.wire_axis!r} not in mesh "
                             f"axes {sorted(names)}")
        return plan

    # -- axis bookkeeping ----------------------------------------------------
    @property
    def dp_axes(self) -> Tuple[str, ...]:
        return self.dp.axes if self.dp is not None else ()

    @property
    def dp_size(self) -> int:
        sizes = dict(self.mesh.shape)
        n = 1
        for a in self.dp_axes:
            n *= sizes[a]
        return n

    @property
    def model_size(self) -> int:
        return dict(self.mesh.shape).get("model", 1)

    @property
    def tp_size(self) -> int:
        """Model-axis size when TensorParallel is active, else 1 (a mesh
        may carry a model axis the plan deliberately leaves replicated)."""
        return self.model_size if self.tp is not None else 1

    @property
    def wire_axis(self) -> Optional[str]:
        """The dp axis the (possibly compressed) reduction runs over — the
        slowest link: 'pod' when present, else 'data'. None when there is
        no data parallelism."""
        if not self.dp_axes:
            return None
        if self.dist.wire_axis is not None:
            return self.dist.wire_axis
        return self.dp_axes[0]

    @property
    def inner_dp_axes(self) -> Tuple[str, ...]:
        """dp axes pre-reduced in full precision before the wire hop."""
        return tuple(a for a in self.dp_axes if a != self.wire_axis)

    @property
    def n_wire(self) -> int:
        w = self.wire_axis
        return dict(self.mesh.shape)[w] if w is not None else 1

    @property
    def compresses(self) -> bool:
        """Whether the DP reduction actually goes through the fp8_ef path
        (needs the knob AND >1 device on the wire axis)."""
        return self.dist.wire == "fp8_ef" and self.n_wire > 1 \
            and self.dp is not None

    # -- specs ---------------------------------------------------------------
    def param_specs(self, params: Any) -> Any:
        if self.tp is None:
            return sharding.replicated(params)
        return sharding.param_specs(params, self.mesh)

    def master_specs(self, params: Any, pspecs: Any = None) -> Any:
        """TP specs + the ZeRO-1 'data' shard on the largest free dim."""
        if pspecs is None:
            pspecs = self.param_specs(params)
        if self.zero1 is None:
            return pspecs
        return sharding.zero1_specs(params, pspecs, self.mesh)

    # Gradients share the master layout: the f32 grad buffer is ZeRO-sharded
    # instead of ballooning to a model-sharded-only copy.
    grad_specs = master_specs

    def train_state_specs(self, state: Any) -> Any:
        """Spec tree for a MixedPrecisionState (master / opt moments get the
        zero1 layout, scalars replicate)."""
        from repro.core.loss_scale import LossScaleState
        from repro.core.master_weights import MixedPrecisionState
        mspecs = self.master_specs(state.master)
        opt_specs = {k: (mspecs if k in ("mu", "nu") else P())
                     for k in state.opt_state}
        return MixedPrecisionState(
            master=mspecs, opt_state=opt_specs,
            loss_scale=LossScaleState(P(), P(), P(), P()))

    def batch_specs(self, batch: Any) -> Any:
        if self.dp is None:
            return sharding.replicated(batch)
        return sharding.batch_specs(batch, self.mesh,
                                    batch_axes=self.dp_axes)

    def serve_state_specs(self, states: Any, *, paged: bool = False) -> Any:
        if paged:
            return self.paged_state_specs(states)
        return sharding.state_specs(states, self.mesh,
                                    batch_axes=self.dp_axes)

    def paged_state_specs(self, states: Any) -> Any:
        """Specs for the paged KV slot pool. Unlike fixed-slot caches there
        is no batch dim to shard — the pool is shared by every in-flight
        request and slots are gathered by index, so the slot dim stays
        replicated over the data axes; the kv-head dim shards over 'model'
        (matching attention TP) when divisible."""
        msize = self.tp_size

        def spec_one(x):
            shape = np.shape(x)
            hdim = len(shape) - 2   # (..., n_slots, n_kv_heads, head_dim)
            if msize > 1 and len(shape) >= 3 and shape[hdim] % msize == 0:
                spec = [None] * len(shape)
                spec[hdim] = "model"
                return P(*spec)
            return P()

        return jax.tree_util.tree_map(spec_one, states)

    def logits_spec(self, batch: int, vocab: int) -> P:
        vdim = "model" if (self.tp_size > 1
                           and vocab % self.tp_size == 0) else None
        dp = self.dp_axes
        bdim = None
        if dp and batch % self.dp_size == 0:
            bdim = dp if len(dp) > 1 else dp[0]
        return P(bdim, None, vdim)

    # -- collectives ---------------------------------------------------------
    def shard_map(self, f, in_specs, out_specs):
        """shard_map over the dp axes (manual); a model axis stays with the
        XLA partitioner (build() refuses fp8 wire formats with one)."""
        auto = frozenset({"model"}) if self.tp_size > 1 else frozenset()
        return sharding.shard_map(f, self.mesh, in_specs, out_specs,
                                  auto=auto)

    def dp_allreduce(self, *, wire: Optional[str] = None):
        """The stacked-contract DP reduction over the wire axis:
        allreduce(grads, error) -> (reduced, new_error); leaves of grads /
        error carry a leading per-device axis sharded P(wire_axis)."""
        w = self.wire_axis
        if w is None:
            raise ValueError("no data-parallel axes: nothing to reduce")
        auto = frozenset({"model"}) if self.tp_size > 1 else frozenset()
        wire = self.dist.wire if wire is None else wire
        if wire == "fp8_ef":
            return make_compressed_dp_allreduce(self.mesh, axis_name=w,
                                                fmt=E5M2, auto=auto)
        return make_full_dp_allreduce(self.mesh, axis_name=w, auto=auto)

    def gather_params(self, params: Any) -> Array:
        """The ZeRO-1 weight all-gather leg. With wire_zero_gather='fp8'
        each 'data'-sharded leaf is re-gathered explicitly as e4m3 payloads
        (shared per-leaf scale, 1 byte/element on the wire); otherwise the
        params pass through and XLA's native bf16 gather applies."""
        if self.dist.wire_zero_gather != "fp8" or self.zero1 is None:
            return params
        mspecs = self.master_specs(params)
        zaxis = self.zero1.axis

        def manual_spec(x, spec):
            entries = list(spec) + [None] * (len(np.shape(x)) - len(spec))
            return P(*[e if e == zaxis else None for e in entries])

        in_specs = jax.tree_util.tree_map(manual_spec, params, mspecs)
        out_specs = sharding.replicated(params)

        def body(tree):
            def leaf(x, spec):
                entries = tuple(spec)
                if zaxis not in entries:
                    return x
                d = entries.index(zaxis)
                xf = x.astype(jnp.float32)
                amax = jax.lax.pmax(jnp.max(jnp.abs(xf)), zaxis)
                scale = jnp.maximum(amax / E4M3.max_normal, 1e-30)
                q = quantize_rne(xf / scale, E4M3, saturate=True)
                g = jax.lax.all_gather(q.astype(E4M3.dtype), zaxis,
                                       axis=d, tiled=True)
                return (g.astype(jnp.float32) * scale).astype(x.dtype)

            return jax.tree_util.tree_map(leaf, tree, mspecs)

        return self.shard_map(body, (in_specs,), out_specs)(params)

    # -- error-feedback wire state -------------------------------------------
    def init_wire_state(self, params: Any) -> Any:
        """Error-feedback residual pytree: one f32 residual per wire device
        per master leaf, stacked on a leading axis sharded P(wire_axis) —
        made in place, so no device ever holds the whole stack. Lives next
        to ScaleState in the checkpoint."""
        stacked = NamedSharding(self.mesh, P(self.wire_axis))
        return jax.tree_util.tree_map(
            lambda p: jnp.zeros((self.n_wire,) + tuple(np.shape(p)),
                                jnp.float32, device=stacked), params)

    def wire_state_struct(self, params_struct: Any) -> Any:
        n = self.n_wire
        return jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct((n,) + tuple(np.shape(p)),
                                           jnp.float32), params_struct)

    def wire_state_specs(self, err: Any) -> Any:
        w = self.wire_axis
        return jax.tree_util.tree_map(lambda _: P(w), err)

    # -- accounting / description --------------------------------------------
    def wire_bytes(self, params: Any) -> dict:
        """Modeled per-step wire bytes of the DP gradient reduction over the
        wire axis (matches the 1-byte fp8 payload dtypes in the lowered
        HLO). Keys feed the comm/* metrics stream and BENCH_comm.json."""
        m = wire_bytes_model(params, self.n_wire)
        active = m["bytes_fp8_ef"] if self.compresses \
            else m["bytes_full_bf16"]
        m["wire"] = self.dist.wire if self.compresses else "full"
        m["bytes_per_step"] = active
        return m

    def describe(self) -> dict:
        """JSON-able summary for launch meta / logger sidecars / docs."""
        return {
            "dp_axes": list(self.dp_axes),
            "dp_size": self.dp_size,
            "zero1_axis": self.zero1.axis if self.zero1 else None,
            "tp_axis": self.tp.axis if self.tp else None,
            "tp_size": self.model_size if self.tp else 1,
            "wire": self.dist.wire,
            "wire_axis": self.wire_axis,
            "wire_zero_gather": self.dist.wire_zero_gather,
            "compresses": self.compresses,
        }
