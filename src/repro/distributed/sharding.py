"""Sharding rules: parameter/optimizer/batch PartitionSpecs for any mesh.

Megatron-style tensor parallelism over the 'model' axis:
  * column-parallel: qkv / up / gate projections — shard the output dim.
  * row-parallel: out / down projections — shard the input dim.
  * vocab-parallel embedding (+ head).
  * expert-parallel MoE: expert dim over 'model'.
Data parallelism over ('pod', 'data') on the batch dim; ZeRO-1 shards the
master weights + optimizer state over 'data' on the largest free dim.

Every rule checks divisibility against the actual mesh axis sizes and falls
back to replication when a dim does not divide — small models (xlstm-125m)
thus degrade gracefully instead of failing to lower.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

# (regex on the param path, candidate dims for the 'model' axis counted from
# the *end* of the shape — first divisible candidate wins; none => replicate).
_RULES = [
    (r"embed/table", (-2, -1)),    # (vocab, d): vocab-parallel, else d
    (r"embed/head", (-1, -2)),     # (d, vocab)
    (r"moe/router", None),         # replicated (f32, precision-critical)
    (r"moe/w_(gate|up|down)", (-3,)),  # (E, d, f): expert-parallel
    (r"(wq|wk|wv|up|gate|w_up|w_gate|wx|wg|wa|wi|w_zifo|w_if)$", (-1,)),
    (r"(wo|down|w_down)$", (-2,)),
    (r"(bq|bk|bv)$", (-1,)),       # column-parallel bias
    (r"(scale|bias|lam|conv|r_zifo|norm)", None),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _spec_for(path: str, shape: Tuple[int, ...], *, model_size: int,
              model_axis: str = "model") -> P:
    ndim = len(shape)
    for pat, dims in _RULES:
        if re.search(pat, path):
            if dims is None or ndim == 0 or model_size <= 1:
                return P()
            for dim in dims:
                if -dim > ndim:
                    continue
                if shape[dim] % model_size == 0 and shape[dim] >= model_size:
                    spec = [None] * ndim
                    spec[ndim + dim] = model_axis
                    return P(*spec)
            return P()              # graceful fallback: replicate
    return P()


def param_specs(params: Any, mesh) -> Any:
    """PartitionSpec pytree matching `params` (arrays or ShapeDtypeStructs)."""
    msize = dict(mesh.shape).get("model", 1)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: _spec_for(_path_str(path), np.shape(x),
                                  model_size=msize), params)


def zero1_specs(params: Any, pspecs: Any, mesh) -> Any:
    """ZeRO-1: additionally shard the largest unsharded dim over 'data'."""
    dsize = dict(mesh.shape).get("data", 1)
    if dsize <= 1:
        return pspecs

    def shard_one(x, spec: P):
        shape = np.shape(x)
        if not shape:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        # Largest dim that is unsharded and divides the data axis.
        order = sorted(range(len(shape)), key=lambda i: -shape[i])
        for i in order:
            if entries[i] is None and shape[i] % dsize == 0 and shape[i] >= dsize:
                entries[i] = "data"
                return P(*entries)
        return spec

    return jax.tree_util.tree_map(shard_one, params, pspecs)


def state_specs(state_proto: Any, mesh, *, batch_axes=("pod", "data")) -> Any:
    """Serving-state (KV cache / recurrent state) specs: shard the batch dim
    (dim 1 for stacked (L, B, ...) leaves, dim 0 for per-layer (B, ...))."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    sizes = dict(mesh.shape)
    total = int(np.prod([sizes[a] for a in axes])) if axes else 1

    msize = sizes.get("model", 1)

    def spec_one(path, x):
        shape = np.shape(x)
        # stacked leaves: (groups, B, ...); per-layer leaves: (B, ...)
        pstr = _path_str(path)
        bdim = 1 if ("stack" in pstr and len(shape) >= 2) else 0
        spec = [None] * len(shape)
        ok = False
        if len(shape) > bdim and total > 1 and shape[bdim] % total == 0:
            spec[bdim] = axes if len(axes) > 1 else axes[0]
            ok = True
        # KV caches: additionally shard the cache-length dim over 'model'
        # (decode is KV-bandwidth bound; XLA handles the softmax reduction
        # over the sharded dim with an all-reduce — flash-decoding style).
        cdim = bdim + 1
        if (pstr.endswith("kv/k") or pstr.endswith("kv/v")
                or pstr.endswith("kv/slot_pos")) and len(shape) > cdim \
                and msize > 1 and shape[cdim] % msize == 0 \
                and shape[cdim] >= msize:
            spec[cdim] = "model"
            ok = True
        return P(*spec) if ok else P()

    return jax.tree_util.tree_map_with_path(spec_one, state_proto)


def batch_specs(batch: Any, mesh, *, batch_axes=("pod", "data")) -> Any:
    """Input batch: shard dim 0 over the data-parallel axes (if divisible)."""
    axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    sizes = dict(mesh.shape)
    total = int(np.prod([sizes[a] for a in axes])) if axes else 1

    def spec_one(x):
        shape = np.shape(x)
        if shape and total > 1 and shape[0] % total == 0:
            return P(axes if len(axes) > 1 else axes[0],
                     *([None] * (len(shape) - 1)))
        return P()

    return jax.tree_util.tree_map(spec_one, batch)


def replicated(tree: Any) -> Any:
    return jax.tree_util.tree_map(lambda _: P(), tree)


def shard_map(f, mesh, in_specs, out_specs, check: bool = False,
              auto: frozenset = frozenset()):
    """jax.shard_map over every mesh axis except `auto`, which stay with
    the XLA partitioner (tensor-parallel axes under an explicitly
    data-parallel collective)."""
    unknown = set(auto) - set(mesh.axis_names)
    if unknown:
        raise ValueError(f"auto axes {sorted(unknown)} not in mesh axes "
                         f"{mesh.axis_names}")
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check,
                         axis_names=set(mesh.axis_names) - set(auto))


# ---------------------------------------------------------------------------
# activation sharding constraints (logical-axis style, divisibility-checked)
# ---------------------------------------------------------------------------

# Axes currently manually mapped by an enclosing shard_map body. Inside such
# a body the axes are *gone* from the positional sharding world —
# with_sharding_constraint naming them is meaningless (and rejected), so
# `constrain` drops those entries. Installed by `manual_axes(...)`, which the
# train step wraps around the model call in wire-compressed mode.
_MANUAL_AXES: frozenset = frozenset()


class manual_axes:
    """Context manager: declare mesh axes as manually mapped (shard_map) so
    logical activation constraints over them become no-ops in this scope."""

    def __init__(self, names):
        self.names = frozenset(names)

    def __enter__(self):
        global _MANUAL_AXES
        self._saved = _MANUAL_AXES
        _MANUAL_AXES = _MANUAL_AXES | self.names
        return self

    def __exit__(self, *exc):
        global _MANUAL_AXES
        _MANUAL_AXES = self._saved
        return False


def _drop_manual(entry):
    if entry is None or not _MANUAL_AXES:
        return entry
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a not in _MANUAL_AXES)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return None if entry in _MANUAL_AXES else entry


def constrain(x, *logical_spec):
    """with_sharding_constraint with logical axes and graceful fallback.

    logical entries: "dp" -> the ('pod','data') axes present in the current
    mesh; "model" -> the model axis; None -> unsharded. Any entry whose mesh
    axes do not divide the corresponding dim degrades to None. No-op outside
    a mesh context — models stay runnable on a single CPU device.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    if not isinstance(x, jax.core.Tracer):
        return x   # eager (smoke-test) execution: constraints are jit-only
    sizes = dict(mesh.shape)
    entries = []
    for dim, name in zip(x.shape, logical_spec):
        if name is None:
            entries.append(None)
        elif name == "dp":
            axes = tuple(a for a in ("pod", "data") if a in sizes)
            total = 1
            for a in axes:
                total *= sizes[a]
            if axes and dim % total == 0 and dim >= total:
                entries.append(axes if len(axes) > 1 else axes[0])
            else:
                entries.append(None)
        else:
            if name in sizes and dim % sizes[name] == 0 and dim >= sizes[name]:
                entries.append(name)
            else:
                entries.append(None)
    entries = [_drop_manual(e) for e in entries]
    if all(e is None for e in entries):
        return x
    return jax.lax.with_sharding_constraint(x, P(*entries))
