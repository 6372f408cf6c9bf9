"""Per-site FP8 precision-health counters.

Two observation flavors, one semantics:

 * `payload_health(data, fmt)` — host/XLA side, for tensors whose FP8
   payload is already materialized (quantized operands, fused-GEMM outputs,
   error cotangents). Reads the same `& 0x7F` bit patterns the delayed-
   scaling `_observe` amax reduction reads, so XLA fuses the counts into
   the pass that consumes the payload anyway: zero extra HBM traffic.
 * `value_counts(q, fmt, mask)` — kernel side, for tensors that never hit
   HBM (attention S/P/dP/dS tiles, fused-GEMM epilogue tiles). Counts in
   VMEM from the just-quantized values, next to the amax epilogue.

Definitions (per tensor, per use):
  saturation fraction — |q| at the format's max-normal or beyond
    (incl. inf/nan payloads): the per-tensor scale is too LARGE for the
    format's range, values are clipping (Noune et al. 2206.02915's
    format-fit signal).
  flush fraction — |q| below the format's min-normal (exact zeros and
    subnormals): values parked in (or below) the subnormal range where
    e5m2 keeps only 2 mantissa bits — the paper's Fig. 2a underflow regime.

Both are fractions of the observed region so microbatch / multi-use
averaging is well-defined.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro.core.fp8_formats import FloatFormat, get_format
from repro.obs.trace import scope

_ML_DTYPE = {"e5m2": ml_dtypes.float8_e5m2, "e4m3": ml_dtypes.float8_e4m3fn}


@functools.lru_cache(maxsize=None)
def payload_thresholds(fmt_name: str) -> Tuple[int, int]:
    """(min_normal_bits, max_normal_bits) of the |payload| (sign stripped).

    Payload magnitudes order like their bit patterns, so
      bits <  lo  <=> zero or subnormal (flush)
      bits >= hi  <=> max-normal or inf/nan (saturated)
    """
    fmt = get_format(fmt_name)
    dt = _ML_DTYPE[fmt_name]
    lo = int(np.asarray(fmt.min_normal, dt).view(np.uint8))
    hi = int(np.asarray(fmt.max_normal, dt).view(np.uint8))
    return lo, hi


@scope("fp8.amax")
def payload_health(data: jax.Array, fmt_name: str) -> jax.Array:
    """(2,) f32 [sat_frac, flush_frac] from an FP8 payload's bit patterns."""
    lo, hi = payload_thresholds(fmt_name)
    bits = jax.lax.bitcast_convert_type(data, jnp.uint8) & jnp.uint8(0x7F)
    n = jnp.float32(max(1, bits.size))
    sat = (bits >= jnp.uint8(hi)).sum().astype(jnp.float32) / n
    flush = (bits < jnp.uint8(lo)).sum().astype(jnp.float32) / n
    return jnp.stack([sat, flush])


def value_counts(q: jax.Array, fmt: FloatFormat,
                 mask: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """(sat_count, flush_count) f32 scalars from just-quantized values.

    For kernel epilogues: `q` is the quantized tile still in VMEM (any
    float dtype). `mask` restricts to the logical/observed region.
    """
    a = jnp.abs(q.astype(jnp.float32))
    sat = (a >= jnp.float32(fmt.max_normal)) | ~jnp.isfinite(a)
    flush = a < jnp.float32(fmt.min_normal)
    if mask is not None:
        sat = sat & mask
        flush = flush & mask
    return (sat.sum().astype(jnp.float32), flush.sum().astype(jnp.float32))


def counts_to_frac(counts: jax.Array) -> jax.Array:
    """(…, 3) [sat, flush, n] count triples -> (…, 2) [sat_frac, flush_frac]."""
    n = jnp.maximum(counts[..., 2], 1.0)
    return jnp.stack([counts[..., 0] / n, counts[..., 1] / n], axis=-1)
