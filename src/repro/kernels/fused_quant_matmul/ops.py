"""Jit'd public wrapper for fused_quant_matmul.

The wrapper hands the kernel each operand either as the fp8 payload or
widened to bf16 by one XLA convert (exact: bf16 holds every e4m3 and e5m2
value). The rule is `kernel.widened_operands` on the resolved blocks: an
operand whose tiles the grid reads more than once is widened, since the
kernel would otherwise cast the same fp8 tile to bf16 on every visit (v5e
has no FP8 unit, so that cast is VPU work on the k loop). An operand read
once, such as a decode GEMM's weight (M <= bm), stays fp8. The convert
runs under the `fp8.quant` scope; the caller's fp8 payloads (the custom-VJP
residuals) are untouched.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels.fused_quant_matmul import kernel as _k
from repro.obs.trace import scope


def _pad_to(x, mult0, mult1):
    p0 = (-x.shape[0]) % mult0
    p1 = (-x.shape[1]) % mult1
    if p0 or p1:
        x = jnp.pad(x, ((0, p0), (0, p1)))
    return x


@functools.partial(jax.jit, static_argnames=("dims", "bm", "bk", "bn",
                                             "autotune",
                                             "out_format", "rounding",
                                             "saturate", "with_amax",
                                             "with_counts",
                                             "amax_units", "interpret"))
def fused_quant_matmul(a, b, key, scale=None, *,
                       dims: str = "nn",
                       bm=None, bk=None, bn=None,
                       autotune: str = "table",
                       out_format: str = "e5m2",
                       rounding: str = "sr", saturate: bool = True,
                       with_amax: bool = False,
                       with_counts: bool = False,
                       amax_units: str = "real",
                       interpret: bool = False):
    """Q((a . b) / scale) -> fp8 in `out_format` ('e5m2' | 'e4m3'), with the
    Q node fused into the epilogue. `dims` selects the contraction layout
    ('nn' A@B, 'nt' A@B^T, 'tn' A^T@B — see kernel module docstring); the
    transposed layouts serve the dgrad/wgrad GEMMs without materializing a
    transpose.

    with_amax=True returns (out, amax): the observed amax of the quantized
    output (delayed-scaling observation), computed in the epilogue while the
    tile is still in VMEM — no extra pass over HBM. amax_units='real'
    (default) de-scales the observation back to input units; 'grid' returns
    the raw max |q| over the fp8 grid, bit-identical to what the bit-pattern
    reduction core.quantize.fp8_amax_bits would report on the payload.

    SR random bits are drawn over the *logical* (m, n) output and zero-padded
    alongside the operands, and the amax epilogue masks the padded region, so
    results are invariant to the (bm, bk, bn) tiling choice.

    a and b are fp8 payloads; each one whose tiles the kernel grid reads
    more than once enters the kernel widened to bf16 (module docstring).

    bm/bk/bn default to None: unset knobs resolve through the block-size
    autotuner winners table (`autotune`: "table" = the shipped /
    $REPRO_AUTOTUNE_TABLE table, "off" = built-in defaults, or a table
    path — see kernels.autotune) and fall back to the built-in defaults.
    Explicit ints always win. Resolution happens at trace time, per
    logical shape.

    with_counts=True (requires with_amax) returns (out, amax, health) where
    health is a (2,) f32 [saturated_fraction, flushed_fraction] of the
    logical output — the repro.obs precision-health counters, taken from the
    quantized tile in the same VMEM epilogue as the amax (no extra HBM
    pass). The quantize math is identical with counts on or off.
    """
    m, n, c = _k.gemm_shape(a.shape, b.shape, dims)
    bm, bk, bn = _at.resolve_gemm_blocks(
        dims, m, c, n, out_format=out_format, bm=bm, bk=bk, bn=bn,
        autotune=autotune,
        defaults=(_k.DEFAULT_BM, _k.DEFAULT_BK, _k.DEFAULT_BN))
    if scale is None:
        scale = jnp.ones((1,), jnp.float32)
    scale = jnp.asarray(scale, jnp.float32).reshape((1,))
    bm_ = min(bm, max(8, m))
    bn_ = min(bn, max(128, n))
    bk_ = min(bk, max(128, c))
    if dims == "nn":
        ap, bp = _pad_to(a, bm_, bk_), _pad_to(b, bk_, bn_)
    elif dims == "nt":
        ap, bp = _pad_to(a, bm_, bk_), _pad_to(b, bn_, bk_)
    else:  # "tn"
        ap, bp = _pad_to(a, bk_, bm_), _pad_to(b, bk_, bn_)
    widen_a, widen_b = _k.widened_operands(m, n, bm_, bn_)
    with scope("fp8.quant"):
        # The barrier keeps each convert a pass of its own. Fused into the
        # producer of the payload, it changes how XLA compiles that
        # producer on the TPU, and the training step's bits drift from
        # the unwidened program's; kept apart, they match bit for bit.
        if widen_a:
            ap = jax.lax.optimization_barrier(ap).astype(jnp.bfloat16)
        if widen_b:
            bp = jax.lax.optimization_barrier(bp).astype(jnp.bfloat16)
    # Draw SR bits for the logical cells only; padded cells get zero bits
    # (their zero accumulator then stays exactly zero under SR truncation).
    with scope("fp8.sr_bits"):
        rand8 = jax.random.bits(key, (m, n), jnp.uint8) \
            if rounding == "sr" else jnp.zeros((m, n), jnp.uint8)
    rand8 = _pad_to(rand8, bm_, bn_)
    out = _k.fused_quant_matmul_kernel(ap, bp, rand8, scale,
                                       dims=dims, bm=bm_, bk=bk_, bn=bn_,
                                       out_format=out_format,
                                       rounding=rounding, saturate=saturate,
                                       with_amax=with_amax,
                                       with_counts=with_counts,
                                       logical_mn=(m, n),
                                       interpret=interpret)
    if with_amax:
        health = None
        with scope("fp8.amax"):
            if with_counts:
                out, tile_amax, tile_sat, tile_flush = out
                health = jnp.stack([jnp.sum(tile_sat),
                                    jnp.sum(tile_flush)]) / jnp.float32(m * n)
            else:
                out, tile_amax = out
            amax = jnp.max(tile_amax)
        if amax_units == "real":
            amax = amax * scale[0]
        elif amax_units != "grid":
            raise ValueError(f"unknown amax_units {amax_units!r}")
        if with_counts:
            return out[:m, :n], amax, health
        return out[:m, :n], amax
    return out[:m, :n]
