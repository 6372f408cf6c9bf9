"""Quantization primitives: RNE and stochastic rounding into FP8.

This is the software realization of the paper's `Q` nodes (Fig. 1a): each GEMM
produces a 32-bit result which is down-converted + rounded to FP8 before the
next op. Two rounding modes, per paper §3.2:

 * RNE  (round-to-nearest-even): what commodity hardware implements; shown by
   the paper to be sufficient for small nets but to cause generalization loss
   on ResNet-50 (unconstrained parameter growth).
 * SR   (stochastic rounding): round(x) = floor(x) + eps with probability
   (x - floor(x))/eps. The paper applies SR to activations and gradients and
   recovers (slightly beats) the FP32 baseline.

SR is implemented *exactly* with an fp16 bit-twiddle for BOTH fp8 formats.
E5M2 is the top byte of an IEEE fp16, so adding a uniform 8-bit integer to
the fp16 bit pattern and truncating the low byte performs stochastic rounding
on the real line (bit patterns are monotone in magnitude, and mantissa
carries propagate into the exponent, handling binade crossings and the
subnormal/normal boundary for free). E4M3 embeds the same way after a
power-of-two prescale (x * 2^-8) that aligns its subnormal threshold with
fp16's: every e4m3 grid point then maps to an fp16 pattern whose low 7 bits
are zero — including the subnormals, which land in fp16's fixed-point
subnormal range — so adding 7 uniform random bits and truncating is again
exact SR. See `sr_fp8_from_bits` / `sr_fp8_via_f16`, the single bit-twiddle
source of truth shared verbatim with the Pallas kernels
(kernels/stochastic_round, kernels/fused_quant_matmul) and their ref
oracles, so ops and kernels are bit-identical by construction.

Note on double rounding: inputs are first converted f32->f16 with RNE, then
stochastically rounded f16->e5m2. The intermediate RNE step contributes a
relative error <= 2^-11, i.e. 256x smaller than the e5m2 machine epsilon
(2^-2); the residual bias is far below the quantization noise floor and is
bounded in tests.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fp8_formats import E4M3, E5M2, FloatFormat, get_format
from repro.obs.trace import scope

Array = jax.Array

_F16_EXP_MASK = 0x7C00  # fp16 exponent field (all-ones => inf/nan)
_F16_MAG_MASK = 0x7FFF
_F16_SIGN_MASK = 0x8000


def _f16_bits_i32(x: Array) -> Array:
    """IEEE f32 -> f16 round-to-nearest-even, returned as the f16 bit pattern
    in an int32 — bit-identical to `x.astype(float16)`, but in 32-bit
    integer and f32 ops only: Mosaic cannot lower an f32 -> f16 cast on
    v5e, so the Pallas epilogues reach fp16 bit patterns this way.

    |x| is rounded onto the f16 grid in f32 (the ulp is a power of two and
    the multiple fits the f32 mantissa, so both scalings are exact and
    `round` is the single rounding); the on-grid value's f16 pattern is
    then read off its f32 bits (normals) or off `q * 2**24` (subnormals).
    Overflow rounds to inf; NaN keeps its top payload bits, quiet bit set."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    sign = (b >> 16) & jnp.int32(_F16_SIGN_MASK)
    ab = b & jnp.int32(0x7FFFFFFF)
    ax = jax.lax.bitcast_convert_type(ab, jnp.float32)
    # Biased f32 exponent, floored at f16's min normal (2**-14 -> 113).
    e = jnp.maximum(ab >> 23, jnp.int32(113))
    ulp = jax.lax.bitcast_convert_type((e - 10) << 23, jnp.float32)
    inv_ulp = jax.lax.bitcast_convert_type((264 - e) << 23, jnp.float32)
    q = jnp.round(ax * inv_ulp) * ulp
    qb = jax.lax.bitcast_convert_type(q, jnp.int32)
    normal = (qb - jnp.int32(112 << 23)) >> 13
    sub = jax.lax.bitcast_convert_type(q * jnp.float32(2.0 ** 24)
                                       + jnp.float32(2.0 ** 23),
                                       jnp.int32) & jnp.int32(0x7FF)
    h = jnp.where(q >= jnp.float32(2.0 ** -14), normal, sub)
    h = jnp.where(q >= jnp.float32(65536.0), jnp.int32(_F16_EXP_MASK), h)
    nan = jnp.int32(_F16_EXP_MASK | 0x0200) | ((ab >> 13) & jnp.int32(0x3FF))
    h = jnp.where(ab > jnp.int32(0x7F800000), nan, h)
    return sign | h


def _fp8_from_f16_bits(h: Array, fmt: FloatFormat) -> Array:
    """fmt.dtype values of prescaled, on-grid fp16 patterns held in an int32
    (the twiddle's output). The prescale aligns the two exponent fields, so
    the fp8 byte is the sign plus the pattern's top bits; the specials follow
    the f16 -> fp8 storage cast: an e5m2 NaN is 0x7F, and the inf-less fn
    formats (e4m3) turn any non-finite value into a NaN that keeps its
    sign."""
    spec = sr_spec(fmt)
    sign = (h >> 8) & jnp.int32(0x80)
    mag = h & jnp.int32(_F16_MAG_MASK)
    byte = sign | (mag >> spec.drop_bits)
    if fmt.has_inf:
        byte = jnp.where(mag > jnp.int32(_F16_EXP_MASK), jnp.int32(0x7F),
                         byte)
    else:
        byte = jnp.where(mag >= jnp.int32(_F16_EXP_MASK),
                         sign | jnp.int32(0x7F), byte)
    return jax.lax.bitcast_convert_type(byte.astype(jnp.uint8), fmt.dtype)


# ---------------------------------------------------------------------------
# RNE quantization
# ---------------------------------------------------------------------------

def rne_overflow_threshold(fmt: FloatFormat) -> float:
    """Smallest |x| that RNE rounds to infinity (midpoint of max_normal and
    the next power of two)."""
    return (fmt.max_normal + 2.0 ** (fmt.max_exp + 1)) / 2.0


def _rne_on_grid_f32(x: Array, fmt: FloatFormat) -> Array:
    """Correctly-rounded (single-rounding) RNE of f32 onto fmt's value grid.

    XLA lowers f32 -> fp8 casts through an f16 intermediate, which double-
    rounds values near fp8 halfway points (~0.1% of a log-uniform sample).
    This decomposes |x| into (ulp, multiple-of-ulp) exactly — ulp is a power
    of two and the multiple fits in the f32 mantissa — and applies
    ties-to-even on the exact ratio, matching ml_dtypes bit-for-bit. The
    returned value is on-grid (or the next power of two on binade carry), so
    the subsequent storage-dtype cast is exact."""
    xf = x.astype(jnp.float32)
    ax = jnp.abs(xf)
    xb = jax.lax.bitcast_convert_type(ax, jnp.uint32)
    e = jnp.maximum((xb >> 23).astype(jnp.int32) - 127, fmt.min_exp)
    ulp = jnp.exp2((e - fmt.man_bits).astype(jnp.float32))
    # copysign (not sign*) so signed zero survives the round trip.
    return jnp.copysign(jnp.round(ax / ulp) * ulp, xf)


def quantize_rne(x: Array, fmt: FloatFormat = E5M2, *, saturate: bool = True) -> Array:
    """Round-to-nearest-even down-conversion into `fmt`'s storage dtype.

    saturate=True clamps overflow to +-max_normal (forward tensors);
    saturate=False lets overflow become +-inf (error/grad tensors, so the
    dynamic loss scaler can detect it and back off — paper §3.1).
    """
    if fmt.dtype is None:
        raise ValueError(f"format {fmt.name} has no storage dtype")
    # Dtype-preserving: all elementwise work stays in x's dtype (bf16 grads
    # would otherwise materialize f32 copies of every weight-grad tensor —
    # measured as the dominant training-memory term at 123B scale). The fp8
    # grid bounds are exactly representable in bf16/f16/f32.
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    if x.dtype in (jnp.float16, jnp.bfloat16):
        # Narrow inputs convert correctly through XLA's cast chain (bf16 ->
        # f16 is exact at fp8-surviving magnitudes, f16 -> fp8 rounds once).
        q = x.astype(fmt.dtype)
        rounded = x   # clamping below re-rounds via the same exact chain
    else:
        # Wide inputs need the explicit single-rounding grid path (XLA's
        # cast would double-round through f16 — see _rne_on_grid_f32).
        on_grid = _rne_on_grid_f32(x, fmt)
        rounded = jnp.where(jnp.isfinite(x), on_grid, x.astype(jnp.float32))
        q = rounded.astype(fmt.dtype)
    if saturate:
        # XLA's f32->f8 conversion saturates for e5m2 and produces NaN for
        # e4m3fn overflow; normalize both to explicit clamping (of the
        # already-rounded value, so clamping never re-rounds inexactly).
        lo = jnp.asarray(-fmt.max_normal, rounded.dtype)
        hi = jnp.asarray(fmt.max_normal, rounded.dtype)
        clamped = jnp.clip(rounded, lo, hi)
        q = jnp.where(jnp.isfinite(x), clamped.astype(fmt.dtype), q)
    else:
        thresh = jnp.asarray(rne_overflow_threshold(fmt), jnp.float32)
        overflow = jnp.abs(x.astype(jnp.float32)) >= thresh \
            if x.dtype == jnp.float16 else jnp.abs(x) >= thresh.astype(x.dtype)
        inf = jnp.asarray(jnp.inf, x.dtype) * jnp.sign(x)
        # e4m3fn has no inf encoding; overflow becomes NaN (still non-finite,
        # still detectable by the loss scaler).
        q = jnp.where(overflow & jnp.isfinite(x),
                      inf.astype(fmt.dtype) if fmt.has_inf
                      else jnp.asarray(jnp.nan, fmt.dtype),
                      q)
    return q


# ---------------------------------------------------------------------------
# Stochastic rounding
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SRSpec:
    """fp16-embedding constants for exact SR into one fp8 format.

    An fp8 format with m mantissa bits embeds into fp16 under the
    power-of-two prescale 2**pre_exp that moves its subnormal threshold onto
    fp16's (min_exp -> -14): every grid point of the prescaled format is then
    an fp16 bit pattern whose low (10 - m) bits are zero, subnormals
    included, and SR = add (10 - m) uniform random bits + truncate.
    """
    pre_exp: int      # prescale exponent: twiddle on bits of x * 2**pre_exp
    drop_bits: int    # 10 - man_bits: random/truncated low mantissa bits
    max_bits: int     # fp16 pattern of the prescaled fmt.max_normal
    ovf_bits: int     # pattern on round-up past max: inf (IEEE) / NaN (fn)


@functools.lru_cache(maxsize=None)
def sr_spec(fmt: FloatFormat) -> SRSpec:
    pre_exp = -14 - fmt.min_exp
    if fmt.man_bits > 10 or fmt.max_normal * 2.0 ** pre_exp > 65504.0:
        raise ValueError(f"format {fmt.name} does not embed in fp16")
    max_bits = int(np.float16(fmt.max_normal * 2.0 ** pre_exp)
                   .view(np.uint16))
    return SRSpec(pre_exp=pre_exp, drop_bits=10 - fmt.man_bits,
                  max_bits=max_bits,
                  ovf_bits=_F16_EXP_MASK if fmt.has_inf else 0x7E00)


def _sr_twiddle(h_bits: Array, rand: Array, spec: SRSpec, *,
                saturate: bool) -> Array:
    """The SR bit-twiddle on prescaled fp16 patterns held in int32 (the
    sums stay below 2**16, so this is the uint16 math in wider lanes)."""
    mask = jnp.int32((1 << spec.drop_bits) - 1)
    sign = h_bits & jnp.int32(_F16_SIGN_MASK)
    mag = h_bits & jnp.int32(_F16_MAG_MASK)
    finite = mag < jnp.int32(_F16_EXP_MASK)
    bumped = mag + (rand.astype(jnp.int32) & mask)
    trunc = bumped & ~mask
    if saturate:
        trunc = jnp.minimum(trunc, jnp.int32(spec.max_bits))
    else:
        # Rounding up past max normal overflows: to the inf pattern for IEEE
        # formats (e5m2: 0x7B00 + 0x100 lands exactly on 0x7C00), to a NaN
        # pattern for the inf-less fn formats (e4m3).
        trunc = jnp.where(trunc > jnp.int32(spec.max_bits),
                          jnp.int32(spec.ovf_bits), trunc)
    out_mag = jnp.where(finite, trunc, mag & ~mask | (mag & jnp.int32(0x0200)))
    # (non-finite: preserve inf/nan; keep a nan-signalling mantissa bit)
    return sign | out_mag


def sr_fp8_from_bits(h_bits: Array, rand: Array, fmt: FloatFormat = E5M2, *,
                     saturate: bool = True) -> Array:
    """Exact fp8 stochastic rounding given *prescaled* fp16 bit patterns plus
    random bits (only the low `drop_bits` are used; masking a wider uniform
    draw is fine). Pure integer math — the same twiddle the Pallas kernel
    bodies run through `sr_fp8_via_f16`. The result is the prescaled uint16
    fp16 pattern; undo the prescale before casting to fmt.dtype
    (`sr_fp8_via_f16` does both ends).
    """
    out = _sr_twiddle(h_bits.astype(jnp.int32), rand, sr_spec(fmt),
                      saturate=saturate)
    return out.astype(jnp.uint16)


def sr_e5m2_from_bits(h_bits: Array, rand8: Array, *,
                      saturate: bool = True) -> Array:
    """Back-compat alias for the e5m2-hardwired helper name."""
    return sr_fp8_from_bits(h_bits, rand8, E5M2, saturate=saturate)


def sr_fp8_via_f16(x: Array, rand: Array, fmt: FloatFormat = E5M2, *,
                   saturate: bool = True) -> Array:
    """Stochastically round `x` into fmt.dtype via the exact fp16 bit-twiddle
    (prescale -> f16 pattern -> twiddle -> fp8 byte), in 32-bit ops only so
    the Pallas epilogues lower on v5e. `rand` supplies the random bits
    (uint; low `sr_spec(fmt).drop_bits` used)."""
    spec = sr_spec(fmt)
    if saturate:
        # Clamp before the f16 step so |x| beyond fp16 range cannot escape to
        # inf around the bit-twiddle's finite-only path. Dtype-preserving:
        # the fp8 max normals are exact in bf16/f16/f32.
        lo = jnp.asarray(-fmt.max_normal, x.dtype)
        hi = jnp.asarray(fmt.max_normal, x.dtype)
        x = jnp.where(jnp.isnan(x), x, jnp.clip(x, lo, hi))
    if spec.pre_exp:
        x = x * jnp.asarray(2.0 ** spec.pre_exp, x.dtype)
    out_bits = _sr_twiddle(_f16_bits_i32(x), rand, spec, saturate=saturate)
    return _fp8_from_f16_bits(out_bits, fmt)


@scope("fp8.quant")
def quantize_sr_fp8(x: Array, key: Array, fmt: FloatFormat = E5M2, *,
                    saturate: bool = True) -> Array:
    """Stochastically round into an fp16-embeddable fp8 format (exact on the
    fp16 grid — the paper's SR, format-generalized)."""
    with scope("fp8.sr_bits"):
        rand = jax.random.bits(key, x.shape, jnp.uint16)
    return sr_fp8_via_f16(x, rand, fmt, saturate=saturate)


def quantize_sr_e5m2(x: Array, key: Array, *, saturate: bool = True) -> Array:
    """Back-compat alias: SR into e5m2 (the paper's format)."""
    return quantize_sr_fp8(x, key, E5M2, saturate=saturate)


@scope("fp8.quant")
def quantize_sr_grid(x: Array, fmt: FloatFormat, key: Array, *,
                     saturate: bool = True) -> Array:
    """Generic grid-based stochastic rounding (any format, e.g. E4M3).

    Decomposes |x| into (ulp, multiple-of-ulp) using the f32 exponent field,
    adds U[0,1) before flooring. All grid arithmetic is exact in f32 because
    ulp is a power of two and the mantissa multiple fits in 24 bits.
    """
    xf = x.astype(jnp.float32)
    ax = jnp.abs(xf)
    sgn = jnp.sign(xf)
    xb = jax.lax.bitcast_convert_type(ax, jnp.uint32)
    e_unb = (xb >> 23).astype(jnp.int32) - 127
    e = jnp.maximum(e_unb, fmt.min_exp)
    ulp_exp = e - fmt.man_bits
    ulp = jnp.exp2(ulp_exp.astype(jnp.float32))
    with scope("fp8.sr_bits"):
        r = jax.random.uniform(key, xf.shape, jnp.float32)
    q = jnp.floor(ax / ulp + r) * ulp
    if saturate:
        q = jnp.minimum(q, fmt.max_normal)
    else:
        q = jnp.where(q > fmt.max_normal, jnp.inf, q)
    q = jnp.where(jnp.isfinite(xf), sgn * q, xf)
    out = q.astype(fmt.dtype)
    if not saturate and not fmt.has_inf:
        out = jnp.where(jnp.isinf(q), jnp.asarray(jnp.nan, fmt.dtype), out)
    return out


def quantize_sr(x: Array, fmt: FloatFormat, key: Array, *,
                saturate: bool = True) -> Array:
    # Both fp8 storage formats use the exact fp16 bit-twiddle (one source of
    # truth with the Pallas kernels); the float grid path covers formats
    # without an fp16 embedding (emulation-only ablations).
    if fmt.name in ("e5m2", "e4m3"):
        return quantize_sr_fp8(x, key, fmt, saturate=saturate)
    return quantize_sr_grid(x, fmt, key, saturate=saturate)


# ---------------------------------------------------------------------------
# Scaled quantization (QTensor)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QTensor:
    """An FP8 payload plus a dequantization scale: x ~= data.astype(f32) * scale.

    `scale` is a scalar (per-tensor). The paper's loss scaling is *global*
    (applied to the loss), so training-path QTensors usually carry scale=1;
    per-tensor amax scaling (beyond-paper, cf. FP8-LM) sets
    scale = amax / fmt.max_normal.
    """
    data: Array
    scale: Array

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    def dequantize(self, dtype=jnp.float32) -> Array:
        return self.data.astype(jnp.float32) * self.scale.astype(jnp.float32) \
            if self.scale.ndim == 0 else \
            self.data.astype(jnp.float32) * self.scale[..., None].astype(jnp.float32)


@scope("fp8.amax")
def fp8_amax_bits(data: Array) -> Array:
    """amax of an FP8 tensor via its bit patterns — the delayed-scaling
    observation primitive. For sign-cleared fp8 encodings the bit pattern is
    monotone in magnitude, so the max over uint8 views IS the max magnitude:
    the reduction runs on 1-byte integers (no float upcast pass over the
    tensor, and in the jaxpr no reduce_max over a >=16-bit float appears —
    the property the hot-path op-count test checks). NaN payloads sort above
    inf and therefore propagate, which the history update guards against."""
    bits = jax.lax.bitcast_convert_type(data, jnp.uint8) & jnp.uint8(0x7F)
    return jax.lax.bitcast_convert_type(jnp.max(bits), data.dtype) \
        .astype(jnp.float32)


@scope("fp8.amax")
def amax_scale(x: Array, fmt: FloatFormat, *, margin: float = 1.0) -> Array:
    """Per-tensor scale mapping amax -> fmt.max_normal / margin. The abs/max
    reduce stays in x's dtype (no f32 copy); only the scalar is f32."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    amax = jnp.maximum(amax, 1e-12)
    return amax * margin / fmt.max_normal


@scope("fp8.quant")
def quantize(x: Array, fmt: Union[str, FloatFormat] = E5M2, *,
             rounding: str = "rne",
             key: Optional[Array] = None,
             scale: Optional[Array] = None,
             use_amax_scale: bool = False,
             saturate: bool = True) -> QTensor:
    """Quantize into a QTensor. rounding in {'rne','sr'}; 'sr' requires key."""
    if isinstance(fmt, str):
        fmt = get_format(fmt)
    if not jnp.issubdtype(x.dtype, jnp.floating):
        x = x.astype(jnp.float32)
    explicit_scale = scale is not None
    if scale is None:
        scale = amax_scale(x, fmt) if use_amax_scale \
            else jnp.asarray(1.0, jnp.float32)
    scale = jnp.asarray(scale, jnp.float32)
    if use_amax_scale or explicit_scale \
            or (hasattr(scale, "shape") and scale.shape != ()):
        # Reciprocal-multiply path — shared by jit-amax and delayed scaling
        # so the two modes are bitwise identical given the same scale value.
        xs = x * (1.0 / scale).astype(x.dtype)
    else:
        # scale may be the static 1.0 default: keep the division but in
        # x's dtype so no f32 copy of the tensor is materialized.
        xs = x / scale.astype(x.dtype)
    if rounding == "rne":
        data = quantize_rne(xs, fmt, saturate=saturate)
    elif rounding == "sr":
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        data = quantize_sr(xs, fmt, key, saturate=saturate)
    else:
        raise ValueError(f"unknown rounding mode {rounding!r}")
    return QTensor(data=data, scale=scale)


def dequantize(q: QTensor, dtype=jnp.float32) -> Array:
    # Dequantize directly in the target dtype (no f32 intermediate copy).
    return q.data.astype(dtype) * q.scale.astype(dtype)


# Convenience: fake-quantize (quantize-dequantize) in one call — used by the
# emulation path on CPU and by tests as the semantic reference.
def fake_quant(x: Array, fmt: Union[str, FloatFormat] = E5M2, *,
               rounding: str = "rne", key: Optional[Array] = None,
               scale: Optional[Array] = None, saturate: bool = True) -> Array:
    q = quantize(x, fmt, rounding=rounding, key=key, scale=scale,
                 saturate=saturate)
    return dequantize(q, dtype=x.dtype)
