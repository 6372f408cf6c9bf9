"""Pallas TPU kernel: stochastic rounding f32/bf16 -> fp8 (paper §3.2).

TPU adaptation of the paper's SR: the paper argues SR belongs in the
*epilogue*, not in the MAC path — on TPU that means a VPU pass over the
output tile while it is still in VMEM. The rounding itself is the exact
fp16 bit-twiddle (add uniform random bits below the kept mantissa, then
truncate; e4m3 goes through a power-of-two prescale first), shared
bit-for-bit with repro.core.quantize.sr_fp8_via_f16 — the kernel is
format-parameterized over float8_e5m2 and float8_e4m3fn.

Randomness: two sources, selected at trace time —
 * rand operand (uint8 tile streamed from HBM) — validated in interpret mode
   on CPU; costs 1 byte/element of extra HBM read.
 * on-chip PRNG (pltpu.prng_seed + prng_random_bits) — the production TPU
   path, zero extra HBM traffic. Not executable in CPU interpret mode (the
   interpreter stubs the PRNG), so it is exercised only when a real TPU is
   attached.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fp8_formats import get_format
from repro.core.quantize import sr_fp8_via_f16

# Block shape: 8x128 VPU lanes; 512x1024 f32 = 2 MiB in + 0.5 MiB out per
# block — comfortably inside a 16 MiB VMEM with double buffering.
DEFAULT_BLOCK = (512, 1024)


def _sr_body(x_ref, rand_ref, scale_ref, o_ref, *, fmt_name: str,
             saturate: bool):
    fmt = get_format(fmt_name)
    inv = 1.0 / scale_ref[0]
    y = x_ref[...].astype(jnp.float32) * inv
    o_ref[...] = sr_fp8_via_f16(y, rand_ref[...], fmt, saturate=saturate)


def _sr_body_onchip(seed_ref, x_ref, scale_ref, o_ref, *, fmt_name: str,
                    saturate: bool):
    fmt = get_format(fmt_name)
    # Per-block seed decorrelation: fold the grid position into the seed.
    i, j = pl.program_id(0), pl.program_id(1)
    pltpu.prng_seed(seed_ref[0] + i * pl.num_programs(1) + j)
    r = pltpu.prng_random_bits(x_ref.shape)
    r8 = (r & 0xFF).astype(jnp.uint16)
    inv = 1.0 / scale_ref[0]
    y = x_ref[...].astype(jnp.float32) * inv
    o_ref[...] = sr_fp8_via_f16(y, r8, fmt, saturate=saturate)


def sr_quantize_kernel(x, rand8, scale, *, block=DEFAULT_BLOCK,
                       fmt: str = "e5m2", saturate: bool = True,
                       interpret: bool = False):
    """x: (M, N) float; rand8: (M, N) uint8; scale: (1,) f32 -> (M, N) fp8."""
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_sr_body, fmt_name=fmt, saturate=saturate),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), get_format(fmt).dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(x, rand8, scale)


def sr_quantize_kernel_onchip(x, seed, scale, *, block=DEFAULT_BLOCK,
                              fmt: str = "e5m2", saturate: bool = True):
    """Production TPU variant using the on-chip PRNG (no rand operand)."""
    m, n = x.shape
    bm, bn = min(block[0], m), min(block[1], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_sr_body_onchip, fmt_name=fmt, saturate=saturate),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), get_format(fmt).dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(seed, x, scale)
