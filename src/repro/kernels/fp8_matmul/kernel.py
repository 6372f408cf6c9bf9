"""Pallas TPU kernel: FP8 x FP8 -> FP32-accumulated matmul (paper Fig. 1a).

TPU adaptation of the paper's FP8 GEMM primitive. The v5e MXU has no FP8
datapath, so FP8 here is a *memory* format (that is the paper's own stance:
FP32 accumulation, rounding in the epilogue, no exotic MAC hardware):

  HBM:  A (M,K) e5m2, B (K,N) e5m2      — half the bytes of bf16, quarter f32
  VMEM: tiles up-converted e5m2 -> bf16  — a VPU-register pass, no HBM traffic
  MXU:  bf16 x bf16 -> f32 accumulator scratch (paper: "32-bit accumulator")
  out:  f32 accumulator cast to out_dtype on the last K step

Blocking: (bm, bk) x (bk, bn) with K innermost ("arbitrary" semantics) so the
f32 accumulator tile lives in VMEM scratch across the K sweep. Default tiles
(256, 512, 256): A-tile 128 KiB + B-tile 128 KiB (fp8 bytes) + acc 256 KiB —
~0.5 MiB working set, leaving VMEM room for double buffering. All dims are
multiples of the 128-lane MXU width.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256


def _mm_body(a_ref, b_ref, o_ref, acc_ref, *, out_dtype, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...].astype(jnp.bfloat16)   # e5m2 -> bf16 up-convert in VMEM
    b = b_ref[...].astype(jnp.bfloat16)
    acc_ref[...] += jnp.dot(a, b, preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        o_ref[...] = acc_ref[...].astype(out_dtype)


def fp8_matmul_kernel(a, b, *, bm=DEFAULT_BM, bk=DEFAULT_BK, bn=DEFAULT_BN,
                      out_dtype=jnp.float32, interpret: bool = False):
    """a: (M, K) fp8, b: (K, N) fp8 -> (M, N) out_dtype. Dims must divide."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_mm_body, out_dtype=out_dtype, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)
