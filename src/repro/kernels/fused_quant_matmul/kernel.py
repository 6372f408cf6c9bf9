"""Pallas TPU kernel: FP8 matmul with the quantize epilogue FUSED in VMEM.

Beyond-paper optimization. The paper's dataflow materializes the FP32 GEMM
output to memory and then applies the Q node (down-convert + round) as a
separate op — on TPU that is an extra HBM round-trip of 4 bytes/element out +
4 in + 1 out. Fusing Q into the matmul epilogue means the f32 accumulator
tile is scaled and rounded to fp8 *while still in VMEM*, writing only
1 byte/element to HBM: an 8x reduction in epilogue write traffic and the
elimination of the Q-node read pass entirely.

Rounding in the epilogue supports both RNE (deterministic, the correctly-
rounded single-rounding path shared with core.quantize.quantize_rne) and SR
(the exact fp16 bit-twiddle shared with core.quantize), matching the paper's
Q-node semantics. This is precisely the paper's architectural argument —
"rounding belongs in the epilogue, not the MAC" — taken one step further:
the epilogue never leaves the chip.

Three contraction layouts cover the full training step (qeinsum fwd/bwd):

    dims="nn"   out = A    @ B     A:(M,K)  B:(K,N)   forward  Y = Q(A.W)
    dims="nt"   out = A    @ B^T   A:(M,C)  B:(N,C)   dgrad   dA = Q(dY.W^T)
    dims="tn"   out = A^T  @ B     A:(C,M)  B:(C,N)   wgrad   dW = Q(A^T.dY)

The transposed layouts index the k-sweep over the *contraction* axis of each
operand in HBM, so no materialized transpose (and no extra HBM pass) is ever
needed for the backward GEMMs.

The optional amax epilogue output is reported in *grid units* (the max |q|
of the quantized fp8 values, before de-scaling) and masked to the logical
(m, n) region, so zero-padded tiles can never leak into the delayed-scaling
observation.

Operands hold fp8 values but may arrive in either dtype, each on its own:
fp8 (1 byte; the tile is widened to bf16 in VMEM on every k step) or bf16
already widened by the caller (2 bytes; no per-step cast). bf16 holds every
e4m3 and e5m2 value exactly, so the products, the f32 accumulator and every
output are bit-identical either way. `ops.fused_quant_matmul` widens an
operand in HBM when the grid visits each of its tiles more than once
(`widened_operands`); v5e has no FP8 unit, so the per-step cast is VPU work
that would otherwise be repeated on every visit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fp8_formats import get_format
from repro.core.quantize import quantize_rne, sr_fp8_via_f16

DEFAULT_BM = 256
DEFAULT_BK = 512
DEFAULT_BN = 256
STATS_TILE = (8, 128)   # one f32 (sublane, lane) tile of stats per grid cell

DIMS = ("nn", "nt", "tn")


def _quantize_tile(acc, rand8, inv_scale, *, fmt_name: str, rounding: str,
                   saturate: bool):
    fmt = get_format(fmt_name)
    y = acc * inv_scale
    if rounding == "rne":
        # The correctly-rounded f32 path (single rounding + explicit
        # overflow semantics) — the same function the unfused Q node uses,
        # so fused and unfused payloads are bit-identical by construction.
        return quantize_rne(y, fmt, saturate=saturate)
    return sr_fp8_via_f16(y, rand8, fmt, saturate=saturate)


def _tile_dot(a, b, dims: str):
    """f32-accumulated bf16 tile contraction for one k step of `dims`; the
    cast is a no-op for an operand that arrives in bf16."""
    a = a.astype(jnp.bfloat16)
    b = b.astype(jnp.bfloat16)
    if dims == "nn":      # (bm, bk) x (bk, bn)
        contract = (((1,), (0,)), ((), ()))
    elif dims == "nt":    # (bm, bk) x (bn, bk)
        contract = (((1,), (1,)), ((), ()))
    else:                 # "tn": (bk, bm) x (bk, bn)
        contract = (((0,), (0,)), ((), ()))
    return jax.lax.dot_general(a, b, contract,
                               preferred_element_type=jnp.float32)


def _amax_mask(i, j, bm: int, bn: int, m: int, n: int):
    """Validity mask of output tile (i, j) of shape (bm, bn) against the
    logical (m, n) bounds — padded rows/cols are excluded from the amax
    epilogue so the observation is invariant to the (bm, bk, bn) tiling
    choice."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) + i * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) + j * bn
    return (rows < m) & (cols < n)


def _body(a_ref, b_ref, rand_ref, scale_ref, o_ref, *refs,
          dims: str, fmt_name: str, rounding: str, saturate: bool, n_k: int,
          m: int, n: int, with_amax: bool, with_counts: bool):
    """k-sweep accumulation plus the Q-node epilogue. With `with_amax`, the
    epilogue also writes the tile's observation into its (8, 128) stats
    block, for delayed scaling: the observed amax of the quantized tile,
    computed from the fp8 values while they are STILL IN VMEM, so the
    observation costs no extra pass over HBM (a separate amax op re-reads
    the whole output). The amax is in grid units (max |q| of the quantized
    values, no scale multiply) and masked to the logical (m, n) region,
    exactly matching the bit-pattern reduction core.quantize.fp8_amax_bits
    performs on a materialized payload.

    `with_counts` adds the precision-health counts (repro.obs): how many
    quantized values landed at/above the format ceiling (saturated — inf/nan
    from non-saturating error outputs included) and how many below
    min_normal (flushed: exact zeros + subnormals), masked like the amax.
    The quantize computation is untouched: counts on/off is bit-identical
    output (the repro.obs parity law).

    Stats block rows (each broadcast over the 128 lanes): 0 amax, 1
    saturated count, 2 flushed count, the rest zero. A per-cell scalar
    block would be (1, 1), which the TPU's (8, 128) tiling refuses."""
    stats_ref = refs[0] if with_amax else None
    acc_ref = refs[-1]

    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += _tile_dot(a_ref[...], b_ref[...], dims)

    # Read at body top level: interpret mode does not substitute
    # program_id inside pl.when sub-jaxprs, so the epilogue closes over
    # the indices and builds the mask on the last k step only.
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _epilogue():
        inv = 1.0 / scale_ref[0]
        q = _quantize_tile(acc_ref[...], rand_ref[...], inv,
                           fmt_name=fmt_name, rounding=rounding,
                           saturate=saturate)
        o_ref[...] = q
        if stats_ref is None:
            return
        mask = _amax_mask(i, j, *acc_ref.shape, m, n)
        qf = q.astype(jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, stats_ref.shape, 0)
        stats = jnp.where(row == 0,
                          jnp.max(jnp.where(mask, jnp.abs(qf), 0.0)), 0.0)
        if with_counts:
            fmt = get_format(fmt_name)
            sat = (jnp.abs(qf) >= jnp.float32(fmt.max_normal)) \
                | ~jnp.isfinite(qf)
            flush = jnp.abs(qf) < jnp.float32(fmt.min_normal)
            stats = jnp.where(
                row == 1, jnp.sum(jnp.where(mask & sat, 1.0, 0.0)), stats)
            stats = jnp.where(
                row == 2, jnp.sum(jnp.where(mask & flush, 1.0, 0.0)), stats)
        stats_ref[...] = stats


def _block_specs(dims: str, bm: int, bk: int, bn: int):
    if dims == "nn":
        return [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]
    if dims == "nt":
        return [pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
                pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk))]
    # "tn"
    return [pl.BlockSpec((bk, bm), lambda i, j, kk: (kk, i)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j))]


def gemm_shape(a_shape, b_shape, dims: str):
    """(M, N, C): logical output dims + contraction dim for a `dims` GEMM."""
    if dims == "nn":
        (m, c), (c2, n) = a_shape, b_shape
    elif dims == "nt":
        (m, c), (n, c2) = a_shape, b_shape
    elif dims == "tn":
        (c, m), (c2, n) = a_shape, b_shape
    else:
        raise ValueError(f"unknown dims {dims!r}; expected one of {DIMS}")
    assert c == c2, (a_shape, b_shape, dims)
    return m, n, c


def widened_operands(m: int, n: int, bm: int, bn: int):
    """(widen_a, widen_b): which operands the caller widens to bf16 in HBM
    before the call. The (i, j, k) grid reads each A tile once per output
    column block, cdiv(n, bn) times, and each B tile once per output row
    block, cdiv(m, bm) times, in every layout. An operand read more than
    once is widened in one pass rather than cast again on every visit; one
    read once (a decode call's weight, M <= bm) stays fp8 and is cast in
    the kernel, which costs no extra pass over it."""
    return pl.cdiv(n, bn) > 1, pl.cdiv(m, bm) > 1


def fused_quant_matmul_kernel(a, b, rand8, scale, *,
                              dims: str = "nn",
                              bm=DEFAULT_BM, bk=DEFAULT_BK, bn=DEFAULT_BN,
                              out_format: str = "e5m2",
                              rounding: str = "sr", saturate: bool = True,
                              with_amax: bool = False,
                              with_counts: bool = False,
                              logical_mn=None,
                              interpret: bool = False):
    """fp8 GEMM (layout per `dims`, see module docstring) with the Q node in
    the epilogue: out = Q((a . b) / scale) -> (M, N) fp8 in `out_format`.
    a, b: fp8, or bf16 holding fp8 values (each operand on its own).
    rand8: (M, N) u8 SR bits, scale: (1,) f32.

    with_amax=True additionally returns a (grid_m, grid_n) f32 array of
    per-tile observed amaxes in grid units (reduce with jnp.max for the
    scalar; multiply by the dequantization scale for real units), masked to
    `logical_mn` (defaults to the padded (M, N)).

    with_counts=True (requires with_amax) further returns two (grid_m,
    grid_n) f32 arrays of per-tile saturated / flushed value counts
    (precision-health counters, see repro.obs.counters) — reduce with
    jnp.sum and divide by the logical element count for fractions."""
    m, n, k = gemm_shape(a.shape, b.shape, dims)
    bm, bk, bn = min(bm, m), min(bk, k), min(bn, n)
    lm, ln = logical_mn if logical_mn is not None else (m, n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    in_specs = _block_specs(dims, bm, bk, bn) + [
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    common = dict(
        grid=grid,
        in_specs=in_specs,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )
    if with_counts and not with_amax:
        raise ValueError("with_counts requires with_amax")
    out_block = pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j))
    out_shape = jax.ShapeDtypeStruct((m, n), get_format(out_format).dtype)
    if with_amax:
        tr, tc = STATS_TILE
        out_block = (out_block,
                     pl.BlockSpec(STATS_TILE, lambda i, j, kk: (i, j)))
        out_shape = (out_shape, jax.ShapeDtypeStruct(
            (grid[0] * tr, grid[1] * tc), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_body, dims=dims, fmt_name=out_format,
                          rounding=rounding, saturate=saturate, n_k=grid[2],
                          m=lm, n=ln, with_amax=with_amax,
                          with_counts=with_counts),
        out_specs=out_block,
        out_shape=out_shape,
        name=f"fused_quant_matmul_{dims}",
        **common,
    )(a, b, rand8, scale)
    if not with_amax:
        return out
    q, stats = out
    # Lane 0 of each stats row holds the per-cell value: (grid_m, grid_n).
    stats = stats.reshape(grid[0], tr, grid[1], tc)[:, :, :, 0]
    if not with_counts:
        return q, stats[:, 0]
    return q, stats[:, 0], stats[:, 1], stats[:, 2]
