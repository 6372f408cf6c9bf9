"""Jit'd public wrappers for the streamed-KV fused FP8 attention kernels.

Padding contract: Q pads to a block_q multiple, the KV length to a block_kv
multiple (block_kv itself a LANE multiple, capped at the padded length so
short sequences keep one stripe), the head dim to a LANE (128) multiple —
all with zeros, which the shared stripe math makes numerically invisible
(exact-0.0 contributions; observations masked to the attended region), so
outputs and amaxes are invariant to padding and to the block_q / block_kv
choices. SR bits come from a counter-based hash of absolute coordinates
(ref.sr_hash_bits), so no rand array is ever materialized and every tiling
draws identical bits.

VMEM residency is O(block_q * D + block_kv * D) per grid step — the
sequence length only grows the grid, so 32k+ contexts train and serve
through the same kernels; causal / sliding-window tiles skip their
fully-masked stripes entirely (ref.kv_stripe_span).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune as _at
from repro.kernels.fp8_attention import kernel as _k
from repro.kernels.fp8_attention import ref as _r


def _health_frac(h):
    """(B, H, nq, 3) [sat, flush, observed] counts -> (2,) fractions."""
    tot = jnp.sum(h.reshape(-1, 3), axis=0)
    return tot[:2] / jnp.maximum(tot[2], 1.0)


@functools.partial(jax.jit, static_argnames=(
    "mask_mode", "window", "block_q", "block_kv", "autotune", "fmt_s",
    "fmt_p", "rounding_s", "rounding_p", "saturate_s", "saturate_p",
    "with_counts", "interpret"))
def fp8_attention_fwd(q8, k8, v8, seed, scal, *, mask_mode: str = "causal",
                      window: int = 0, kv_mask=None, chunk_pos=None,
                      block_q: int = None,
                      block_kv: int = None,
                      autotune: str = "table",
                      fmt_s: str = "e5m2", fmt_p: str = "e5m2",
                      rounding_s: str = "sr", rounding_p: str = "sr",
                      saturate_s: bool = True, saturate_p: bool = True,
                      with_counts: bool = False,
                      interpret: bool = False):
    """Fused FP8 attention forward on logical fp8 payloads.

    q8 (B,H,Q,D); k8/v8 (B,Hkv,S,D) — any fp8 dtype (the FP8 KV cache's
    e5m2 payloads compose with an e4m3 recipe; tiles upcast to bf16 for the
    MXU); seed u32 scalar; scal (4,) f32 [f_s, s_s, f_p, f_o] (ref module
    docstring). kv_mask: (B, S) int8/bool validity for mask_mode='kv';
    (B, S) int32 slot POSITIONS (-1 = hole/padding) for mask_mode='chunk',
    which additionally takes chunk_pos (B, 2) int32 [start, n_valid] —
    q row r of batch b sits at absolute position start_b + r when
    r < n_valid_b and is fully masked (exact-zero output) otherwise: the
    causal condition on logical positions, for paged/gathered KV layouts.
    block_kv: kv-stripe rows resident in VMEM per grid step. Unset
    block_q/block_kv resolve through the autotuner winners table (see
    kernels.autotune; `autotune="off"` pins the built-in defaults) and
    fall back to the kernel defaults; explicit knobs always win and are
    validated (never silently clamped to a different schedule). Results
    are bit-invariant to both knobs, so the table only moves wall-clock.

    Returns (o (B,H,Q,D) bf16, amax_s, amax_p) — scalar amaxes of the
    quantized S/P tiles in grid units (multiply by s_s / s_p for real
    units), masked to the attended region: bit-identical to
    `fp8_amax_bits` over the masked logical payloads of the unfused
    composition.

    with_counts=True (training masks only) additionally returns
    (health_s, health_p): (2,) f32 [saturated_fraction, flushed_fraction]
    of the in-kernel quantized S / P tiles over the attended region — the
    repro.obs precision-health counters, read in the same VMEM epilogue as
    the amaxes (S/P never hit HBM). Counts on/off is bit-identical.
    """
    b_, h_, q_len, d = q8.shape
    s_len = k8.shape[2]
    block_q, block_kv = _at.resolve_attn_blocks(
        "fwd", mask_mode, q_len, s_len, d, block_q=block_q,
        block_kv=block_kv, autotune=autotune)
    bq = min(block_q, max(1, q_len))
    bkv = _r.resolve_block_kv(s_len, block_kv)
    qp, kp, vp = _r.pad_qkv(q8, k8, v8, bq, bkv)
    mask = None
    cpos = None
    if mask_mode == "kv":
        # int32: a one-row int8 block is below the TPU's int8 tiling.
        mask = _r._pad_to(kv_mask.astype(jnp.int32), 1, bkv)
    elif mask_mode == "chunk":
        # Slot positions pad with -1: 0 is a VALID position, so the usual
        # zero padding would alias slot 0 into every padded lane.
        mask = _r._pad_to(kv_mask.astype(jnp.int32), 1, bkv, -1)
        cpos = jnp.asarray(chunk_pos, jnp.int32)
    seed = jnp.asarray(seed, jnp.uint32).reshape((1,))
    scal = jnp.asarray(scal, jnp.float32).reshape((4,))
    outs = _k.fp8_attention_fwd_kernel(
        qp, kp, vp, mask, seed, scal, chunk_pos=cpos,
        block_q=bq, block_kv=bkv,
        mask_mode=mask_mode,
        window=window, q_len=q_len, s_len=s_len, fmt_s=fmt_s, fmt_p=fmt_p,
        rounding_s=rounding_s, rounding_p=rounding_p,
        saturate_s=saturate_s, saturate_p=saturate_p,
        with_counts=with_counts, interpret=interpret)
    if with_counts:
        o, amax_s, amax_p, hs, hp = outs
        return (o[:, :, :q_len, :d], jnp.max(amax_s), jnp.max(amax_p),
                _health_frac(hs), _health_frac(hp))
    o, amax_s, amax_p = outs
    return o[:, :, :q_len, :d], jnp.max(amax_s), jnp.max(amax_p)


@functools.partial(jax.jit, static_argnames=(
    "mask_mode", "window", "block_q", "block_kv", "autotune", "fmt_s",
    "fmt_p", "fmt_e", "rounding_s", "rounding_p", "rounding_e",
    "saturate_s", "saturate_p", "saturate_e", "with_counts", "interpret"))
def fp8_attention_bwd(q8, k8, v8, do8, seed, scal, *,
                      mask_mode: str = "causal", window: int = 0,
                      block_q: int = None,
                      block_kv: int = None,
                      autotune: str = "table",
                      fmt_s: str = "e5m2", fmt_p: str = "e5m2",
                      fmt_e: str = "e5m2",
                      rounding_s: str = "sr", rounding_p: str = "sr",
                      rounding_e: str = "sr",
                      saturate_s: bool = True, saturate_p: bool = True,
                      saturate_e: bool = False,
                      with_counts: bool = False,
                      interpret: bool = False):
    """Fused FP8 attention backward (training masks: 'causal'/'full').
    do8: the error-quantized output cotangent payload (B,H,Q,D). scal (10,)
    f32 (ref.bwd_q_tile). An explicit block_q must be a positive TQ (128)
    multiple — dK/dV contraction granularity is pinned to TQ rows, so a
    sub-TQ request is a schedule the kernel cannot honor and raises
    (never a silent clamp). Unset knobs resolve through the autotuner
    winners table, then the kernel defaults; results are invariant to
    both block knobs. Returns (dq (B,H,Q,D) f32,
    dk/dv (B,Hkv,S,D) f32, amax_dp, amax_ds) with amaxes in grid units.

    with_counts=True additionally returns (health_dp, health_ds): (2,) f32
    [saturated_fraction, flushed_fraction] of the in-kernel quantized
    dP / dS tiles, counted once in the dQ kernel (the dK/dV kernel replays
    the same tiles and is excluded). Counts on/off is bit-identical."""
    if mask_mode not in ("causal", "full"):
        raise ValueError(
            f"fused attention backward supports causal/full, not "
            f"{mask_mode!r}")
    b_, h_, q_len, d = q8.shape
    s_len = k8.shape[2]
    block_q, block_kv = _at.resolve_attn_blocks(
        "bwd", mask_mode, q_len, s_len, d, block_q=block_q,
        block_kv=block_kv, autotune=autotune)
    bq = block_q
    bkv = _r.resolve_block_kv(s_len, block_kv)
    qp, kp, vp = _r.pad_qkv(q8, k8, v8, bq, bkv)
    dop = _r._pad_to(_r._pad_to(do8, 2, bq), 3, _r.LANE)
    seed = jnp.asarray(seed, jnp.uint32).reshape((1,))
    scal = jnp.asarray(scal, jnp.float32).reshape((10,))
    outs = _k.fp8_attention_bwd_kernel(
        qp, kp, vp, dop, seed, scal, block_q=bq, block_kv=bkv,
        mask_mode=mask_mode, window=window,
        q_len=q_len, s_len=s_len, fmt_s=fmt_s, fmt_p=fmt_p, fmt_e=fmt_e,
        rounding_s=rounding_s, rounding_p=rounding_p, rounding_e=rounding_e,
        saturate_s=saturate_s, saturate_p=saturate_p, saturate_e=saturate_e,
        with_counts=with_counts, interpret=interpret)
    if with_counts:
        dq, dk, dv, amax_dp, amax_ds, hdp, hds = outs
        return (dq[:, :, :q_len, :d], dk[:, :, :s_len, :d],
                dv[:, :, :s_len, :d], jnp.max(amax_dp), jnp.max(amax_ds),
                _health_frac(hdp), _health_frac(hds))
    dq, dk, dv, amax_dp, amax_ds = outs
    return (dq[:, :, :q_len, :d], dk[:, :, :s_len, :d],
            dv[:, :, :s_len, :d], jnp.max(amax_dp), jnp.max(amax_ds))
