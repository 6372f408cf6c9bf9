"""Pallas TPU kernels: streamed-KV fused FP8 flash attention with
quantize-in-epilogue S/P, delayed-scaling amax observation, and zero S/P in
HBM at ANY context length.

The PR-4 kernel held one (batch, kv-head)'s entire K/V row set in VMEM —
fine to ~8k fp8 context, hopeless at 32k. These kernels stream K/V through a
kv-stripe grid dimension instead, so the VMEM footprint is
O(block_kv * head_dim) per grid step regardless of the sequence length:

  forward grid   (B, H, Q/block_q, S/block_kv)
      ONE grid step per kv stripe: the online-softmax recurrence
      (ref.fwd_stripe_online) rescales the (l, PV accumulator) carries by
      exp(m_old - m_new) per LANE block, so each K/V stripe is DMA'd and
      read exactly once — the PR-5 kernel visited every stripe three times
      (m -> l -> PV phases), re-computing the quantized score tiles each
      visit. The carries live in VMEM scratch across stripes; the LANE-
      block chain is independent of the stripe cut, so outputs are
      invariant to block_kv. With every (k, v) block visited once, Mosaic's
      grid pipeline double-buffers the NEXT stripe's K/V DMA against the
      current stripe's compute (the revisiting phase structure used to
      defeat that overlap for 2 of every 3 visits).

  backward grid  (B, H, Q/block_q, 4 * S/block_kv)     [stats + dQ]
      Phases m -> l -> rd (the softmax-VJP row reduction, with the dP amax)
      -> dQ (with the dS amax). The tiny per-row (m, l, rd) statistics are
      written to HBM (the flash-attention LSE/delta pattern) for:

  backward grid  (B, Hkv, S/block_kv, group * Q/block_q)  [dK/dV]
      One dK/dV stripe block stays resident while every (GQA group member,
      query tile) contribution is accumulated into it in RAW grid units —
      contraction pinned to TQ=128 query rows so results are invariant to
      block_q — and the f_dk/f_dv scale is applied exactly once at the last
      visit (see ref.bwd_stripe_dkv on why scale-per-part would FMA-fuse).

Stripe skipping: causal and sliding-window modes visit only the
`ref.kv_stripe_span` / `ref.q_tile_span` stripe range per query tile — the
block index maps clamp skipped iterations onto an already-resident block (no
DMA) and `pl.when` predicates skip their compute entirely. A window=1k,
S=32k layer therefore touches ~1/32 of the stripes. Skipping is exact:
fully-masked stripes contribute exact zeros everywhere, and the amax
observations are masked to the attended region (ref.py module docstring).

All tile math lives in ref.py (the `*_stripe_*` pass functions) and is
shared verbatim with the unfused reference drivers, so kernel and oracle are
bit-identical in interpret mode by construction. GQA is resolved in the
block-index maps (kv head = q head // group) — the repeated K/V copies the
unfused path materializes via `_repeat_kv` never exist here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fp8_attention import ref as _r

DEFAULT_BQ = 128
DEFAULT_BKV = _r.DEFAULT_BKV   # kv-stripe rows resident in VMEM per step
STATS_TILE = (8, 128)   # f32 observation block per (b, h, q tile): rows
#                         0/1 the two amaxes, 2/3 the two health-count rows
TQ = _r.TQ        # fixed dK/dV contraction granularity in query rows (not a
#                   knob: backward results are tiling-invariant by
#                   construction)


def _span(iq, bq, bkv, nk, mask_mode, window):
    """Traced kv-stripe span for the q tile at grid index iq (same formula
    the reference drivers use — ref.kv_stripe_span)."""
    return _r.kv_stripe_span(iq * bq, bq, block_kv=bkv, n_kv=nk,
                             mask_mode=mask_mode, window=window,
                             _max=jnp.maximum, _min=jnp.minimum)


def _qspan(j, bq, bkv, nq, mask_mode, window):
    return _r.q_tile_span(j, block_q=bq, block_kv=bkv, n_q=nq,
                          mask_mode=mask_mode, window=window,
                          _max=jnp.maximum, _min=jnp.minimum)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _stats_row(st_ref, r):
    return st_ref[0, 0, r:r + 1, :]


def _set_stats_row(st_ref, r, v):
    st_ref[0, 0, r:r + 1, :] = v


def _fwd_body(q_ref, k_ref, v_ref, msk_ref, scal_ref, seed_ref,
              o_ref, st_ref, m_scr, l_scr, acc_scr, *,
              n_heads: int, bq: int, bkv: int, nk: int,
              mask_mode: str, window: int, q_len: int, s_len: int,
              fmt_s: str, fmt_p: str, rounding_s: str, rounding_p: str,
              saturate_s: bool, saturate_p: bool, with_counts: bool,
              chunk_ref=None):
    # st_ref: this q tile's (8, 128) observation block, resident across the
    # kv stripes (see STATS_TILE). The amax rows are broadcast over the
    # lanes; with_counts adds the S/P precision-health rows
    # ([saturated, flushed, observed] in lanes 0-2 — repro.obs).
    # Observation-only: the stripe carries and every quantize are
    # untouched, so counts on/off is bit-identical.
    # chunk_ref ('chunk' mode): (B, 2) int32 SMEM [start, n_valid] rows —
    # per-batch chunk coordinates, bound via the _fwd_body_chunk adapter.
    b, h, iq, j = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))
    jmin, jmax = _span(iq, bq, bkv, nk, mask_mode, window)
    active = (j >= jmin) & (j <= jmax)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)
        st_ref[...] = jnp.zeros_like(st_ref)

    # The stripe math slices the mask per LANE block; slicing the ref (not
    # a loaded row) is what lets Mosaic broadcast each slice over the rows.
    kvmask = None if msk_ref is None else msk_ref.at[0]
    kw = dict(seed=seed_ref[0], bh=b * n_heads + h, row0=iq * bq,
              col0=j * bkv, scal2=(scal_ref[0], scal_ref[1]),
              mask_mode=mask_mode, window=window, q_len=q_len, s_len=s_len,
              fmt_s=fmt_s, rounding_s=rounding_s, saturate_s=saturate_s,
              f_p=scal_ref[2], fmt_p=fmt_p, rounding_p=rounding_p,
              saturate_p=saturate_p)
    if chunk_ref is not None:
        kw["chunk"] = (chunk_ref[b, 0], chunk_ref[b, 1])
    if with_counts:
        kw.update(health_s=_stats_row(st_ref, 2),
                  health_p=_stats_row(st_ref, 3))

    @pl.when(active)
    def _stripe():
        out = _r.fwd_stripe_online(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], kvmask,
            m_scr[...], l_scr[...], acc_scr[...],
            _stats_row(st_ref, 0), _stats_row(st_ref, 1), **kw)
        m, l, acc, amax_s, amax_p = out[:5]
        if with_counts:
            _set_stats_row(st_ref, 2, out[7])
            _set_stats_row(st_ref, 3, out[8])
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc
        _set_stats_row(st_ref, 0, amax_s)
        _set_stats_row(st_ref, 1, amax_p)

    @pl.when(j == nk - 1)
    def _write():
        l = l_scr[...]
        d_safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_scr[...] * scal_ref[3] / d_safe
                       ).astype(jnp.bfloat16)


def _stats_block():
    """(1, 1, 8, 128) observation block of q tile (b, h, iq). A per-tile
    scalar block would be (1, 1, 1), which the TPU's (8, 128) tiling
    refuses."""
    return pl.BlockSpec((1, 1) + STATS_TILE,
                        lambda b, h, iq, u: (b, h, iq, 0))


def _split_stats(st, with_counts):
    """(B, H, nq*8, 128) stats -> (first amax, second amax) as (B, H, nq)
    and, with counts, the two (B, H, nq, 3) health-count rows."""
    b_, h_ = st.shape[:2]
    st = st.reshape(b_, h_, -1, *STATS_TILE)
    amaxes = (st[:, :, :, 0, 0], st[:, :, :, 1, 0])
    if not with_counts:
        return amaxes
    return amaxes + (st[:, :, :, 2, :3], st[:, :, :, 3, :3])


def fp8_attention_fwd_kernel(q8, k8, v8, kv_mask, seed, scal, *,
                             chunk_pos=None,
                             block_q: int = DEFAULT_BQ,
                             block_kv: int = 0,
                             mask_mode: str = "causal", window: int = 0,
                             q_len: int, s_len: int,
                             fmt_s: str, fmt_p: str,
                             rounding_s: str, rounding_p: str,
                             saturate_s: bool, saturate_p: bool,
                             with_counts: bool = False,
                             interpret: bool = False):
    """q8 (B,H,Qp,Dp), k8/v8 (B,Hkv,Sp,Dp) fp8 payloads (pre-padded: Qp a
    block_q multiple, Sp a block_kv multiple, Dp a LANE multiple); kv_mask
    None or (B,Sp) int32 validity — slot positions for mask_mode='chunk',
    padded with -1, with chunk_pos (B,2) int32 [start, n_valid] per batch;
    seed (1,) u32; scal (4,) f32 [f_s, s_s, f_p, f_o].

    Returns (o (B,H,Qp,Dp) bf16, amax_s (B,H,nq) f32, amax_p (B,H,nq) f32)
    with amaxes in grid units, masked to the attended region.

    with_counts=True (training masks only) additionally returns hs, hp:
    (B, H, nq, 3) f32 per-q-tile [saturated, flushed, observed] counts of
    the in-kernel quantized S / P tiles — the repro.obs precision-health
    counters, accumulated next to the amaxes while the tiles are still in
    VMEM (S/P never reach HBM, so this is the ONLY place they can be
    counted). The stripe math is untouched: counts on/off is bit-identical.
    """
    b_, h_, qp, dp = q8.shape
    hkv, sp = k8.shape[1], k8.shape[2]
    group = h_ // hkv
    bq = min(block_q, qp)
    bkv = sp if not block_kv else min(block_kv, sp)
    nk = sp // bkv
    nq = qp // bq
    grid = (b_, h_, nq, nk)

    def kv_index(b, h, iq, u):
        jmin, jmax = _span(iq, bq, bkv, nk, mask_mode, window)
        return (b, h // group, jnp.clip(u, jmin, jmax), 0)

    in_specs = [
        pl.BlockSpec((1, 1, bq, dp), lambda b, h, iq, u: (b, h, iq, 0)),
        pl.BlockSpec((1, 1, bkv, dp), kv_index),
        pl.BlockSpec((1, 1, bkv, dp), kv_index),
    ]
    args = [q8, k8, v8]
    if mask_mode in ("kv", "chunk"):
        if with_counts:
            raise ValueError("with_counts supports the training masks "
                             f"(causal/full), not {mask_mode!r}")
        # (B, 1, Sp): a (1, 1, bkv) block keeps its last two dims legal
        # for the TPU tiling at any batch size.
        in_specs.append(pl.BlockSpec((1, 1, bkv),
                                     lambda b, h, iq, u: (b, 0, u)))
        args.append(kv_mask.reshape(b_, 1, sp))
        body = _fwd_body
        if mask_mode == "chunk":
            # Per-batch chunk coordinates ride whole in SMEM (scalars,
            # dynamically indexed by the batch program id).
            in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            args.append(chunk_pos)
            body = _fwd_body_chunk
    else:
        body = functools.partial(_masked_none_fwd, _fwd_body)
    in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                 pl.BlockSpec(memory_space=pltpu.SMEM)]
    args += [scal, seed]
    o, st = pl.pallas_call(
        functools.partial(body, n_heads=h_, bq=bq, bkv=bkv, nk=nk,
                          mask_mode=mask_mode, window=window,
                          q_len=q_len, s_len=s_len, fmt_s=fmt_s, fmt_p=fmt_p,
                          rounding_s=rounding_s, rounding_p=rounding_p,
                          saturate_s=saturate_s, saturate_p=saturate_p,
                          with_counts=with_counts),
        grid=grid,
        in_specs=in_specs,
        out_specs=(pl.BlockSpec((1, 1, bq, dp),
                                lambda b, h, iq, u: (b, h, iq, 0)),
                   _stats_block()),
        out_shape=(jax.ShapeDtypeStruct((b_, h_, qp, dp), jnp.bfloat16),
                   jax.ShapeDtypeStruct((b_, h_, nq * STATS_TILE[0],
                                         STATS_TILE[1]), jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dp), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="fp8_attention_fwd",
    )(*args)
    return (o,) + _split_stats(st, with_counts)


def _masked_none_fwd(body, q_ref, k_ref, v_ref, scal_ref, seed_ref,
                     o_ref, st_ref, m_scr, l_scr, acc_scr, **kw):
    """Adapter for mask-free modes: re-inserts msk_ref=None."""
    body(q_ref, k_ref, v_ref, None, scal_ref, seed_ref,
         o_ref, st_ref, m_scr, l_scr, acc_scr, **kw)


def _fwd_body_chunk(q_ref, k_ref, v_ref, msk_ref, chunk_ref, scal_ref,
                    seed_ref, o_ref, st_ref, m_scr, l_scr, acc_scr, **kw):
    """Adapter for 'chunk' mode: rebinds the positional (B, 2) SMEM chunk
    coordinates (after the slot-position mask in pallas_call order) as the
    chunk_ref keyword."""
    _fwd_body(q_ref, k_ref, v_ref, msk_ref, scal_ref, seed_ref,
              o_ref, st_ref, m_scr, l_scr, acc_scr,
              chunk_ref=chunk_ref, **kw)


# ---------------------------------------------------------------------------
# backward kernel 1: softmax statistics + dQ  (grid streams kv stripes)
# ---------------------------------------------------------------------------

def _bwd_dq_body(q_ref, k_ref, v_ref, do_ref, scal_ref, seed_ref,
                 dq_ref, m_ref, l_ref, rd_ref, st_ref,
                 m_scr, l_scr, rd_scr, dq_scr, *,
                 n_heads: int, bq: int, bkv: int, nk: int,
                 mask_mode: str, window: int, q_len: int, s_len: int,
                 fmt_s: str, fmt_p: str, fmt_e: str,
                 rounding_s: str, rounding_p: str, rounding_e: str,
                 saturate_s: bool, saturate_p: bool, saturate_e: bool,
                 with_counts: bool):
    # st_ref: this q tile's (8, 128) observation block — rows 0/1 the dP/dS
    # amaxes, 2/3 the dP/dS health counts when with_counts (only this
    # kernel counts dP/dS: the dK/dV kernel replays the same quantized
    # tiles and would double-count).
    b, h, iq, u = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                   pl.program_id(3))
    j, phase = u % nk, u // nk
    jmin, jmax = _span(iq, bq, bkv, nk, mask_mode, window)
    active = (j >= jmin) & (j <= jmax)

    # amax outputs are PER (b, h, iq) — like the forward kernel — so the
    # parallel iq dimension carries no cross-iteration state (ops.py
    # reduces with an exact jnp.max); accumulating a shared (b, h) block
    # across iq would race if Mosaic partitioned the parallel dim.
    @pl.when(u == 0)
    def _init_row():
        m_scr[...] = jnp.full_like(m_scr, -1e30)
        l_scr[...] = jnp.zeros_like(l_scr)
        rd_scr[...] = jnp.zeros_like(rd_scr)
        dq_scr[...] = jnp.zeros_like(dq_scr)
        st_ref[...] = jnp.zeros_like(st_ref)

    kw = dict(seed=seed_ref[0], bh=b * n_heads + h, row0=iq * bq,
              col0=j * bkv, scal2=(scal_ref[0], scal_ref[1]),
              mask_mode=mask_mode, window=window, q_len=q_len, s_len=s_len,
              fmt_s=fmt_s, rounding_s=rounding_s, saturate_s=saturate_s)
    bkw = dict(f_p=scal_ref[2], s_p=scal_ref[3], f_dp=scal_ref[4],
               s_dp=scal_ref[5], fmt_p=fmt_p, fmt_e=fmt_e,
               rounding_p=rounding_p, rounding_e=rounding_e,
               saturate_p=saturate_p, saturate_e=saturate_e)

    @pl.when(active & (phase == 0))
    def _pass_m():
        m, _, _ = _r.fwd_stripe_m(q_ref[0, 0], k_ref[0, 0], None,
                                  m_scr[...], jnp.float32(0.0), **kw)
        m_scr[...] = m

    @pl.when(active & (phase == 1))
    def _pass_l():
        l_scr[...] = _r.fwd_stripe_l(q_ref[0, 0], k_ref[0, 0], None,
                                     m_scr[...], l_scr[...], **kw)

    @pl.when(active & (phase == 2))
    def _pass_rd():
        l = l_scr[...]
        d_safe = jnp.where(l > 0, l, 1.0)
        hkw = dict(health=_stats_row(st_ref, 2)) if with_counts else {}
        out = _r.bwd_stripe_rd(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0], None,
            m_scr[...], d_safe, rd_scr[...], _stats_row(st_ref, 0),
            **hkw, **kw, **bkw)
        if with_counts:
            _set_stats_row(st_ref, 2, out[3])
        rd_scr[...] = out[0]
        _set_stats_row(st_ref, 0, out[1])

    @pl.when(active & (phase == 3))
    def _pass_dq():
        l = l_scr[...]
        d_safe = jnp.where(l > 0, l, 1.0)
        hkw = dict(health=_stats_row(st_ref, 3)) if with_counts else {}
        out = _r.bwd_stripe_dq(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], do_ref[0, 0], None,
            m_scr[...], d_safe, rd_scr[...], dq_scr[...],
            _stats_row(st_ref, 1), f_ds=scal_ref[6], **hkw, **kw, **bkw)
        if with_counts:
            _set_stats_row(st_ref, 3, out[3])
        dq_scr[...] = out[0]
        _set_stats_row(st_ref, 1, out[1])

    @pl.when(u == 4 * nk - 1)
    def _write():
        dq_ref[0, 0] = dq_scr[...] * scal_ref[7]
        m_ref[0, 0] = m_scr[...]
        l_ref[0, 0] = l_scr[...]
        rd_ref[0, 0] = rd_scr[...]


# ---------------------------------------------------------------------------
# backward kernel 2: dK/dV stripes  (grid streams GQA-group query tiles)
# ---------------------------------------------------------------------------

def _bwd_dkv_body(q_ref, do_ref, k_ref, v_ref, m_ref, l_ref, rd_ref,
                  scal_ref, seed_ref, dk_ref, dv_ref, *,
                  n_heads: int, group: int, bq: int, bkv: int,
                  nq: int, nk: int, mask_mode: str, window: int,
                  q_len: int, s_len: int,
                  fmt_s: str, fmt_p: str, fmt_e: str,
                  rounding_s: str, rounding_p: str, rounding_e: str,
                  saturate_s: bool, saturate_p: bool, saturate_e: bool):
    b, hkv, j, t = (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                    pl.program_id(3))
    iq = t % nq
    h = hkv * group + t // nq
    jmin, jmax = _span(iq, bq, bkv, nk, mask_mode, window)
    active = (j >= jmin) & (j <= jmax)

    @pl.when(t == 0)
    def _init():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(active)
    def _accumulate():
        bkw = dict(f_p=scal_ref[2], s_p=scal_ref[3], f_dp=scal_ref[4],
                   s_dp=scal_ref[5], fmt_p=fmt_p, fmt_e=fmt_e,
                   rounding_p=rounding_p, rounding_e=rounding_e,
                   saturate_p=saturate_p, saturate_e=saturate_e)

        # TQ sub-tiles via fori_loop (one traced body however large
        # block_q is — a python loop would inline bq/TQ copies of the
        # stripe math and blow up compile time at long context). The loop
        # is sequential, so the per-slice add order over (head, TQ tile)
        # is exactly the oracle's flat chain.
        def t2_body(t2, carry):
            r0 = t2 * TQ
            kw = dict(seed=seed_ref[0], bh=b * n_heads + h,
                      row0=iq * bq + r0, col0=j * bkv,
                      scal2=(scal_ref[0], scal_ref[1]),
                      mask_mode=mask_mode, window=window,
                      q_len=q_len, s_len=s_len, fmt_s=fmt_s,
                      rounding_s=rounding_s, saturate_s=saturate_s)
            l = l_ref[0, 0, pl.dslice(r0, TQ)]
            d_safe = jnp.where(l > 0, l, 1.0)
            dk_parts, dv_parts = _r.bwd_stripe_dkv(
                q_ref[0, 0, pl.dslice(r0, TQ)], k_ref[0, 0], v_ref[0, 0],
                do_ref[0, 0, pl.dslice(r0, TQ)], None,
                m_ref[0, 0, pl.dslice(r0, TQ)], d_safe,
                rd_ref[0, 0, pl.dslice(r0, TQ)], f_ds=scal_ref[6],
                **kw, **bkw)
            # RAW grid-unit accumulation; the scale is applied exactly
            # once below (see ref.bwd_stripe_dkv on the FMA hazard).
            for jj, (pk, pv_) in enumerate(zip(dk_parts, dv_parts)):
                js = slice(jj * _r.LANE, (jj + 1) * _r.LANE)
                dk_ref[0, 0, js, :] += pk
                dv_ref[0, 0, js, :] += pv_
            return carry

        jax.lax.fori_loop(0, max(1, bq // TQ), t2_body, 0)

    @pl.when(t == group * nq - 1)
    def _scale():
        dk_ref[...] = dk_ref[...] * scal_ref[8]
        dv_ref[...] = dv_ref[...] * scal_ref[9]


def fp8_attention_bwd_kernel(q8, k8, v8, do8, seed, scal, *,
                             block_q: int = DEFAULT_BQ,
                             block_kv: int = 0,
                             mask_mode: str = "causal", window: int = 0,
                             q_len: int, s_len: int,
                             fmt_s: str, fmt_p: str, fmt_e: str,
                             rounding_s: str, rounding_p: str,
                             rounding_e: str,
                             saturate_s: bool, saturate_p: bool,
                             saturate_e: bool,
                             with_counts: bool = False,
                             interpret: bool = False):
    """Backward of the fused attention (training masks only: causal/full).
    Inputs pre-padded (Qp a block_q multiple — block_q a TQ multiple when
    larger, Sp a block_kv multiple, Dp a LANE multiple); scal (10,) f32
    (see ref.bwd_q_tile). Runs the two streamed kernels (stats+dQ, then
    dK/dV) with the per-row (m, l, rd) statistics round-tripped through HBM
    in exact f32. Returns (dq (B,H,Qp,Dp) f32, dk/dv (B,Hkv,Sp,Dp) f32,
    amax_dp (B,H,nq) f32, amax_ds (B,H,nq) f32) with amaxes in grid units
    per query block (reduce with an exact max).

    with_counts=True additionally returns hdp, hds: (B, H, nq, 3) f32
    per-q-tile [saturated, flushed, observed] counts of the in-kernel
    quantized dP / dS tiles, accumulated in the dQ kernel's epilogue (the
    dK/dV kernel re-quantizes the same tiles and is deliberately excluded
    so nothing is counted twice). Stripe math is unchanged: counts on/off
    is bit-identical."""
    b_, h_, qp, dp = q8.shape
    hkv, sp = k8.shape[1], k8.shape[2]
    group = h_ // hkv
    bq = min(block_q, qp)
    if bq > TQ and bq % TQ:
        raise ValueError(f"backward block_q must be a multiple of {TQ}")
    bkv = sp if not block_kv else min(block_kv, sp)
    nk = sp // bkv
    nq = qp // bq
    fmt_kw = dict(mask_mode=mask_mode, window=window, q_len=q_len,
                  s_len=s_len, fmt_s=fmt_s, fmt_p=fmt_p, fmt_e=fmt_e,
                  rounding_s=rounding_s, rounding_p=rounding_p,
                  rounding_e=rounding_e, saturate_s=saturate_s,
                  saturate_p=saturate_p, saturate_e=saturate_e)

    def kv_index(b, h, iq, u):
        jmin, jmax = _span(iq, bq, bkv, nk, mask_mode, window)
        return (b, h // group, jnp.clip(u % nk, jmin, jmax), 0)

    dq_outs = pl.pallas_call(
        functools.partial(_bwd_dq_body, n_heads=h_, bq=bq, bkv=bkv, nk=nk,
                          with_counts=with_counts, **fmt_kw),
        grid=(b_, h_, nq, 4 * nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dp), lambda b, h, iq, u: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bkv, dp), kv_index),
            pl.BlockSpec((1, 1, bkv, dp), kv_index),
            pl.BlockSpec((1, 1, bq, dp), lambda b, h, iq, u: (b, h, iq, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bq, dp), lambda b, h, iq, u: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, u: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, u: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, iq, u: (b, h, iq, 0)),
            _stats_block(),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b_, h_, qp, dp), jnp.float32),
            jax.ShapeDtypeStruct((b_, h_, qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b_, h_, qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b_, h_, qp, 1), jnp.float32),
            jax.ShapeDtypeStruct((b_, h_, nq * STATS_TILE[0],
                                  STATS_TILE[1]), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dp), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="fp8_attention_bwd_dq",
    )(q8, k8, v8, do8, scal, seed)
    dq, m, l, rd, st = dq_outs
    stats = _split_stats(st, with_counts)

    def q_index(b, hkv_, j, t):
        # Shared by the q/do blocks AND the m/l/rd statistics blocks —
        # they must be sliced identically per (head, q-tile).
        imin, imax = _qspan(j, bq, bkv, nq, mask_mode, window)
        return (b, hkv_ * group + t // nq, jnp.clip(t % nq, imin, imax), 0)

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_body, n_heads=h_, group=group, bq=bq,
                          bkv=bkv, nq=nq, nk=nk, **fmt_kw),
        grid=(b_, hkv, nk, group * nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dp), q_index),
            pl.BlockSpec((1, 1, bq, dp), q_index),
            pl.BlockSpec((1, 1, bkv, dp),
                         lambda b, hkv_, j, t: (b, hkv_, j, 0)),
            pl.BlockSpec((1, 1, bkv, dp),
                         lambda b, hkv_, j, t: (b, hkv_, j, 0)),
            pl.BlockSpec((1, 1, bq, 1), q_index),
            pl.BlockSpec((1, 1, bq, 1), q_index),
            pl.BlockSpec((1, 1, bq, 1), q_index),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, bkv, dp),
                         lambda b, hkv_, j, t: (b, hkv_, j, 0)),
            pl.BlockSpec((1, 1, bkv, dp),
                         lambda b, hkv_, j, t: (b, hkv_, j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b_, hkv, sp, dp), jnp.float32),
            jax.ShapeDtypeStruct((b_, hkv, sp, dp), jnp.float32),
        ),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        name="fp8_attention_bwd_dkv",
    )(q8, do8, k8, v8, m, l, rd, scal, seed)
    return (dq, dk, dv) + stats
