"""Shared stripe math + unfused oracle for the fused FP8 flash-attention path.

This module is the SINGLE SOURCE OF TRUTH for the fused-attention numerics:
the Pallas kernel bodies (kernel.py) and the unfused reference drivers below
call the *same* per-stripe pass functions (`fwd_stripe_online`,
`bwd_stripe_rd` / `bwd_stripe_dq` / `bwd_stripe_dkv`, with
`fwd_stripe_m` / `fwd_stripe_l` recomputing the softmax statistics for the
backward), so in interpret mode the kernel is bit-identical to the unfused
quantize -> matmul -> softmax -> quantize -> matmul composition by
construction — the same guarantee structure `sr_fp8_from_bits` gives the
fused GEMM kernels.

Semantics (the paper's Fig. 1a dataflow extended into attention, all four
tensor classes in FP8):

    forward (ONE-PASS online softmax — each K/V stripe is read once):
        per LANE column block j, in ascending column order:
          S8_j = Q_A((q8 . k8_j^T) * f_s)       f_s = s_q s_k sm / s_s
          x_j  = S8_j * s_s                     (masked lanes -1e30)
          m'   = max(m, rowmax(x_j));  c = exp(m - m')
          e_j  = exp(x_j - m')                  (masked lanes exact 0)
          E8_j = Q_A(e_j / s_p)    UNNORMALIZED probs vs the running max
          l    = l * c + rowsum(e_j)
          acc  = acc * c + E8_j . v8_j
          m    = m'
        O = acc * (s_p s_v) / l   -> bf16       (l -> 1 fully-masked rows)
    backward:  P8  = Q_A(exp(x - m_final) / l / s_p)   (normalized — the
               exact softmax rows, recomputed from the two-pass statistics)
               dP8 = Q_E((do8 . v8^T) * f_dp)       f_dp = s_do s_v / s_dp
               dS  = P_deq * (dP_deq - rowsum(P_deq * dP_deq))
               dS8 = Q_E(dS * sm / s_ds)
               dQ = (dS8 . k8)   * (s_ds s_k)
               dK = (dS8^T . q8) * (s_ds s_q)
               dV = (P8^T . do8) * (s_p s_do)

The forward quantizes its probs UNNORMALIZED against the running row max
(e_j <= 1 because the running max dominates every column seen so far, with
exact 1.0 at the row's max column — better FP8 range utilization than the
normalized p = e/l it replaces), while the backward recomputes the
NORMALIZED P8 from the exact final statistics — the standard FP8
flash-attention structure: quantization is straight-through in the adjoint
either way, and the forward E8 tiles never reach HBM to be reused. Both
the `#p.A` amax observation and the P payload/health counters therefore
refer to the forward's unnormalized E8 tiles.

Streamed-KV structure: the KV axis is partitioned into stripes of `block_kv`
rows and the (m, l, PV accumulator) carries cross stripe boundaries — ONE
visit per stripe (the PR-5 kernel needed three). Results are invariant to
the `block_kv` choice because the online recurrence advances in fixed
LANE-wide column blocks whose order is independent of how they are grouped
into stripes: the running max after block j is the prefix max over blocks
<= j under ANY stripe cut, so every e_j / E8_j / l / acc value is
identical. `kv_stripe_span` gives the static per-q-tile stripe range
outside which causal/sliding-window tiles are FULLY masked; both the
kernels (via block index maps + predication) and the reference drivers
skip those stripes, which is exact because a fully-masked stripe
contributes exact-0.0 to `e`/`l`/PV/dQ/dK/dV, leaves `m` unchanged (its
rescale factor is exp(m - m) = exp(0) = exact 1.0), and (see below)
nothing to any amax.

Stripe-skip observation semantics (changed from the PR-4 kernel): the fused
amax observations at `#qk.A` / `#p.A` / `#dp.E` / `#ds.E` are masked to the
*attended* region — (row < q_len) AND the mask-mode validity — not to the
full logical rectangle. Scores/dP values at positions the mask excludes are
never part of any inner product and, under the streamed grid, are never
computed for skipped stripes; observing them would make the observation
depend on the stripe partition. The reference drivers materialize their
payloads with masked positions zeroed, so `fp8_amax_bits(payload)` equals
the in-kernel observation exactly.

Determinism / tiling invariance: every cross-position reduction (softmax
normalizer, PV / dQ accumulation) advances in fixed LANE-wide steps, dK/dV
contraction granularity is pinned to TQ=128 query rows, and SR bits are
drawn from a counter-based hash of the *absolute* (head, row, col)
coordinates — so results are invariant to the query/kv block-size knobs, to
padding (zero-padded lanes contribute exact 0.0), and identical between the
kernel grids and the reference loops. (One theoretical caveat: a skipped
stripe cannot flip a -0.0 accumulator element to +0.0 the way an explicit
`+ 0.0` add would; that divergence needs an all-zero quantized-P row and is
shared by kernel and oracle, which skip identically.) Zero materialized S/P
ever reaches HBM on the kernel path; the reference drivers materialize them
(that is the point of an oracle) and also return the payloads for
observation checks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.fp8_formats import get_format
from repro.core.quantize import quantize_rne, sr_fp8_via_f16

# Fixed inner reduction width (TPU lane count). All KV-axis loops advance in
# LANE steps regardless of any block-size knob.
LANE = 128

# Fixed dK/dV contraction granularity in query rows: each (TQ, LANE) dS/P
# tile contributes one (LANE, D) partial dot, accumulated in (head, q-tile)
# order — pinning the f32 reduction grouping so dK/dV are invariant to the
# backward block_q knob.
TQ = 128

# SR draw channels: one salt per in-kernel Q node so S/P/dP/dS consume
# independent bit streams at the same coordinates.
SALT_S, SALT_P, SALT_DP, SALT_DS = 0x51, 0x52, 0x53, 0x54

_GOLD = 0x9E3779B9  # 2^32 / golden ratio


def _fmix32(x):
    """murmur3 finalizer: full avalanche on uint32."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> 16)
    return x


def sr_hash_bits(seed, salt: int, bh, rows, cols):
    """Counter-based uint8 SR bits from absolute tile coordinates.

    Unlike the fused GEMM kernels (which stream a materialized rand8 array
    from HBM), attention draws its SR bits *in the kernel* from a stateless
    hash of (seed, salt, batch*head, row, col) — an S-shaped rand array in
    HBM would cost exactly the S materialization the kernel exists to avoid.
    Bits depend only on absolute coordinates, so any tiling/padding draws
    identical bits for a logical cell."""
    gold = jnp.uint32(_GOLD)
    s = _fmix32(jnp.asarray(seed, jnp.uint32)
                + jnp.uint32(salt) * gold)
    s = _fmix32(s + jnp.asarray(bh, jnp.uint32) * gold)
    h = _fmix32(s + rows.astype(jnp.uint32) * gold)
    h = _fmix32(h ^ (cols.astype(jnp.uint32) * gold))
    return (h & jnp.uint32(0xFF)).astype(jnp.uint8)


def _quant_tile(y, bits, fmt_name: str, rounding: str, saturate: bool):
    fmt = get_format(fmt_name)
    if rounding == "rne":
        return quantize_rne(y, fmt, saturate=saturate)
    return sr_fp8_via_f16(y, bits, fmt, saturate=saturate)


def _mask_block(mask_mode: str, rows, cols, s_len: int, window: int, kvmask,
                qpos=None):
    """Validity of one (bq, LANE) score tile: KV padding is always masked;
    'causal' adds the triangular (+ optional sliding-window) condition from
    absolute coordinates; 'kv' ANDs a runtime per-batch validity row;
    'chunk' compares a runtime int32 row of KV slot POSITIONS (-1 = hole /
    padding) against per-q-row absolute positions `qpos` (-1 = inactive
    row) — the causal condition on logical positions rather than physical
    columns, which is what a paged/gathered KV layout needs."""
    valid = cols < s_len
    if mask_mode == "causal":
        valid = valid & (cols <= rows)
        if window:
            valid = valid & (cols > rows - window)
    elif mask_mode == "kv":
        valid = valid & (kvmask != 0)
    elif mask_mode == "chunk":
        valid = valid & (kvmask >= 0) & (kvmask <= qpos)
        if window:
            valid = valid & (kvmask > qpos - window)
    elif mask_mode != "full":
        raise ValueError(f"unknown mask mode {mask_mode!r}")
    return valid


def _dot_f32(a8, b8, contract):
    return jax.lax.dot_general(a8.astype(jnp.bfloat16),
                               b8.astype(jnp.bfloat16),
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _score_block(q8, k8_sub, bits, f_s, fmt_s, rounding_s, saturate_s):
    """(bq, LANE) quantized score tile: S8 = Q((q8 . k8_sub^T) * f_s)."""
    s = _dot_f32(q8, k8_sub, ((1,), (1,)))
    return _quant_tile(s * f_s, bits, fmt_s, rounding_s, saturate_s)


# ---------------------------------------------------------------------------
# stripe-skip spans (shared by kernel index maps, kernel bodies, drivers)
# ---------------------------------------------------------------------------

def kv_stripe_span(row0, bq: int, *, block_kv: int, n_kv: int,
                   mask_mode: str, window: int,
                   _max=max, _min=min):
    """Inclusive [jmin, jmax] kv-stripe range a q tile of rows
    [row0, row0+bq) can attend under `mask_mode`; stripes outside it are
    FULLY masked for every row of the tile and are skipped by both the
    kernels and the reference drivers (exact — see module docstring).

    Works on python ints (drivers, tests) and, with
    `_max=jnp.maximum, _min=jnp.minimum`, on traced grid indices (the
    kernel block index maps and `pl.when` predicates use the same
    formula)."""
    if mask_mode != "causal":
        # 'full' attends everything; 'kv' validity is runtime data.
        return row0 * 0, row0 * 0 + (n_kv - 1)
    jmax = _min((row0 + bq - 1) // block_kv, n_kv - 1)
    jmin = row0 * 0
    if window:
        jmin = _max(row0 - window + 1, 0) // block_kv
    return jmin, jmax


def q_tile_span(j, *, block_q: int, block_kv: int, n_q: int,
                mask_mode: str, window: int, _max=max, _min=min):
    """Inverse of `kv_stripe_span`: the inclusive [imin, imax] q-tile range
    for which kv stripe j is (partially) attended. Used by the dK/dV kernel
    to clamp its q/do block index maps over skipped iterations; the active
    q tiles of a stripe always form this contiguous interval because
    `kv_stripe_span` bounds are monotone in the tile index."""
    if mask_mode != "causal":
        return j * 0, j * 0 + (n_q - 1)
    # smallest i with i*bq + bq - 1 >= j*bkv  (the causal jmax condition)
    imin = _max((j * block_kv - block_q + 1 + block_q - 1) // block_q, 0)
    imax = j * 0 + (n_q - 1)
    if window:
        # largest i with max(0, i*bq - window + 1) <= (j+1)*bkv - 1
        imax = _min(((j + 1) * block_kv + window - 2) // block_q, n_q - 1)
    return imin, imax


# ---------------------------------------------------------------------------
# per-stripe pass functions (the tile math shared with the kernels)
# ---------------------------------------------------------------------------

def _zeros_like_fp8(x):
    return jnp.zeros_like(x)


def _health_counts(q8t, obs, fmt_name: str):
    """(1, LANE) f32 row [saturated, flushed, observed, 0, ...] of counts of
    one quantized tile over its observed region — the precision-health
    counters (repro.obs) accumulated next to the amax observations, from
    values already in VMEM/registers. A lane row (not a (3,) vector) is
    what a kernel's (8, 128) stats block stores. Saturated: |q| at/above
    the format ceiling, inf/nan included (non-saturating error tensors keep
    inf). Flushed: |q| below min_normal (exact zeros + subnormals)."""
    fmt = get_format(fmt_name)
    qf = q8t.astype(jnp.float32)
    a = jnp.abs(qf)
    sat = (a >= jnp.float32(fmt.max_normal)) | ~jnp.isfinite(qf)
    flush = a < jnp.float32(fmt.min_normal)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
    row = jnp.where(lane == 2, jnp.sum(jnp.where(obs, 1.0, 0.0)), 0.0)
    row = jnp.where(lane == 1, jnp.sum(jnp.where(obs & flush, 1.0, 0.0)),
                    row)
    return jnp.where(lane == 0, jnp.sum(jnp.where(obs & sat, 1.0, 0.0)), row)


def _sblocks(q8, k8s, kvmask_s, *, seed, bh, row0, col0, scal2,
             mask_mode, window, q_len, s_len,
             fmt_s, rounding_s, saturate_s, chunk=None):
    """Yield (jj, s8, valid, x, cols, obs) for each LANE-wide column block
    of one kv stripe. scal2 = (f_s, s_s). obs is the OBSERVED region:
    logical rows AND mask validity (stripe-skip semantics — see module
    docstring). chunk ('chunk' mode only): per-batch (start, n_valid) int32
    scalars — q row r sits at absolute position start + r when r < n_valid,
    and is inactive (fully masked, exact-zero output) otherwise. Chunk
    positions are affine in the row index by construction (a chunk is a
    run of consecutive tokens), so two scalars replace a per-row vector —
    no cross-lane transpose in the kernel."""
    f_s, s_s = scal2
    bq = q8.shape[0]
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    qpos = None
    if chunk is not None:
        start, n_valid = chunk
        qpos = jnp.where(rows < n_valid, start + rows, jnp.int32(-1))
    for jj in range(k8s.shape[0] // LANE):
        cols = col0 + jj * LANE \
            + jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)
        bits = sr_hash_bits(seed, SALT_S, bh, rows, cols) \
            if rounding_s == "sr" else jnp.zeros((bq, LANE), jnp.uint8)
        s8 = _score_block(q8, k8s[jj * LANE:(jj + 1) * LANE], bits, f_s,
                          fmt_s, rounding_s, saturate_s)
        sub = None if kvmask_s is None \
            else kvmask_s[:, jj * LANE:(jj + 1) * LANE]
        valid = _mask_block(mask_mode, rows, cols, s_len, window, sub, qpos)
        x = jnp.where(valid, s8.astype(jnp.float32) * s_s,
                      jnp.float32(-1e30))
        obs = (rows < q_len) & valid
        yield jj, s8, valid, x, cols, obs


def fwd_stripe_m(q8, k8s, kvmask_s, m, amax_s, *, payload=False,
                 health=None, **kw):
    """Exact running row-max carry over one stripe + the S amax
    observation (masked to the attended region). The BACKWARD's statistics
    recompute (and the retained two-pass baseline `fwd_q_tile_two_pass`)
    use this; the forward kernel itself runs the one-pass
    `fwd_stripe_online`. Returns (m, amax_s, s8_tiles) — tiles only when
    payload=True (oracle use). With a (1, LANE) `health` accumulator,
    additionally returns it advanced by this stripe's S precision-health
    counts (4-tuple; the observation-only extra output never perturbs the
    carries — counters on/off is bit-identical)."""
    tiles = []
    for jj, s8, valid, x, cols, obs in _sblocks(q8, k8s, kvmask_s, **kw):
        m = jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
        amax_s = jnp.maximum(amax_s, jnp.max(
            jnp.where(obs, jnp.abs(s8.astype(jnp.float32)), 0.0)))
        if health is not None:
            health = health + _health_counts(s8, obs, kw["fmt_s"])
        if payload:
            tiles.append(jnp.where(valid, s8, _zeros_like_fp8(s8)))
    if health is not None:
        return m, amax_s, tiles, health
    return m, amax_s, tiles


def fwd_stripe_l(q8, k8s, kvmask_s, m, l, **kw):
    """Softmax normalizer carry over one stripe given the FINAL row max,
    accumulated in LANE-wide sequential steps (the fixed chain block_kv
    cannot change). Backward statistics recompute / two-pass baseline."""
    for jj, s8, valid, x, cols, obs in _sblocks(q8, k8s, kvmask_s, **kw):
        e = jnp.where(valid, jnp.exp(x - m), 0.0)
        l = l + jnp.sum(e, axis=-1, keepdims=True)
    return l


def fwd_stripe_online(q8, k8s, v8s, kvmask_s, m, l, acc, amax_s, amax_p, *,
                      seed, bh, f_p, fmt_p, rounding_p, saturate_p,
                      payload=False, health_s=None, health_p=None, **kw):
    """ONE pass over one stripe: the online-softmax recurrence (module
    docstring) advancing the (m, l, acc) carries per LANE column block,
    with both amax observations (masked to the attended region) taken in
    the same pass. This is the forward kernel's stripe body — each K/V
    stripe is read exactly once.

    Rescaling by exp(m - m') per LANE block (not per stripe) is what makes
    the result invariant to the stripe partition: the block chain is the
    same however the blocks are grouped. A fully-masked block leaves m
    unchanged, so its rescale factor is exp(0) = exact 1.0 and its
    e-contribution is exact 0.0 — stripe skipping stays exact. The probs
    are quantized UNNORMALIZED against the running max (e <= 1 by
    construction); normalization by the final l happens once at write-out.

    Returns (m, l, acc, amax_s, amax_p, s8_tiles, p8_tiles) — tile lists
    only when payload=True (oracle use). With (1, LANE) `health_s`/`health_p`
    accumulators, additionally returns both advanced by this stripe's S/P
    precision-health counts (observation-only: carries are untouched, so
    counters on/off is bit-identical)."""
    s_tiles, p_tiles = [], []
    bq = q8.shape[0]
    rows = kw["row0"] + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    for jj, s8, valid, x, cols, obs in _sblocks(q8, k8s, kvmask_s,
                                                seed=seed, bh=bh, **kw):
        m_new = jnp.maximum(m, jnp.max(x, axis=-1, keepdims=True))
        amax_s = jnp.maximum(amax_s, jnp.max(
            jnp.where(obs, jnp.abs(s8.astype(jnp.float32)), 0.0)))
        if health_s is not None:
            health_s = health_s + _health_counts(s8, obs, kw["fmt_s"])
        corr = jnp.exp(m - m_new)
        e = jnp.where(valid, jnp.exp(x - m_new), 0.0)
        bits = sr_hash_bits(seed, SALT_P, bh, rows, cols) \
            if rounding_p == "sr" else jnp.zeros((bq, LANE), jnp.uint8)
        p8 = _quant_tile(e * f_p, bits, fmt_p, rounding_p, saturate_p)
        amax_p = jnp.maximum(amax_p, jnp.max(
            jnp.where(obs, jnp.abs(p8.astype(jnp.float32)), 0.0)))
        if health_p is not None:
            health_p = health_p + _health_counts(p8, obs, fmt_p)
        l = l * corr + jnp.sum(e, axis=-1, keepdims=True)
        acc = acc * corr + _dot_f32(p8, v8s[jj * LANE:(jj + 1) * LANE],
                                    ((1,), (0,)))
        m = m_new
        if payload:
            s_tiles.append(jnp.where(valid, s8, _zeros_like_fp8(s8)))
            p_tiles.append(jnp.where(valid, p8, _zeros_like_fp8(p8)))
    if health_s is not None:
        return (m, l, acc, amax_s, amax_p, s_tiles, p_tiles,
                health_s, health_p)
    return m, l, acc, amax_s, amax_p, s_tiles, p_tiles


def fwd_stripe_pv(q8, k8s, v8s, kvmask_s, m, d_safe, acc, amax_p, *,
                  seed, bh, f_p, fmt_p, rounding_p, saturate_p,
                  payload=False, health=None, **kw):
    """Two-pass PV stripe (NORMALIZED probs from the final statistics):
    quantized probs + P amax + PV accumulation. Retained as the two-pass
    baseline for the one-pass A/B bench and equivalence tests — the
    forward kernel runs `fwd_stripe_online`. Returns (acc, amax_p,
    p8_tiles) — plus the advanced (1, LANE) P health counts when a `health`
    accumulator is given."""
    tiles = []
    bq = q8.shape[0]
    rows = kw["row0"] + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    for jj, s8, valid, x, cols, obs in _sblocks(q8, k8s, kvmask_s,
                                                seed=seed, bh=bh, **kw):
        e = jnp.where(valid, jnp.exp(x - m), 0.0)
        p = e / d_safe
        bits = sr_hash_bits(seed, SALT_P, bh, rows, cols) \
            if rounding_p == "sr" else jnp.zeros((bq, LANE), jnp.uint8)
        p8 = _quant_tile(p * f_p, bits, fmt_p, rounding_p, saturate_p)
        amax_p = jnp.maximum(amax_p, jnp.max(
            jnp.where(obs, jnp.abs(p8.astype(jnp.float32)), 0.0)))
        if health is not None:
            health = health + _health_counts(p8, obs, fmt_p)
        acc = acc + _dot_f32(p8, v8s[jj * LANE:(jj + 1) * LANE],
                             ((1,), (0,)))
        if payload:
            tiles.append(jnp.where(valid, p8, _zeros_like_fp8(p8)))
    if health is not None:
        return acc, amax_p, tiles, health
    return acc, amax_p, tiles


def _pdp_blocks(q8, k8s, v8s, do8, kvmask_s, m, d_safe, *, seed, bh,
                f_p, s_p, f_dp, s_dp, fmt_p, fmt_e,
                rounding_p, rounding_e, saturate_p, saturate_e, **kw):
    """Backward recomputation per LANE block of one stripe: yields
    (jj, p8, p_d, dp8, dp_d, cols, obs, valid) with S8/P8 recomputed
    bit-exactly from the FP8 residuals (identical hash bits)."""
    bq = q8.shape[0]
    rows = kw["row0"] + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    for jj, s8, valid, x, cols, obs in _sblocks(q8, k8s, kvmask_s,
                                                seed=seed, bh=bh, **kw):
        e = jnp.where(valid, jnp.exp(x - m), 0.0)
        p = e / d_safe
        bits_p = sr_hash_bits(seed, SALT_P, bh, rows, cols) \
            if rounding_p == "sr" else jnp.zeros((bq, LANE), jnp.uint8)
        p8 = _quant_tile(p * f_p, bits_p, fmt_p, rounding_p, saturate_p)
        p_d = p8.astype(jnp.float32) * s_p
        dp = _dot_f32(do8, v8s[jj * LANE:(jj + 1) * LANE], ((1,), (1,)))
        bits_dp = sr_hash_bits(seed, SALT_DP, bh, rows, cols) \
            if rounding_e == "sr" else jnp.zeros((bq, LANE), jnp.uint8)
        dp8 = _quant_tile(dp * f_dp, bits_dp, fmt_e, rounding_e, saturate_e)
        dp_d = dp8.astype(jnp.float32) * s_dp
        yield jj, p8, p_d, dp8, dp_d, cols, obs, valid


def bwd_stripe_rd(q8, k8s, v8s, do8, kvmask_s, m, d_safe, rd, amax_dp, *,
                  payload=False, health=None, **kw):
    """Backward pass A over one stripe: the softmax-VJP row reduction
    rowsum(P * dP) carry + the dP observation. Returns
    (rd, amax_dp, dp8_tiles) — plus the advanced (1, LANE) dP health counts
    when a `health` accumulator is given."""
    tiles = []
    for jj, p8, p_d, dp8, dp_d, cols, obs, valid in _pdp_blocks(
            q8, k8s, v8s, do8, kvmask_s, m, d_safe, **kw):
        rd = rd + jnp.sum(p_d * dp_d, axis=-1, keepdims=True)
        amax_dp = jnp.maximum(amax_dp, jnp.max(
            jnp.where(obs, jnp.abs(dp8.astype(jnp.float32)), 0.0)))
        if health is not None:
            health = health + _health_counts(dp8, obs, kw["fmt_e"])
        if payload:
            tiles.append(jnp.where(valid, dp8, _zeros_like_fp8(dp8)))
    if health is not None:
        return rd, amax_dp, tiles, health
    return rd, amax_dp, tiles


def _ds_block(p_d, dp_d, rd, rows, cols, *, seed, bh, f_ds, fmt_e,
              rounding_e, saturate_e):
    ds = p_d * (dp_d - rd)
    bits = sr_hash_bits(seed, SALT_DS, bh, rows, cols) \
        if rounding_e == "sr" else jnp.zeros(ds.shape, jnp.uint8)
    return _quant_tile(ds * f_ds, bits, fmt_e, rounding_e, saturate_e)


def bwd_stripe_dq(q8, k8s, v8s, do8, kvmask_s, m, d_safe, rd,
                  dq_acc, amax_ds, *, f_ds, payload=False, health=None,
                  **kw):
    """Backward pass B (query side) over one stripe: dS quantization, the
    dQ accumulation, and the dS observation. Returns
    (dq_acc, amax_ds, ds8_tiles) — plus the advanced (1, LANE) dS health counts
    when a `health` accumulator is given."""
    bq = q8.shape[0]
    rows = kw["row0"] + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    tiles = []
    for jj, p8, p_d, dp8, dp_d, cols, obs, valid in _pdp_blocks(
            q8, k8s, v8s, do8, kvmask_s, m, d_safe, **kw):
        ds8 = _ds_block(p_d, dp_d, rd, rows, cols, seed=kw["seed"],
                        bh=kw["bh"], f_ds=f_ds, fmt_e=kw["fmt_e"],
                        rounding_e=kw["rounding_e"],
                        saturate_e=kw["saturate_e"])
        amax_ds = jnp.maximum(amax_ds, jnp.max(
            jnp.where(obs, jnp.abs(ds8.astype(jnp.float32)), 0.0)))
        if health is not None:
            health = health + _health_counts(ds8, obs, kw["fmt_e"])
        dq_acc = dq_acc + _dot_f32(ds8, k8s[jj * LANE:(jj + 1) * LANE],
                                   ((1,), (0,)))
        if payload:
            tiles.append(jnp.where(valid, ds8, _zeros_like_fp8(ds8)))
    if health is not None:
        return dq_acc, amax_ds, tiles, health
    return dq_acc, amax_ds, tiles


def bwd_stripe_dkv(q8, k8s, v8s, do8, kvmask_s, m, d_safe, rd, *,
                   f_ds, **kw):
    """Backward pass B (kv side) for ONE TQ-row query tile against one
    stripe: per-LANE-slice (LANE, D) dK/dV contributions in RAW grid units.
    The caller accumulates slice jj into rows [jj*LANE, (jj+1)*LANE) of the
    stripe's dK/dV (summing over query tiles and GQA group members in a
    fixed order) and applies the f_dk / f_dv scale ONCE after the
    accumulation — scaling per part would let XLA fuse the multiply into
    the running add as an FMA, whose single rounding diverges from the
    unfused mul-then-add by one ulp (the scale-at-end shape is immune:
    (acc + x) * c has no FMA form)."""
    bq = q8.shape[0]
    rows = kw["row0"] + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    dk_parts, dv_parts = [], []
    for jj, p8, p_d, dp8, dp_d, cols, obs, valid in _pdp_blocks(
            q8, k8s, v8s, do8, kvmask_s, m, d_safe, **kw):
        ds8 = _ds_block(p_d, dp_d, rd, rows, cols, seed=kw["seed"],
                        bh=kw["bh"], f_ds=f_ds, fmt_e=kw["fmt_e"],
                        rounding_e=kw["rounding_e"],
                        saturate_e=kw["saturate_e"])
        dk_parts.append(_dot_f32(ds8, q8, ((0,), (0,))))
        dv_parts.append(_dot_f32(p8, do8, ((0,), (0,))))
    return dk_parts, dv_parts


# ---------------------------------------------------------------------------
# per-q-tile drivers (stripe loops; shared by the oracle drivers below)
# ---------------------------------------------------------------------------

def _stripe_kw(seed, bh, row0, scal2, mask_mode, window, q_len, s_len,
               fmt_s, rounding_s, saturate_s):
    return dict(seed=seed, bh=bh, row0=row0, scal2=scal2,
                mask_mode=mask_mode, window=window, q_len=q_len,
                s_len=s_len, fmt_s=fmt_s, rounding_s=rounding_s,
                saturate_s=saturate_s)


# The drivers call the stripe functions through a jit cache keyed on the
# static config: one compile per (function, config/shape) instead of tens
# of thousands of eager op dispatches at long context. Purely an execution-
# mode change for the ORACLE — coordinates (bh/row0/col0) and scales enter
# as traced arguments, so the op chain (and therefore every bit) is
# unchanged; the kernels keep calling the raw functions from their bodies.
_STATIC_KEYS = ("mask_mode", "window", "q_len", "s_len", "fmt_s",
                "rounding_s", "saturate_s", "fmt_p", "rounding_p",
                "saturate_p", "fmt_e", "rounding_e", "saturate_e",
                "payload")
_JIT_CACHE = {}


def _call_stripe(fn, *arrays, **kw):
    static = {k: v for k, v in kw.items() if k in _STATIC_KEYS}
    traced = {k: v for k, v in kw.items() if k not in _STATIC_KEYS}
    key = (fn.__name__, tuple(sorted(static.items())))
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(functools.partial(fn, **static))
    return _JIT_CACHE[key](*arrays, **traced)


def _mask_stripe(kvmask, j, bkv):
    return None if kvmask is None else kvmask[:, j * bkv:(j + 1) * bkv]


def fwd_q_tile(q8, k8, v8, kvmask, *, seed, bh, row0, scal,
               mask_mode: str, window: int, q_len: int, s_len: int,
               fmt_s: str, fmt_p: str, rounding_s: str, rounding_p: str,
               saturate_s: bool, saturate_p: bool,
               block_kv: int = 0, payload: bool = True, chunk=None):
    """Fused FP8 attention forward for one (bq, D) query tile against the
    full padded (Sp, D) K/V of its (batch, kv-head), streamed in
    `block_kv`-row stripes (0 = one stripe; fully-masked stripes skipped)
    with ONE pass per stripe (the online-softmax recurrence — see
    `fwd_stripe_online`).

    scal: indexable [f_s, s_s, f_p, f_o] (see module docstring).
    Returns (o_bf16 (bq, D), amax_s, amax_p, s8_tiles, p8_tiles) — the
    payload tile lists (one (bq, LANE) tile per LANE column block, masked
    positions zeroed, empty when payload=False; P tiles are the
    UNNORMALIZED E8 probs) are consumed by the reference drivers only.
    amaxes are in grid units over the attended region, exactly
    `fp8_amax_bits` over the masked logical payload."""
    f_s, s_s, f_p, f_o = scal[0], scal[1], scal[2], scal[3]
    bq = q8.shape[0]
    sp = k8.shape[0]
    bkv = sp if not block_kv else block_kv
    nk = sp // bkv
    jmin, jmax = kv_stripe_span(row0, bq, block_kv=bkv, n_kv=nk,
                                mask_mode=mask_mode, window=window)
    kw = _stripe_kw(seed, bh, row0, (f_s, s_s), mask_mode, window,
                    q_len, s_len, fmt_s, rounding_s, saturate_s)
    if chunk is not None:
        kw["chunk"] = chunk

    def stripes():
        for j in range(jmin, jmax + 1):
            yield (j, j * bkv, k8[j * bkv:(j + 1) * bkv],
                   v8[j * bkv:(j + 1) * bkv], _mask_stripe(kvmask, j, bkv))

    m = jnp.full((bq, 1), -1e30, jnp.float32)
    l = jnp.zeros((bq, 1), jnp.float32)
    acc = jnp.zeros((bq, v8.shape[1]), jnp.float32)
    amax_s = amax_p = jnp.float32(0.0)
    s8_j, p8_j = {}, {}
    for j, col0, ks, vs, ms in stripes():
        m, l, acc, amax_s, amax_p, s_tiles, p_tiles = _call_stripe(
            fwd_stripe_online, q8, ks, vs, ms, m, l, acc, amax_s, amax_p,
            f_p=f_p, fmt_p=fmt_p, rounding_p=rounding_p,
            saturate_p=saturate_p, payload=payload, **{**kw, "col0": col0})
        if payload:
            s8_j[j] = s_tiles
            p8_j[j] = p_tiles
    d_safe = jnp.where(l > 0, l, 1.0)   # fully-masked (padded) rows -> o = 0
    o = (acc * f_o / d_safe).astype(jnp.bfloat16)
    s8_tiles, p8_tiles = [], []
    if payload:
        # Skipped-stripe payload filler in the RESPECTIVE format (S8 and
        # P8 may differ, e.g. a mixed-format config).
        per_stripe = bkv // LANE
        zt_s = [jnp.zeros((bq, LANE), fmt_dtype(fmt_s))] * per_stripe
        zt_p = [jnp.zeros((bq, LANE), fmt_dtype(fmt_p))] * per_stripe
        for j in range(nk):
            s8_tiles += s8_j.get(j, zt_s)
            p8_tiles += p8_j.get(j, zt_p)
    return o, amax_s, amax_p, s8_tiles, p8_tiles


def fwd_q_tile_two_pass(q8, k8, v8, kvmask, *, seed, bh, row0, scal,
                        mask_mode: str, window: int, q_len: int, s_len: int,
                        fmt_s: str, fmt_p: str, rounding_s: str,
                        rounding_p: str, saturate_s: bool, saturate_p: bool,
                        block_kv: int = 0, chunk=None):
    """The PR-5 two-pass-per-stripe forward (final-max statistics first,
    then a normalized-P PV pass re-reading every stripe), retained as the
    baseline the one-pass rewrite is A/B-benched and equivalence-tested
    against. Returns (o_bf16, amax_s, l) — the normalized composition the
    one-pass output must match to within one final-divide rounding."""
    f_s, s_s, f_p, f_o = scal[0], scal[1], scal[2], scal[3]
    bq = q8.shape[0]
    sp = k8.shape[0]
    bkv = sp if not block_kv else block_kv
    nk = sp // bkv
    jmin, jmax = kv_stripe_span(row0, bq, block_kv=bkv, n_kv=nk,
                                mask_mode=mask_mode, window=window)
    kw = _stripe_kw(seed, bh, row0, (f_s, s_s), mask_mode, window,
                    q_len, s_len, fmt_s, rounding_s, saturate_s)
    if chunk is not None:
        kw["chunk"] = chunk

    def stripes():
        for j in range(jmin, jmax + 1):
            yield (j, j * bkv, k8[j * bkv:(j + 1) * bkv],
                   v8[j * bkv:(j + 1) * bkv], _mask_stripe(kvmask, j, bkv))

    m = jnp.full((bq, 1), -1e30, jnp.float32)
    amax_s = jnp.float32(0.0)
    for j, col0, ks, vs, ms in stripes():
        m, amax_s, _ = _call_stripe(fwd_stripe_m, q8, ks, ms, m, amax_s,
                                    payload=False, **{**kw, "col0": col0})
    l = jnp.zeros((bq, 1), jnp.float32)
    for j, col0, ks, vs, ms in stripes():
        l = _call_stripe(fwd_stripe_l, q8, ks, ms, m, l,
                         **{**kw, "col0": col0})
    d_safe = jnp.where(l > 0, l, 1.0)
    acc = jnp.zeros((bq, v8.shape[1]), jnp.float32)
    amax_p = jnp.float32(0.0)
    for j, col0, ks, vs, ms in stripes():
        acc, amax_p, _ = _call_stripe(
            fwd_stripe_pv, q8, ks, vs, ms, m, d_safe, acc, amax_p,
            f_p=f_p, fmt_p=fmt_p, rounding_p=rounding_p,
            saturate_p=saturate_p, payload=False, **{**kw, "col0": col0})
    o = (acc * f_o).astype(jnp.bfloat16)
    return o, amax_s, l


def bwd_q_tile(q8, k8, v8, do8, kvmask, *, seed, bh, row0, scal,
               mask_mode: str, window: int, q_len: int, s_len: int,
               fmt_s: str, fmt_p: str, fmt_e: str,
               rounding_s: str, rounding_p: str, rounding_e: str,
               saturate_s: bool, saturate_p: bool, saturate_e: bool,
               block_kv: int = 0, payload: bool = True):
    """Fused FP8 attention backward for one (bq, D) query tile: recomputes
    S8/P8 from the FP8 residuals (identical hash bits -> identical
    payloads), quantizes the dP and dS intermediates to the error format,
    and returns

        (dq (bq, D) f32, amax_dp, amax_ds, dp8_tiles, ds8_tiles,
         (m, d_safe, rd))

    The trailing stats tuple feeds the driver's dK/dV pass
    (`bwd_tile_dkv_stripe`), mirroring the kernel's two-stage structure
    (stats+dQ kernel, then dK/dV stripe kernel)."""
    (f_s, s_s, f_p, s_p, f_dp, s_dp, f_ds, f_dq, f_dk, f_dv) = (
        scal[0], scal[1], scal[2], scal[3], scal[4], scal[5], scal[6],
        scal[7], scal[8], scal[9])
    bq = q8.shape[0]
    sp = k8.shape[0]
    bkv = sp if not block_kv else block_kv
    nk = sp // bkv
    jmin, jmax = kv_stripe_span(row0, bq, block_kv=bkv, n_kv=nk,
                                mask_mode=mask_mode, window=window)
    kw = _stripe_kw(seed, bh, row0, (f_s, s_s), mask_mode, window,
                    q_len, s_len, fmt_s, rounding_s, saturate_s)
    bkw = dict(f_p=f_p, s_p=s_p, f_dp=f_dp, s_dp=s_dp, fmt_p=fmt_p,
               fmt_e=fmt_e, rounding_p=rounding_p, rounding_e=rounding_e,
               saturate_p=saturate_p, saturate_e=saturate_e)

    def stripes():
        for j in range(jmin, jmax + 1):
            yield (j, j * bkv, k8[j * bkv:(j + 1) * bkv],
                   v8[j * bkv:(j + 1) * bkv], _mask_stripe(kvmask, j, bkv))

    # Softmax statistics, recomputed bitwise (same ops, same bits).
    m = jnp.full((bq, 1), -1e30, jnp.float32)
    for j, col0, ks, vs, ms in stripes():
        m, _, _ = _call_stripe(fwd_stripe_m, q8, ks, ms, m,
                               jnp.float32(0.0), **{**kw, "col0": col0})
    l = jnp.zeros((bq, 1), jnp.float32)
    for j, col0, ks, vs, ms in stripes():
        l = _call_stripe(fwd_stripe_l, q8, ks, ms, m, l,
                         **{**kw, "col0": col0})
    d_safe = jnp.where(l > 0, l, 1.0)

    # Pass A: softmax-VJP row reduction rowsum(P * dP) + dP observation.
    rd = jnp.zeros((bq, 1), jnp.float32)
    amax_dp = jnp.float32(0.0)
    dp8_j = {}
    for j, col0, ks, vs, ms in stripes():
        rd, amax_dp, tiles = _call_stripe(
            bwd_stripe_rd, q8, ks, vs, do8, ms, m, d_safe, rd, amax_dp,
            payload=payload, **{**kw, "col0": col0}, **bkw)
        if payload:
            dp8_j[j] = tiles
    # Pass B (query side): dS quantization + the dQ accumulation.
    dq_acc = jnp.zeros((bq, q8.shape[1]), jnp.float32)
    amax_ds = jnp.float32(0.0)
    ds8_j = {}
    for j, col0, ks, vs, ms in stripes():
        dq_acc, amax_ds, tiles = _call_stripe(
            bwd_stripe_dq, q8, ks, vs, do8, ms, m, d_safe, rd, dq_acc,
            amax_ds, f_ds=f_ds, payload=payload,
            **{**kw, "col0": col0}, **bkw)
        if payload:
            ds8_j[j] = tiles
    dp8_tiles, ds8_tiles = [], []
    if payload:
        per_stripe = bkv // LANE
        zt = [jnp.zeros((bq, LANE), fmt_dtype(fmt_e))] * per_stripe
        for j in range(nk):
            dp8_tiles += dp8_j.get(j, zt)
            ds8_tiles += ds8_j.get(j, zt)
    return (dq_acc * f_dq, amax_dp, amax_ds, dp8_tiles, ds8_tiles,
            (m, d_safe, rd))


def bwd_tile_dkv_stripe(q8, k8s, v8s, do8, kvmask_s, m, d_safe, rd,
                        dk_s, dv_s, *, f_ds, **kw):
    """Accumulate one (bq, D) query tile's dK/dV contributions into one
    stripe's (bkv, D) RAW-grid-unit accumulators, TQ sub-tile by TQ
    sub-tile via lax.fori_loop — each per-LANE-slice part is added
    individually (the flat left-to-right chain the kernel's dK/dV grid
    performs; pre-summing per q block would regroup the f32 adds and
    break block_q invariance). The f_dk / f_dv scale is applied ONCE by
    the caller after ALL tiles/heads have contributed (see
    `bwd_stripe_dkv` on the FMA hazard)."""
    bq = q8.shape[0]
    row0 = kw.pop("row0")

    def t2_body(t2, carry):
        dk_s, dv_s = carry
        r0 = t2 * TQ

        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, r0, TQ, 0)

        pk, pv_ = bwd_stripe_dkv(sl(q8), k8s, v8s, sl(do8), kvmask_s,
                                 sl(m), sl(d_safe), sl(rd), f_ds=f_ds,
                                 **{**kw, "row0": row0 + r0})
        for jj, (a, b) in enumerate(zip(pk, pv_)):
            js = slice(jj * LANE, (jj + 1) * LANE)
            dk_s = dk_s.at[js].add(a)
            dv_s = dv_s.at[js].add(b)
        return dk_s, dv_s

    return jax.lax.fori_loop(0, max(1, bq // TQ), t2_body, (dk_s, dv_s))


def fmt_dtype(fmt_name: str):
    return {"e5m2": jnp.float8_e5m2, "e4m3": jnp.float8_e4m3fn}[fmt_name]


# ---------------------------------------------------------------------------
# unfused reference drivers (the oracle the kernels are locked against)
# ---------------------------------------------------------------------------

def _pad_to(x, axis: int, mult: int, value=0):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def pad_qkv(q8, k8, v8, block_q: int, block_kv: int = LANE):
    """Zero-pad Q to a block_q multiple and S to a block_kv multiple (D to
    LANE). Padding is numerically invisible (exact-0.0 contributions,
    masked observations)."""
    qp = _pad_to(_pad_to(q8, 2, block_q), 3, LANE)
    kp = _pad_to(_pad_to(k8, 2, block_kv), 3, LANE)
    vp = _pad_to(_pad_to(v8, 2, block_kv), 3, LANE)
    return qp, kp, vp


def resolve_block_kv(s_len: int, block_kv) -> int:
    """The effective stripe size for a kv length: LANE-aligned, capped at
    the padded length (so short sequences keep a single stripe)."""
    if block_kv is None:
        block_kv = DEFAULT_BKV
    if block_kv % LANE:
        raise ValueError(f"block_kv must be a multiple of {LANE}, "
                         f"got {block_kv}")
    sp_lane = -(-max(s_len, 1) // LANE) * LANE
    return min(block_kv, sp_lane)


DEFAULT_BKV = 512


def fp8_attention_fwd_ref(q8, k8, v8, seed, scal, *, mask_mode="causal",
                          window: int = 0, kv_mask=None, chunk_pos=None,
                          block_q: int = LANE, block_kv=None,
                          fmt_s="e5m2", fmt_p="e5m2",
                          rounding_s="sr", rounding_p="sr",
                          saturate_s=True, saturate_p=True,
                          payload: bool = True):
    """Unfused composition oracle on logical (B,H,Q,D) / (B,Hkv,S,D) fp8
    payloads. Materializes and returns the S8/P8 payloads the fused kernel
    never writes (masked positions zeroed; payload=False skips them for
    long-context runs). Returns (o, amax_s, amax_p, s8, p8) with o
    (B,H,Q,D) bf16, payloads (B,H,Q,S) or None, amaxes in grid units.
    mask_mode='chunk': kv_mask is (B, S) int32 slot POSITIONS (-1 = hole)
    and chunk_pos is (B, 2) int32 [start, n_valid] per batch row."""
    b_, h_, q_len, d = q8.shape
    s_len = k8.shape[2]
    g = h_ // k8.shape[1]
    bkv = resolve_block_kv(s_len, block_kv)
    qp, kp, vp = pad_qkv(q8, k8, v8, block_q, bkv)
    nq = qp.shape[2] // block_q
    o = []
    s8_all, p8_all = [], []
    amax_s = amax_p = jnp.float32(0.0)
    for b in range(b_):
        o_h, s8_h, p8_h = [], [], []
        if mask_mode == "chunk":
            # Slot positions pad with -1 (slot 0 is a VALID position).
            mrow = _pad_to(kv_mask[b:b + 1].astype(jnp.int32), 1, bkv, -1)
            chunk = (chunk_pos[b, 0], chunk_pos[b, 1])
        else:
            mrow = None if kv_mask is None \
                else _pad_to(kv_mask[b:b + 1].astype(jnp.int8), 1, bkv)
            chunk = None
        for h in range(h_):
            o_t, s8_t, p8_t = [], [], []
            for iq in range(nq):
                qt = qp[b, h, iq * block_q:(iq + 1) * block_q]
                ot, a_s, a_p, s8s, p8s = fwd_q_tile(
                    qt, kp[b, h // g], vp[b, h // g], mrow,
                    seed=seed, bh=b * h_ + h, row0=iq * block_q, scal=scal,
                    mask_mode=mask_mode, window=window,
                    q_len=q_len, s_len=s_len,
                    fmt_s=fmt_s, fmt_p=fmt_p, rounding_s=rounding_s,
                    rounding_p=rounding_p, saturate_s=saturate_s,
                    saturate_p=saturate_p, block_kv=bkv, payload=payload,
                    chunk=chunk)
                amax_s = jnp.maximum(amax_s, a_s)
                amax_p = jnp.maximum(amax_p, a_p)
                o_t.append(ot)
                if payload:
                    s8_t.append(jnp.concatenate(s8s, axis=1))
                    p8_t.append(jnp.concatenate(p8s, axis=1))
            o_h.append(jnp.concatenate(o_t, axis=0)[None])
            if payload:
                s8_h.append(jnp.concatenate(s8_t, axis=0)[None])
                p8_h.append(jnp.concatenate(p8_t, axis=0)[None])
        o.append(jnp.concatenate(o_h, axis=0)[None])
        if payload:
            s8_all.append(jnp.concatenate(s8_h, axis=0)[None])
            p8_all.append(jnp.concatenate(p8_h, axis=0)[None])
    o = jnp.concatenate(o, axis=0)[:, :, :q_len, :d]
    s8 = p8 = None
    if payload:
        s8 = jnp.concatenate(s8_all, axis=0)[:, :, :q_len, :s_len]
        p8 = jnp.concatenate(p8_all, axis=0)[:, :, :q_len, :s_len]
    return o, amax_s, amax_p, s8, p8


def fp8_attention_bwd_ref(q8, k8, v8, do8, seed, scal, *,
                          mask_mode="causal", window: int = 0, kv_mask=None,
                          block_q: int = LANE, block_kv=None,
                          fmt_s="e5m2", fmt_p="e5m2", fmt_e="e5m2",
                          rounding_s="sr", rounding_p="sr", rounding_e="sr",
                          saturate_s=True, saturate_p=True,
                          saturate_e=False, payload: bool = True):
    """Unfused backward oracle. Returns (dq, dk, dv, amax_dp, amax_ds,
    dp8, ds8): dq (B,H,Q,D) f32, dk/dv (B,Hkv,S,D) f32 (GQA groups
    accumulated in head order), payloads (B,H,Q,S) or None."""
    b_, h_, q_len, d = q8.shape
    hkv, s_len = k8.shape[1], k8.shape[2]
    g = h_ // hkv
    bkv = resolve_block_kv(s_len, block_kv)
    qp, kp, vp = pad_qkv(q8, k8, v8, block_q, bkv)
    dop = _pad_to(_pad_to(do8, 2, block_q), 3, LANE)
    sp, dp_ = kp.shape[2], kp.shape[3]
    nq = qp.shape[2] // block_q
    dq = jnp.zeros(qp.shape, jnp.float32)
    dk = jnp.zeros((b_, hkv, sp, dp_), jnp.float32)
    dv = jnp.zeros((b_, hkv, sp, dp_), jnp.float32)
    amax_dp = amax_ds = jnp.float32(0.0)
    dp8_all, ds8_all = [], []
    for b in range(b_):
        dp8_h, ds8_h = [], []
        mrow = None if kv_mask is None \
            else _pad_to(kv_mask[b:b + 1].astype(jnp.int8), 1, bkv)
        for h in range(h_):
            dp8_t, ds8_t = [], []
            for iq in range(nq):
                sl = slice(iq * block_q, (iq + 1) * block_q)
                dq_t, a_dp, a_ds, dp8s, ds8s, (m_t, dsafe_t, rd_t) = \
                    bwd_q_tile(
                        qp[b, h, sl], kp[b, h // g], vp[b, h // g],
                        dop[b, h, sl], mrow,
                        seed=seed, bh=b * h_ + h, row0=iq * block_q,
                        scal=scal, mask_mode=mask_mode, window=window,
                        q_len=q_len, s_len=s_len,
                        fmt_s=fmt_s, fmt_p=fmt_p, fmt_e=fmt_e,
                        rounding_s=rounding_s, rounding_p=rounding_p,
                        rounding_e=rounding_e, saturate_s=saturate_s,
                        saturate_p=saturate_p, saturate_e=saturate_e,
                        block_kv=bkv, payload=payload)
                dq = dq.at[b, h, sl].set(dq_t)
                # dK/dV stripe pass (the kernel's second backward stage).
                jmin, jmax = kv_stripe_span(
                    iq * block_q, block_q, block_kv=bkv, n_kv=sp // bkv,
                    mask_mode=mask_mode, window=window)
                for j in range(jmin, jmax + 1):
                    sj = slice(j * bkv, (j + 1) * bkv)
                    ms_j = None if mrow is None else mrow[:, sj]
                    dk_s, dv_s = _call_stripe(
                        bwd_tile_dkv_stripe, qp[b, h, sl],
                        kp[b, h // g, sj], vp[b, h // g, sj],
                        dop[b, h, sl], ms_j, m_t, dsafe_t, rd_t,
                        dk[b, h // g, sj], dv[b, h // g, sj],
                        f_ds=scal[6], seed=seed, bh=b * h_ + h,
                        row0=iq * block_q, col0=j * bkv,
                        scal2=(scal[0], scal[1]), mask_mode=mask_mode,
                        window=window, q_len=q_len, s_len=s_len,
                        fmt_s=fmt_s, rounding_s=rounding_s,
                        saturate_s=saturate_s, f_p=scal[2], s_p=scal[3],
                        f_dp=scal[4], s_dp=scal[5], fmt_p=fmt_p,
                        fmt_e=fmt_e, rounding_p=rounding_p,
                        rounding_e=rounding_e, saturate_p=saturate_p,
                        saturate_e=saturate_e)
                    dk = dk.at[b, h // g, sj].set(dk_s)
                    dv = dv.at[b, h // g, sj].set(dv_s)
                amax_dp = jnp.maximum(amax_dp, a_dp)
                amax_ds = jnp.maximum(amax_ds, a_ds)
                if payload:
                    dp8_t.append(jnp.concatenate(dp8s, axis=1))
                    ds8_t.append(jnp.concatenate(ds8s, axis=1))
            if payload:
                dp8_h.append(jnp.concatenate(dp8_t, axis=0)[None])
                ds8_h.append(jnp.concatenate(ds8_t, axis=0)[None])
        if payload:
            dp8_all.append(jnp.concatenate(dp8_h, axis=0)[None])
            ds8_all.append(jnp.concatenate(ds8_h, axis=0)[None])
    # Raw-units accumulation, single scale multiply (see bwd_stripe_dkv).
    dq = dq[:, :, :q_len, :d]
    dk = dk[:, :, :s_len, :d] * scal[8]
    dv = dv[:, :, :s_len, :d] * scal[9]
    dp8 = ds8 = None
    if payload:
        dp8 = jnp.concatenate(dp8_all, axis=0)[:, :, :q_len, :s_len]
        ds8 = jnp.concatenate(ds8_all, axis=0)[:, :, :q_len, :s_len]
    return dq, dk, dv, amax_dp, amax_ds, dp8, ds8
