"""Fused FP8 flash-attention as a composable JAX op (the attention analogue
of core.qlinear's fused quantize-in-epilogue path).

`fp8_sdpa(q, k, v)` is scaled-dot-product attention whose forward and
backward inner products all take FP8 operands, with the score matrix S, the
softmax probs P, and the backward dP/dS intermediates quantized *inside* the
Pallas kernel (delayed-scaling amax observed in the same pass) — S and P are
never materialized in HBM, and the FP8 q/k/v payloads double as the
flash-style backward residuals. K/V stream through the kernels in
`QuantConfig.attn_block_kv`-row stripes (VMEM footprint independent of the
sequence length; fully-masked stripes of causal/sliding-window tiles are
skipped), so 32k+ contexts train and serve through the same kernels; the
amax observations are masked to the attended region so they cannot depend
on the stripe partition. Class assignment follows the recipe: S and P
are activations (saturating e4m3 under `hybrid`, Noune et al. 2206.02915);
dO/dP/dS are errors (e5m2, inf kept so the dynamic loss scaler of
Micikevicius et al. 1710.03740 sees overflow).

Scale-site grammar (scaling.context.attention_keys): one "sdpa" site
replaces the unfused path's qk/pv qeinsum pair, with operand sites
{#q,#k,#v}.A, in-kernel forward sites #qk.A / #p.A, and error sites
#E (dO) / #dp.E / #ds.E riding the token cotangent channels 0/3/4.

`fp8_sdpa_decode` is the serving-side forward: deterministic RNE, frozen
scales, and — when the KV cache is FP8 — the cache payloads feed the kernel
DIRECTLY with their frozen per-site scales, eliminating the
dequantize -> requantize round trip of the unfused decode path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision_policy import ACT, ERROR, QuantConfig, dtype_of
from repro.core.qlinear import _health, _observe, _quant_operand, _track
from repro.obs.trace import scope
from repro.scaling import context as scale_ctx

Array = jax.Array

# Per-site scale-vector layout: operands q/k/v, in-kernel forward sites
# S ("#qk.A") and P ("#p.A"), then the error-class dO ("#E") and in-kernel
# dP/dS ("#dp.E"/"#ds.E").
ATTN_SCALES = 8
_ORDER = ("q", "k", "v", "s", "p", "do", "dp", "ds")


def fuse_attention(cfg: QuantConfig) -> bool:
    """True when attention routes through the fused FP8 flash kernel:
    Pallas backend + delayed scaling (the in-kernel Q nodes need
    history-derived scales), attention quantization on, and the
    `fuse_attention` knob not switched off."""
    return (cfg.enabled and cfg.quantize_attention and cfg.delayed
            and cfg.fuse_attention and cfg.backend.startswith("pallas"))


def _fwd_factors(scales: Array, sm_scale: float):
    """(4,) f32 kernel factors [f_s, s_s, f_p, f_o] from the site scales.
    Single-multiply form: the kernel (and the unfused oracle) apply each
    collapsed factor once, mirroring `_fused_gemm`'s kscale convention."""
    f_s = scales[0] * scales[1] * jnp.float32(sm_scale) / scales[3]
    return jnp.stack([f_s, scales[3], 1.0 / scales[4],
                      scales[4] * scales[2]])


def _bwd_factors(scales: Array, sm_scale: float):
    """(10,) f32 backward factors (see kernels.fp8_attention.ref
    bwd_q_tile): [f_s, s_s, f_p, s_p, f_dp, s_dp, f_ds, f_dq, f_dk, f_dv].
    """
    f_s = scales[0] * scales[1] * jnp.float32(sm_scale) / scales[3]
    return jnp.stack([
        f_s, scales[3], 1.0 / scales[4], scales[4],
        scales[5] * scales[2] / scales[6], scales[6],
        jnp.float32(sm_scale) / scales[7],
        scales[7] * scales[1], scales[7] * scales[0],
        scales[4] * scales[5]])


def _kernel_kwargs(cfg: QuantConfig):
    return dict(fmt_s=cfg.format_for(ACT), fmt_p=cfg.format_for(ACT),
                rounding_s=cfg.rounding_for(ACT),
                rounding_p=cfg.rounding_for(ACT),
                saturate_s=cfg.saturate_for(ACT),
                saturate_p=cfg.saturate_for(ACT),
                block_q=cfg.attn_block_q, block_kv=cfg.attn_block_kv,
                autotune=cfg.autotune,
                interpret=cfg.backend == "pallas_interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _fp8_sdpa(cfg: QuantConfig, mask_mode: str, window: int,
              sm_scale: float, q: Array, k: Array, v: Array, key: Array,
              scales: Array, token: Array):
    """Returns (o, fwd_obs) with fwd_obs = [amax_q, amax_k, amax_v,
    amax_s, amax_p] in real units (zeros unless cfg.scaling == 'delayed');
    when cfg.track_health, fwd_obs extends to (15,) with the (sat, flush)
    fraction pairs of q/k/v (payload reads) and in-kernel S/P. token:
    f32[token_width] whose cotangent carries
    [amax_dO, 0, 0, amax_dP, amax_dS] (+ health pairs when tracking)."""
    out, _ = _fp8_sdpa_fwd(cfg, mask_mode, window, sm_scale, q, k, v, key,
                           scales, token)
    return out


def _fp8_sdpa_fwd(cfg, mask_mode, window, sm_scale, q, k, v, key, scales,
                  token):
    from repro.kernels.fp8_attention import ops as attn_ops  # lazy
    k_q, k_k, k_v, k_seed, k_bwd = jax.random.split(key, 5)
    q8 = _quant_operand(q, ACT, cfg, k_q, scale=scales[0])
    k8 = _quant_operand(k, ACT, cfg, k_k, scale=scales[1])
    v8 = _quant_operand(v, ACT, cfg, k_v, scale=scales[2])
    # In-kernel SR bits come from a counter hash of this seed + absolute
    # coordinates (no rand array in HBM; bits are tiling-invariant).
    with scope("fp8.sr_bits"):
        seed = jax.random.bits(k_seed, (), jnp.uint32)
    outs = attn_ops.fp8_attention_fwd(
        q8.data, k8.data, v8.data, seed, _fwd_factors(scales, sm_scale),
        mask_mode=mask_mode, window=window, with_counts=_track(cfg),
        **_kernel_kwargs(cfg))
    if _track(cfg):
        o, amax_s, amax_p, hs, hp = outs
    else:
        o, amax_s, amax_p = outs
    obs = jnp.stack([_observe(q8, cfg), _observe(k8, cfg),
                     _observe(v8, cfg), amax_s * scales[3],
                     amax_p * scales[4]])
    if _track(cfg):
        obs = jnp.concatenate([obs, _health(q8, cfg, ACT),
                               _health(k8, cfg, ACT),
                               _health(v8, cfg, ACT), hs, hp])
    res = (q8, k8, v8, seed, scales, k_bwd,
           jnp.zeros((0,), q.dtype), jnp.zeros((0,), k.dtype),
           jnp.zeros((0,), v.dtype))
    return (o.astype(dtype_of(cfg.output_dtype)), obs), res


def _fp8_sdpa_bwd(cfg, mask_mode, window, sm_scale, res, ct):
    from repro.kernels.fp8_attention import ops as attn_ops  # lazy
    dy, _ = ct   # fwd_obs cotangent discarded
    q8, k8, v8, seed, scales, k_bwd, q_wit, k_wit, v_wit = res
    qdo = _quant_operand(dy, ERROR, cfg, k_bwd, scale=scales[5])
    outs = attn_ops.fp8_attention_bwd(
        q8.data, k8.data, v8.data, qdo.data, seed,
        _bwd_factors(scales, sm_scale),
        mask_mode=mask_mode, window=window,
        fmt_e=cfg.format_for(ERROR), rounding_e=cfg.rounding_for(ERROR),
        saturate_e=cfg.saturate_for(ERROR), with_counts=_track(cfg),
        **_kernel_kwargs(cfg))
    health = None
    if _track(cfg):
        dq, dk, dv, amax_dp, amax_ds, hdp, hds = outs
        health = scale_ctx.health_pairs(
            [_health(qdo, cfg, ERROR), None, None, hdp, hds])
    else:
        dq, dk, dv, amax_dp, amax_ds = outs
    token_ct = scale_ctx.token_cotangent(
        e=_observe(qdo, cfg), dp=amax_dp * scales[6],
        ds=amax_ds * scales[7], health=health)
    return (dq.astype(q_wit.dtype), dk.astype(k_wit.dtype),
            dv.astype(v_wit.dtype),
            np.zeros(np.shape(k_bwd), dtype=jax.dtypes.float0),
            jnp.zeros((ATTN_SCALES,), jnp.float32), token_ct)


_fp8_sdpa.defvjp(_fp8_sdpa_fwd, _fp8_sdpa_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _check_frozen_sites(ctx, keys):
    """Frozen serving must not fall back to silent unit scales for the
    fused-attention sites (the same failure class _kv_scales refuses for
    the FP8 KV cache): a frozen-scales file calibrated before this path
    existed — or with fuse_attention=False — lacks the {#q,#k,#v,#qk,#p}.A
    sites, and the in-kernel Q nodes would quantize with wrong constants
    burned into the jitted program."""
    if ctx.mode != "frozen":
        return
    missing = [keys[n] for n in ("q", "k", "v", "s", "p")
               if not ctx.has_scale(keys[n])]
    if missing:
        raise ValueError(
            f"frozen serving through the fused FP8 attention kernel, but "
            f"site(s) {missing} have no calibrated scale — the in-kernel "
            "S/P Q nodes would use silent unit scales; recalibrate with "
            "fuse_attention enabled or serve with "
            "QuantConfig(fuse_attention=False)")


def fp8_sdpa(q: Array, k: Array, v: Array, *, key: Optional[Array],
             cfg: QuantConfig, sm_scale: float, mask_mode: str = "causal",
             window: int = 0, site: Optional[str] = None) -> Array:
    """Fused FP8 attention over (B,H,Q,dh) queries and UNREPEATED
    (B,Hkv,S,dh) keys/values — GQA grouping happens in the kernel's block
    index maps, so the `_repeat_kv` copies of the unfused path are never
    materialized. mask_mode: 'causal' (with optional sliding `window`) or
    'full'.

    Under an active ScaleContext with a site name, operand scales come from
    ScaleState history, forward amaxes (q/k/v + in-kernel S/P) are recorded,
    and the dO/dP/dS error observations ride the site token's cotangent.
    """
    if key is None:
        if cfg.needs_key:
            raise ValueError("QuantConfig uses stochastic rounding; "
                             "fp8_sdpa needs a PRNG key")
        key = jax.random.PRNGKey(0)
    ctx = scale_ctx.current()
    if cfg.delayed and ctx is not None and site is not None:
        skey = ctx.site_key(site)
        keys = scale_ctx.attention_keys(skey)
        for kk in keys.values():
            ctx.register(kk)
        _check_frozen_sites(ctx, keys)
        scales = jnp.stack([ctx.scale_for(keys[n]) for n in _ORDER])
        token = ctx.token_for(skey)
        o, obs = _fp8_sdpa(cfg, mask_mode, window, sm_scale, q, k, v, key,
                           scales, token)
        for i, n in enumerate(_ORDER[:5]):
            ctx.record(keys[n], obs[i])
        if _track(cfg):
            # Health pairs follow the 5 amaxes: q/k/v payloads, then the
            # in-kernel S/P tiles.
            for i, n in enumerate(_ORDER[:5]):
                ctx.record_health(keys[n], obs[5 + 2 * i: 7 + 2 * i])
        return o
    o, _ = _fp8_sdpa(cfg, mask_mode, window, sm_scale, q, k, v, key,
                     jnp.ones((ATTN_SCALES,), jnp.float32),
                     jnp.zeros((scale_ctx.token_width(_track(cfg)),),
                               jnp.float32))
    return o


def fp8_sdpa_decode(q: Array, k_cached: Array, v_cached: Array,
                    valid: Array, *, cfg: QuantConfig, sm_scale: float,
                    key: Optional[Array] = None,
                    k_cache_scale=1.0, v_cache_scale=1.0,
                    site: Optional[str] = None) -> Array:
    """Serving decode through the fused kernel (forward only, 'kv' mask).

    q: (B,H,1,dh) high precision. k_cached/v_cached: (B,Hkv,C,dh) — FP8 KV
    cache payloads are consumed DIRECTLY with their frozen per-site cache
    scales (k_cache_scale/v_cache_scale, the `.../kv/{k,v}#A` constants): no
    dequantize -> requantize round trip, and the kernel never materializes
    the repeated GQA copies. bf16 caches are quantized here at the #k.A/#v.A
    sites. valid: (B, C) slot-validity mask."""
    ctx = scale_ctx.current()
    keys = None
    one = jnp.float32(1.0)
    s_q = s_s = s_p = one
    if cfg.delayed and ctx is not None and site is not None:
        skey = ctx.site_key(site)
        keys = scale_ctx.attention_keys(skey)
        for n in ("q", "k", "v", "s", "p"):
            ctx.register(keys[n])
        _check_frozen_sites(ctx, keys)
        s_q = ctx.scale_for(keys["q"])
        s_s = ctx.scale_for(keys["s"])
        s_p = ctx.scale_for(keys["p"])
    if key is None:
        key = jax.random.PRNGKey(0)
    k_q, k_k, k_v, k_seed = jax.random.split(key, 4)
    q8 = _quant_operand(q, ACT, cfg, k_q, scale=s_q)
    if k_cached.dtype in (jnp.float8_e5m2, jnp.float8_e4m3fn):
        k8d, v8d = k_cached, v_cached
        s_k = jnp.asarray(k_cache_scale, jnp.float32)
        s_v = jnp.asarray(v_cache_scale, jnp.float32)
    else:
        s_k = ctx.scale_for(keys["k"]) if keys is not None else one
        s_v = ctx.scale_for(keys["v"]) if keys is not None else one
        qk8 = _quant_operand(k_cached, ACT, cfg, k_k, scale=s_k)
        qv8 = _quant_operand(v_cached, ACT, cfg, k_v, scale=s_v)
        k8d, v8d = qk8.data, qv8.data
    from repro.kernels.fp8_attention import ops as attn_ops  # lazy
    seed = jax.random.bits(k_seed, (), jnp.uint32)
    f_s = s_q * s_k * jnp.float32(sm_scale) / s_s
    scal = jnp.stack([f_s, s_s, 1.0 / s_p, s_p * s_v])
    o, amax_s, amax_p = attn_ops.fp8_attention_fwd(
        q8.data, k8d, v8d, seed, scal, mask_mode="kv",
        kv_mask=valid.astype(jnp.int8), **_kernel_kwargs(cfg))
    if keys is not None:
        ctx.record(keys["q"], _observe(q8, cfg))
        ctx.record(keys["s"], amax_s * s_s)
        ctx.record(keys["p"], amax_p * s_p)
    return o.astype(dtype_of(cfg.output_dtype))


def fp8_sdpa_chunk(q: Array, k_cached: Array, v_cached: Array,
                   slot_pos: Array, chunk_pos: Array, *, cfg: QuantConfig,
                   sm_scale: float, window: int = 0,
                   key: Optional[Array] = None,
                   k_cache_scale=1.0, v_cache_scale=1.0,
                   site: Optional[str] = None) -> Array:
    """Serving chunk step through the fused kernel (forward only, 'chunk'
    mask): T consecutive tokens per request attend a paged/gathered KV
    layout in ONE kernel call — the chunked-prefill + decode unified path
    (decode is the T=1 special case; the mask reduces exactly to the 'kv'
    decode condition then).

    q: (B,H,T,dh) high precision — the chunk's queries. k_cached/v_cached:
    (B,Hkv,C,dh) gathered cache rows, FP8 payloads consumed DIRECTLY with
    their frozen cache scales, bf16 quantized here at the #k.A/#v.A sites
    (identical to `fp8_sdpa_decode`). slot_pos: (B,C) int32 absolute
    position held by each gathered column (-1 = hole). chunk_pos: (B,2)
    int32 [start, n_valid] — q row r of request b sits at position
    start_b + r when r < n_valid_b, and is fully masked (exact-zero
    output row) otherwise, so ragged chunks batch under one static shape.
    Validity is (slot >= 0) & (slot <= qpos) [& window band] — in-chunk
    causality emerges from the position comparison, with no separate
    causal mask."""
    ctx = scale_ctx.current()
    keys = None
    one = jnp.float32(1.0)
    s_q = s_s = s_p = one
    if cfg.delayed and ctx is not None and site is not None:
        skey = ctx.site_key(site)
        keys = scale_ctx.attention_keys(skey)
        for n in ("q", "k", "v", "s", "p"):
            ctx.register(keys[n])
        _check_frozen_sites(ctx, keys)
        s_q = ctx.scale_for(keys["q"])
        s_s = ctx.scale_for(keys["s"])
        s_p = ctx.scale_for(keys["p"])
    if key is None:
        key = jax.random.PRNGKey(0)
    k_q, k_k, k_v, k_seed = jax.random.split(key, 4)
    q8 = _quant_operand(q, ACT, cfg, k_q, scale=s_q)
    if k_cached.dtype in (jnp.float8_e5m2, jnp.float8_e4m3fn):
        k8d, v8d = k_cached, v_cached
        s_k = jnp.asarray(k_cache_scale, jnp.float32)
        s_v = jnp.asarray(v_cache_scale, jnp.float32)
    else:
        s_k = ctx.scale_for(keys["k"]) if keys is not None else one
        s_v = ctx.scale_for(keys["v"]) if keys is not None else one
        qk8 = _quant_operand(k_cached, ACT, cfg, k_k, scale=s_k)
        qv8 = _quant_operand(v_cached, ACT, cfg, k_v, scale=s_v)
        k8d, v8d = qk8.data, qv8.data
    from repro.kernels.fp8_attention import ops as attn_ops  # lazy
    seed = jax.random.bits(k_seed, (), jnp.uint32)
    f_s = s_q * s_k * jnp.float32(sm_scale) / s_s
    scal = jnp.stack([f_s, s_s, 1.0 / s_p, s_p * s_v])
    o, amax_s, amax_p = attn_ops.fp8_attention_fwd(
        q8.data, k8d, v8d, seed, scal, mask_mode="chunk", window=window,
        kv_mask=slot_pos.astype(jnp.int32),
        chunk_pos=chunk_pos.astype(jnp.int32), **_kernel_kwargs(cfg))
    if keys is not None:
        ctx.record(keys["q"], _observe(q8, cfg))
        ctx.record(keys["s"], amax_s * s_s)
        ctx.record(keys["p"], amax_p * s_p)
    return o.astype(dtype_of(cfg.output_dtype))
