"""Quantized GEMM with the paper's Fig. 1a dataflow, as a composable JAX op.

`qeinsum(spec, a, b)` is an einsum whose *forward and backward* GEMMs all take
FP8 operands and accumulate in FP32:

    forward:   Y  = Q_A(a) . Q_W(b)                 (fp8 x fp8 -> fp32)
    backward:  dA = Q_E(dY) . Q_W(b)^T              (fp8 x fp8 -> fp32)
               dW = Q_A(a)^T . Q_E(dY), then Q_G    (fp8 x fp8 -> fp32 -> fp8)

Q_A/Q_W/Q_E/Q_G are the quantization nodes for activations / weights / errors
/ weight-gradients with per-class rounding (paper: SR for A, E, G; RNE for W)
and per-class overflow behavior (errors keep inf so dynamic loss scaling can
back off).

The residuals saved for backward are the *quantized* fp8 tensors — a 4x
activation-memory saving relative to an f32-residual baseline, mirroring the
paper's storage story.

On TPU the inner computes route to the Pallas kernels in
repro.kernels.{fp8_matmul,fused_quant_matmul}; on CPU (and for the dry-run)
they run an XLA path that upcasts fp8 -> bf16 and issues a dot with
preferred_element_type=f32, which is exactly the MXU dataflow the kernels
implement (bf16 multiplies into an f32 accumulator).

Under a Pallas backend with delayed scaling the projection GEMMs take the
FUSED quantize-in-epilogue path (see `_fused_epilogue`): each of the three
GEMMs applies its output Q node inside the kernel epilogue (fwd Y = Q_A(A.W)
via the 'nn' layout, dgrad dA = Q_E(dY.W^T) via 'nt', wgrad dW = Q_G(A^T.dY)
via 'tn' — no materialized transposes), writing FP8 straight from the VMEM
accumulator and observing the delayed-scaling amax in the same pass. The
output Q nodes quantize against their own scale sites ("#y.A", "#da.E",
"#G" — see scaling.context.fused_output_keys); the fused observations are
bit-identical to the `_observe` bit-pattern reduction over the payloads
(tests/test_fused_epilogue.py).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import QTensor, fp8_amax_bits
from repro.core.quantize import dequantize as _dequantize
from repro.core.quantize import quantize as _quantize
from repro.core.fp8_formats import get_format
from repro.core.precision_policy import (ACT, ERROR, GRAD, WEIGHT, PAPER_FP8,
                                         QuantConfig, dtype_of)
from repro.obs.counters import payload_health
from repro.obs.trace import scope
from repro.scaling import context as scale_ctx

Array = jax.Array

# Per-site scale-vector layout fed into _qeinsum:
#   [a, b, E, G, Y, dA_err] — operands, error, FP8-stored weight grad, and
#   the two fused-epilogue output sites (Y forward, error-class dgrad).
N_SCALES = 6


# ---------------------------------------------------------------------------
# einsum spec utilities
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def parse_spec(spec: str) -> Tuple[str, str, str]:
    spec = spec.replace(" ", "")
    lhs, out = spec.split("->")
    a, b = lhs.split(",")
    if "." in spec:
        raise ValueError(f"qeinsum does not support ellipsis specs: {spec!r}")
    return a, b, out


@functools.lru_cache(maxsize=None)
def adjoint_specs(spec: str) -> Tuple[str, str]:
    """Derive the einsum specs computing dA and dB for `spec`.

    For Y = einsum('A,B->O', a, b):  dA = einsum('O,B->A', dy, b) and
    dB = einsum('A,O->B', a, dy).  Valid as long as every index of each
    operand appears in the union of the output and the other operand (true
    for every GEMM-like contraction; sum-only indices are rejected).
    """
    a, b, o = parse_spec(spec)
    for idx in a:
        if idx not in o and idx not in b:
            raise ValueError(f"index {idx!r} of lhs is summed-only in {spec!r}")
    for idx in b:
        if idx not in o and idx not in a:
            raise ValueError(f"index {idx!r} of rhs is summed-only in {spec!r}")
    return f"{o},{b}->{a}", f"{a},{o}->{b}"


# ---------------------------------------------------------------------------
# operand quantization + fp8 compute
# ---------------------------------------------------------------------------

@scope("fp8.quant")
def _quant_operand(x: Array, cls: str, cfg: QuantConfig, key: Array,
                   scale: Optional[Array] = None) -> QTensor:
    """Quantize one operand. With delayed scaling, `scale` is the
    history-derived per-site scale (an explicit input — no amax reduction
    over x happens here); otherwise the legacy jit-amax / unit-scale path."""
    fmt = get_format(cfg.format_for(cls))
    if cfg.delayed:
        return _quantize(
            x, fmt,
            rounding=cfg.rounding_for(cls),
            key=key,
            scale=jnp.float32(1.0) if scale is None else scale,
            saturate=cfg.saturate_for(cls),
        )
    return _quantize(
        x, fmt,
        rounding=cfg.rounding_for(cls),
        key=key,
        use_amax_scale=cfg.amax_for(cls),
        saturate=cfg.saturate_for(cls),
    )


def _pallas_matmul_spec(spec: str) -> bool:
    """True for '...k,kn->...n'-shaped contractions the fp8_matmul kernel covers."""
    a, b, o = parse_spec(spec)
    return (len(b) == 2 and a[-1] == b[0] and o == a[:-1] + b[1]
            and b[1] not in a and b[0] not in o)


def _fused_epilogue(spec: str, classes: Tuple[str, str],
                    cfg: QuantConfig) -> bool:
    """True when this qeinsum routes its three GEMMs (fwd, dgrad, wgrad)
    through the output-quantizing fused Pallas kernels: the paper's Fig. 1a
    dataflow with each Q node IN the GEMM epilogue (output written straight
    to FP8 from the VMEM accumulator, amax observed in the same pass).

    Requires a Pallas backend + delayed scaling (output Q nodes need
    history-derived scales) on a '...k,kn->...n' contraction with a weight
    operand — which covers every projection GEMM; the 4D attention
    contractions keep the unfused path."""
    return (cfg.enabled and cfg.delayed and cfg.fuse_epilogue
            and cfg.backend.startswith("pallas")
            and WEIGHT in classes and _pallas_matmul_spec(spec))


def _fused_gemm(x8: Array, w8: Array, sx: Array, sw: Array, s_out: Array,
                cfg: QuantConfig, key: Array, out_cls: str, dims: str):
    """One fused output-quantizing GEMM: fp8 operands (2D) in, fp8 output +
    grid-amax observation out — plus a (2,) [sat_frac, flush_frac] health
    pair from the kernel's count epilogue under cfg.track_health (None
    otherwise; counted in VMEM next to the amax, zero extra HBM passes).

    Value semantics: out8 = Q_cls((x8.w8 * sx * sw) / s_out), computed as
    Q((x8.w8) / (s_out / (sx*sw))) so the scaling collapses into the
    epilogue's single reciprocal multiply. The returned observation is the
    fused-epilogue amax de-scaled to real units — bit-identical to the
    `_observe` bit-pattern reduction over the materialized payload."""
    from repro.kernels.fused_quant_matmul import ops as fq_ops  # lazy
    s_prod = (sx * sw).astype(jnp.float32)
    kscale = s_out.astype(jnp.float32) / s_prod
    res = fq_ops.fused_quant_matmul(
        x8, w8, key, kscale, dims=dims,
        out_format=cfg.format_for(out_cls),
        rounding=cfg.rounding_for(out_cls),
        saturate=cfg.saturate_for(out_cls),
        with_amax=True, with_counts=_track(cfg), amax_units="grid",
        interpret=cfg.backend == "pallas_interpret")
    if _track(cfg):
        out8, amax_grid, health = res
    else:
        (out8, amax_grid), health = res, None
    return out8, amax_grid * s_out.astype(jnp.float32), health


def _fused_dequant(out8: Array, s_out: Array, cfg: QuantConfig) -> Array:
    return (out8.astype(jnp.float32) * s_out.astype(jnp.float32)) \
        .astype(dtype_of(cfg.output_dtype))


def _compute(spec: str, qa: QTensor, qb: QTensor, cfg: QuantConfig) -> Array:
    """fp8 x fp8 -> f32 (accumulate) -> output_dtype, optionally via Pallas."""
    compute_dtype = dtype_of(cfg.compute_dtype)
    out_scale = (qa.scale * qb.scale).astype(jnp.float32)
    if cfg.backend.startswith("pallas") and _pallas_matmul_spec(spec):
        from repro.kernels.fp8_matmul import ops as mm_ops  # lazy: no cycle
        a2 = qa.data.reshape((-1, qa.data.shape[-1]))
        y = mm_ops.fp8_matmul(a2, qb.data,
                              interpret=cfg.backend == "pallas_interpret")
        y = y.reshape(qa.data.shape[:-1] + (qb.data.shape[-1],))
    else:
        y = jnp.einsum(spec, qa.data.astype(compute_dtype),
                       qb.data.astype(compute_dtype),
                       preferred_element_type=jnp.float32)
    y = y * out_scale
    return y.astype(dtype_of(cfg.output_dtype))


def _plain_einsum(spec: str, a: Array, b: Array, cfg: QuantConfig) -> Array:
    compute_dtype = dtype_of(cfg.compute_dtype)
    y = jnp.einsum(spec, a.astype(compute_dtype), b.astype(compute_dtype),
                   preferred_element_type=jnp.float32)
    return y.astype(dtype_of(cfg.output_dtype))


# ---------------------------------------------------------------------------
# custom_vjp core
# ---------------------------------------------------------------------------

@scope("fp8.amax")
def _observe(q: QTensor, cfg: QuantConfig) -> Array:
    """Observed amax of a quantized operand, from the FP8 payload's bit
    patterns (uint8 reduce — no pass over the high-precision tensor)."""
    if not cfg.delayed:
        return jnp.float32(0.0)
    return fp8_amax_bits(q.data) * q.scale.astype(jnp.float32)


def _track(cfg: QuantConfig) -> bool:
    """Precision-health counters on? (delayed scaling only — the counters
    ride the delayed-scaling observation channels)."""
    return cfg.track_health and cfg.delayed


@scope("fp8.amax")
def _health(q: QTensor, cfg: QuantConfig, cls: str) -> Array:
    """(sat_frac, flush_frac) of a quantized operand, from the same uint8
    payload read `_observe` performs — XLA fuses the two reductions into
    one pass over the 1-byte payload."""
    return payload_health(q.data, cfg.format_for(cls))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _qeinsum(spec: str, classes: Tuple[str, str], cfg: QuantConfig,
             a: Array, b: Array, key: Array, scales: Array,
             token: Array) -> Tuple[Array, Array]:
    """Returns (y, fwd_obs) where fwd_obs = [amax_a, amax_b] — plus
    [amax_y] on the fused-epilogue path — (zeros unless cfg.scaling ==
    'delayed').

    scales: f32[6] per-site quantization scales [a, b, E, G, Y, dA_err]
    (history-derived under delayed scaling; ones otherwise — the last two
    are only consumed by the fused quantize-in-epilogue path). token:
    f32[TOKEN_CHANNELS] observation channel whose *cotangent* is defined as
    [amax_E, amax_G, amax_dA_err] — the backward-pass observations ride the
    gradient of this input out of value_and_grad.
    """
    out, _ = _qeinsum_fwd(spec, classes, cfg, a, b, key, scales, token)
    return out


def _qeinsum_fwd(spec, classes, cfg, a, b, key, scales, token):
    fused = _fused_epilogue(spec, classes, cfg)
    if fused:
        k_a, k_b, k_bwd, k_y = jax.random.split(key, 4)
    else:
        k_a, k_b, k_bwd = jax.random.split(key, 3)
    qa = _quant_operand(a, classes[0], cfg, k_a, scale=scales[0])
    qb = _quant_operand(b, classes[1], cfg, k_b, scale=scales[1])
    if fused:
        # Y = Q_A(A.W) with the Q node + amax observation in the epilogue.
        a2 = qa.data.reshape((-1, qa.data.shape[-1]))
        y8, obs_y, hy = _fused_gemm(a2, qb.data, qa.scale, qb.scale,
                                    scales[4], cfg, k_y, ACT, "nn")
        y = _fused_dequant(y8, scales[4], cfg) \
            .reshape(qa.data.shape[:-1] + (qb.data.shape[-1],))
        obs = jnp.stack([_observe(qa, cfg), _observe(qb, cfg), obs_y])
        if _track(cfg):
            # Health pairs ride behind the amaxes in the fwd_obs vector:
            # [.., ha(2), hb(2), hy(2)] — operand pairs from the payload
            # bits, the output pair from the kernel's count epilogue.
            obs = jnp.concatenate([obs, _health(qa, cfg, classes[0]),
                                   _health(qb, cfg, classes[1]), hy])
    else:
        y = _compute(spec, qa, qb, cfg)
        obs = jnp.stack([_observe(qa, cfg), _observe(qb, cfg)])
        if _track(cfg):
            obs = jnp.concatenate([obs, _health(qa, cfg, classes[0]),
                                   _health(qb, cfg, classes[1])])
    # Zero-size dtype witnesses so bwd can emit cotangents in primal dtypes.
    return (y, obs), (qa, qb, k_bwd, scales,
                      jnp.zeros((0,), a.dtype), jnp.zeros((0,), b.dtype))


def _qeinsum_bwd(spec, classes, cfg, res, ct):
    dy, _ = ct   # cotangent of the fwd_obs output is discarded
    qa, qb, k_bwd, scales, a_wit, b_wit = res
    a_dtype, b_dtype = a_wit.dtype, b_wit.dtype
    if _fused_epilogue(spec, classes, cfg):
        return _qeinsum_bwd_fused(spec, classes, cfg, qa, qb, k_bwd, scales,
                                  a_dtype, b_dtype, dy)
    k_e, k_ga, k_gb = jax.random.split(k_bwd, 3)
    qdy = _quant_operand(dy, ERROR, cfg, k_e, scale=scales[2])
    da_spec, db_spec = adjoint_specs(spec)
    da = _compute(da_spec, qdy, qb, cfg)
    db = _compute(db_spec, qa, qdy, cfg)
    # Weight gradients are stored in FP8 (tensor class G, paper Fig. 1b).
    # Implemented as fake-quant here; the optimizer unscales in FP32.
    obs_g = jnp.float32(0.0)
    h_g = jnp.zeros((2,), jnp.float32) if _track(cfg) else None
    if classes[0] == WEIGHT:
        da, og, hg = _fake_quant_grad(da, cfg, k_ga, scale=scales[3])
        obs_g = jnp.maximum(obs_g, og)
        h_g = jnp.maximum(h_g, hg) if h_g is not None else None
    if classes[1] == WEIGHT:
        db, og, hg = _fake_quant_grad(db, cfg, k_gb, scale=scales[3])
        obs_g = jnp.maximum(obs_g, og)
        h_g = jnp.maximum(h_g, hg) if h_g is not None else None
    health = scale_ctx.health_pairs(
        [_health(qdy, cfg, ERROR), h_g, None, None, None]) \
        if _track(cfg) else None
    token_ct = scale_ctx.token_cotangent(e=_observe(qdy, cfg), g=obs_g,
                                         health=health)
    # Cotangents match primal dtypes; the integer PRNG key gets float0 zeros.
    return (da.astype(a_dtype), db.astype(b_dtype),
            np.zeros(np.shape(k_bwd), dtype=jax.dtypes.float0),
            jnp.zeros((N_SCALES,), jnp.float32), token_ct)


def _qeinsum_bwd_fused(spec, classes, cfg, qa, qb, k_bwd, scales,
                       a_dtype, b_dtype, dy):
    """Backward of the fused quantize-in-epilogue path: both adjoint GEMMs
    write FP8 straight from the accumulator (dgrad via the 'nt' layout,
    wgrad via 'tn' — no materialized transpose), replacing the separate
    `_fake_quant_grad` pass and its extra full-precision HBM round-trip."""
    k_e, k_da, k_db = jax.random.split(k_bwd, 3)
    qdy = _quant_operand(dy, ERROR, cfg, k_e, scale=scales[2])
    dy2 = qdy.data.reshape((-1, qdy.data.shape[-1]))
    a2 = qa.data.reshape((-1, qa.data.shape[-1]))
    # Output class / scale site of each adjoint: the weight operand's
    # gradient is FP8-stored (class G); the activation operand receives the
    # error-class dgrad output (its own "#d{a,b}.E" site).
    cls_a = GRAD if classes[0] == WEIGHT else ERROR
    cls_b = GRAD if classes[1] == WEIGHT else ERROR
    s_da = scales[3] if cls_a == GRAD else scales[5]
    s_db = scales[3] if cls_b == GRAD else scales[5]
    # dA = Q(dY . W^T): (M, N) x (K, N) -> (M, K)
    da8, obs_da, h_da = _fused_gemm(dy2, qb.data, qdy.scale, qb.scale, s_da,
                                    cfg, k_da, cls_a, "nt")
    da = _fused_dequant(da8, s_da, cfg).reshape(qa.data.shape)
    # dW = Q(A^T . dY): (M, K) x (M, N) -> (K, N)
    db8, obs_db, h_db = _fused_gemm(a2, dy2, qa.scale, qdy.scale, s_db,
                                    cfg, k_db, cls_b, "tn")
    db = _fused_dequant(db8, s_db, cfg).reshape(qb.data.shape)
    obs_g = jnp.float32(0.0)
    obs_err = jnp.float32(0.0)
    track = _track(cfg)
    h_g = jnp.zeros((2,), jnp.float32) if track else None
    h_err = None
    if cls_a == GRAD:
        obs_g = jnp.maximum(obs_g, obs_da)
        h_g = jnp.maximum(h_g, h_da) if track else None
    else:
        obs_err = obs_da
        h_err = h_da
    if cls_b == GRAD:
        obs_g = jnp.maximum(obs_g, obs_db)
        h_g = jnp.maximum(h_g, h_db) if track else None
    else:
        obs_err = obs_db
        h_err = h_db
    health = scale_ctx.health_pairs(
        [_health(qdy, cfg, ERROR), h_g, h_err, None, None]) \
        if track else None
    token_ct = scale_ctx.token_cotangent(e=_observe(qdy, cfg), g=obs_g,
                                         err=obs_err, health=health)
    return (da.astype(a_dtype), db.astype(b_dtype),
            np.zeros(np.shape(k_bwd), dtype=jax.dtypes.float0),
            jnp.zeros((N_SCALES,), jnp.float32), token_ct)


def _fake_quant_grad(g: Array, cfg: QuantConfig, key: Array,
                     scale: Optional[Array] = None):
    fmt = get_format(cfg.format_for(GRAD))
    if cfg.delayed:
        q = _quantize(g, fmt, rounding=cfg.rounding_for(GRAD), key=key,
                      scale=scale, saturate=cfg.saturate_for(GRAD))
    else:
        q = _quantize(g, fmt, rounding=cfg.rounding_for(GRAD), key=key,
                      use_amax_scale=cfg.amax_for(GRAD),
                      saturate=cfg.saturate_for(GRAD))
    h = _health(q, cfg, GRAD) if _track(cfg) else None
    return _dequantize(q, dtype=g.dtype), _observe(q, cfg), h


_qeinsum.defvjp(_qeinsum_fwd, _qeinsum_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def qeinsum(spec: str, a: Array, b: Array, *,
            key: Optional[Array] = None,
            cfg: QuantConfig = PAPER_FP8,
            classes: Tuple[str, str] = (ACT, WEIGHT),
            site: Optional[str] = None) -> Array:
    """Quantized einsum (see module docstring). classes tags each operand as
    'act' or 'weight', selecting its rounding/format and whether its gradient
    is additionally stored as FP8 (weights only).

    site: stable name of this call site (scoped by scaling.context.scope).
    Under cfg.scaling == 'delayed' with an active ScaleContext, the operand
    scales are read from ScaleState history for this site and the observed
    amaxes are recorded back (forward classes via the context/aux channel,
    error/grad classes via the site token's cotangent). Without a site or
    context, delayed mode degrades to unit scales (the paper's global-loss-
    scale recipe).
    """
    parse_spec(spec)  # validate early
    if not cfg.enabled:
        return _plain_einsum(spec, a, b, cfg)
    if key is None:
        if cfg.needs_key:
            raise ValueError(
                f"QuantConfig uses stochastic rounding; qeinsum({spec!r}) "
                "needs a PRNG key")
        key = jax.random.PRNGKey(0)
    classes = tuple(classes)
    ctx = scale_ctx.current()
    if cfg.delayed and ctx is not None and site is not None:
        fused = _fused_epilogue(spec, classes, cfg)
        skey = ctx.site_key(site)
        keys = scale_ctx.operand_keys(skey, classes)
        ctx.register(keys["a"])
        ctx.register(keys["b"])
        ctx.register(keys["E"])
        if WEIGHT in classes:
            ctx.register(keys["G"])
        s_y = jnp.float32(1.0)
        s_err = jnp.float32(1.0)
        fkeys = {}
        if fused:
            fkeys = scale_ctx.fused_output_keys(skey, classes)
            ctx.register(fkeys["y"])
            s_y = ctx.scale_for(fkeys["y"])
            if "err" in fkeys:
                ctx.register(fkeys["err"])
                s_err = ctx.scale_for(fkeys["err"])
        scales = jnp.stack([
            ctx.scale_for(keys["a"]), ctx.scale_for(keys["b"]),
            ctx.scale_for(keys["E"]), ctx.scale_for(keys["G"]),
            s_y, s_err])
        token = ctx.token_for(skey)
        y, obs = _qeinsum(spec, classes, cfg, a, b, key, scales, token)
        ctx.record(keys["a"], obs[0])
        ctx.record(keys["b"], obs[1])
        if fused:
            ctx.record(fkeys["y"], obs[2])
        if _track(cfg):
            base = 3 if fused else 2
            ctx.record_health(keys["a"], obs[base:base + 2])
            ctx.record_health(keys["b"], obs[base + 2:base + 4])
            if fused:
                ctx.record_health(fkeys["y"], obs[base + 4:base + 6])
        return y
    y, _ = _qeinsum(spec, classes, cfg, a, b, key,
                    jnp.ones((N_SCALES,), jnp.float32),
                    jnp.zeros((scale_ctx.token_width(_track(cfg)),),
                              jnp.float32))
    return y


def qmatmul(a: Array, w: Array, *, key: Optional[Array] = None,
            cfg: QuantConfig = PAPER_FP8,
            site: Optional[str] = None) -> Array:
    """x @ w for x: (..., K), w: (K, N) — the layer-projection fast path."""
    if a.ndim == 2:
        return qeinsum("mk,kn->mn", a, w, key=key, cfg=cfg, site=site)
    if a.ndim == 3:
        return qeinsum("bsk,kn->bsn", a, w, key=key, cfg=cfg, site=site)
    lead = "abcdefg"[: a.ndim - 1]
    return qeinsum(f"{lead}k,kn->{lead}n", a, w, key=key, cfg=cfg, site=site)
