"""Input specifications for every (architecture x shape) dry-run cell.

ShapeDtypeStruct stand-ins only — weak-type-correct, shardable, no device
allocation. Each cell yields (fn, args, in_shardings, out_shardings, meta).

Shape cells (assigned):
  train_4k     seq=4096   global_batch=256   -> train_step
  prefill_32k  seq=32768  global_batch=32    -> serve_prefill
  decode_32k   seq=32768  global_batch=128   -> serve_decode (1 new token,
                                               KV cache of 32768)
  long_500k    seq=524288 global_batch=1     -> serve_decode; ONLY for
               sub-quadratic archs (ssm/hybrid) — full-attention archs are
               skipped per the assignment (see DESIGN.md §7).

Modality stubs per the assignment: llava gets precomputed patch embeddings,
seamless gets precomputed frame embeddings.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import replicated
from repro.distributed.strategy import ParallelPlan
from repro.models.config import ModelConfig
from repro.models.registry import build_config
from repro.models.transformer import (init_lm, init_paged_stack_state,
                                      init_stack_state)
from repro.train.step import (make_optimizer_for, make_serve_chunk,
                              make_serve_decode, make_serve_prefill,
                              make_train_step)

SHAPES = {
    "train_4k": dict(seq=4096, batch=256, mode="train"),
    "prefill_32k": dict(seq=32768, batch=32, mode="prefill"),
    "decode_32k": dict(seq=32768, batch=128, mode="decode"),
    "long_500k": dict(seq=524288, batch=1, mode="decode"),
}

# Archs from the assignment pool (paper workloads excluded from the grid).
GRID_ARCHS = [
    "internlm2-20b", "mistral-large-123b", "qwen2-1.5b", "codeqwen1.5-7b",
    "dbrx-132b", "moonshot-v1-16b-a3b", "llava-next-34b", "xlstm-125m",
    "recurrentgemma-9b", "seamless-m4t-large-v2",
]

SUBQUADRATIC = ("ssm", "hybrid")


def parse_overrides(pairs) -> Dict[str, Any]:
    """`--set key=value` strings -> build_cell overrides dict (shared by the
    dryrun and perf CLIs; int/float/bool coercion, strings otherwise)."""
    overrides: Dict[str, Any] = {}
    for kv in pairs:
        k, v = kv.split("=", 1)
        for cast in (int, float):
            try:
                v = cast(v)
                break
            except ValueError:
                continue
        if v == "true":
            v = True
        elif v == "false":
            v = False
        overrides[k] = v
    return overrides


def cell_supported(arch: str, shape: str) -> Tuple[bool, str]:
    cfg = build_config(arch, smoke=True)   # family lookup only
    if shape == "long_500k" and cfg.family not in SUBQUADRATIC:
        return False, ("full-attention arch: 512k dense-KV decode is "
                       "unbounded by construction (DESIGN.md §7)")
    return True, ""


def _token_batch(cfg: ModelConfig, batch: int, seq: int,
                 *, labels: bool) -> Dict[str, Any]:
    """ShapeDtypeStructs for one training/prefill batch."""
    sds = jax.ShapeDtypeStruct
    out: Dict[str, Any] = {}
    text_len = seq
    if cfg.frontend == "patch_stub":
        text_len = seq - cfg.n_frontend_tokens
        out["extra_embeds"] = sds((batch, cfg.n_frontend_tokens, cfg.d_model),
                                  jnp.bfloat16)
    out["tokens"] = sds((batch, text_len), jnp.int32)
    if labels:
        out["labels"] = sds((batch, text_len), jnp.int32)
        out["loss_mask"] = sds((batch, text_len), jnp.float32)
    if cfg.is_encoder_decoder:
        out["enc_inputs"] = sds((batch, seq, cfg.d_model), jnp.bfloat16)
    return out


def _shaped(fn, *args):
    return jax.eval_shape(fn, *args)


def pick_microbatches(cfg: ModelConfig, batch: int, seq: int, dp: int,
                      *, residual_budget: float = 2.0e9) -> int:
    """Gradient-accumulation factor sized so the per-device layer-residual
    footprint (L x B_mb/dp x S x D x 2 bytes, the scan bwd carry) stays
    under `residual_budget`. Powers of two, capped so B_mb >= dp.
    `dp` is the total data-parallel degree (ParallelPlan.dp_size)."""
    total_layers = cfg.n_layers + cfg.n_encoder_layers
    per_mb = lambda n: (total_layers * (batch / (dp * n)) * seq
                        * cfg.d_model * 2.0)
    n = 1
    while per_mb(n) > residual_budget and batch // (n * 2) >= dp:
        n *= 2
    return n


@functools.lru_cache(maxsize=None)
def _cfg_for_cell(arch: str, shape: str) -> ModelConfig:
    cfg = build_config(arch)
    seq = SHAPES[shape]["seq"]
    return cfg.replace(max_seq_len=max(cfg.max_seq_len, seq))


def apply_overrides(cfg: ModelConfig, overrides: Optional[Dict[str, Any]]
                     ) -> Tuple[ModelConfig, Any, Any, Dict[str, Any]]:
    """Apply dotted-key cell overrides to a ModelConfig.

    Returns (cfg, force_n_microbatches, force_sequence_parallel,
    serve_kwargs).  'policy.quant.*' / 'policy.dist.*' / 'policy.*' keys
    replace into the nested policy dataclasses; 'serve.*' keys are
    returned for the serving-step builder; everything else replaces
    directly on the ModelConfig.
    """
    force_nmb = None
    force_sp = None
    serve_kw: Dict[str, Any] = {}
    if overrides:
        overrides = dict(overrides)
        force_nmb = overrides.pop("n_microbatches", None)
        force_sp = overrides.pop("force_sequence_parallel", None)
        serve_kw = {k.split(".", 1)[1]: v for k, v in overrides.items()
                    if k.startswith("serve.")}
        pol_kw = {k.split(".", 1)[1]: v for k, v in overrides.items()
                  if k.startswith("policy.")}
        cfg_kw = {k: v for k, v in overrides.items()
                  if not k.startswith(("policy.", "serve."))}
        if pol_kw:
            qkw = {k.split(".", 1)[1]: v for k, v in pol_kw.items()
                   if k.startswith("quant.")}
            dkw = {k.split(".", 1)[1]: v for k, v in pol_kw.items()
                   if k.startswith("dist.")}
            pol_kw = {k: v for k, v in pol_kw.items()
                      if not k.startswith(("quant.", "dist."))}
            pol = cfg.policy
            if qkw:
                pol = dataclasses.replace(pol, quant=dataclasses.replace(
                    pol.quant, **qkw))
            if dkw:
                pol = dataclasses.replace(pol, dist=dataclasses.replace(
                    pol.dist, **dkw))
            cfg = cfg.replace(policy=dataclasses.replace(pol, **pol_kw))
        if cfg_kw:
            cfg = cfg.replace(**cfg_kw)
    return cfg, force_nmb, force_sp, serve_kw


def cell_config(arch: str, shape: str, *,
                overrides: Optional[Dict[str, Any]] = None) -> ModelConfig:
    """The ModelConfig a cell is built with (shape-adjusted, overrides
    applied) — the same resolution path `build_cell` takes, without
    building anything.  Used by `repro.analysis.precision_lint` to
    classify jaxpr findings against the cell's actual knobs."""
    cfg = _cfg_for_cell(arch, shape)
    return apply_overrides(cfg, overrides)[0]


def build_cell(arch: str, shape: str, mesh, *,
               unroll_layers: bool = False,
               overrides: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Returns dict(fn, args, in_shardings, out_shardings, meta).

    unroll_layers=True disables scan-over-layers so cost_analysis counts
    every layer (roofline lowering); the default scan lowering is used for
    the memory-fit proof and the multi-pod pass.

    overrides: perf-iteration knobs applied to the ModelConfig; keys starting
    with 'policy.' modify the PrecisionPolicy (e.g. {'policy.kv_cache_format':
    'e5m2', 'attn_chunk_size': 512, 'capacity_factor': 1.0}). Keys starting
    with 'serve.' select/configure the paged serving step for decode cells
    ({'serve.paged': True, 'serve.page_size': 64, 'serve.chunk_size': 1,
    'serve.n_pages': N}) — KV memory then scales with the page pool, not
    batch * max_len.
    """
    ok, why = cell_supported(arch, shape)
    if not ok:
        raise ValueError(f"cell ({arch}, {shape}) skipped: {why}")
    info = SHAPES[shape]
    seq, batch, mode = info["seq"], info["batch"], info["mode"]
    cfg = _cfg_for_cell(arch, shape)
    cfg, force_nmb, force_sp, serve_kw = apply_overrides(cfg, overrides)
    if unroll_layers:
        cfg = cfg.replace(scan_layers=False)
    # The plan owns every sharding decision from here on: dp/zero1/tp axes,
    # PartitionSpecs, wire-format collectives.
    plan = ParallelPlan.build(mesh, cfg.policy.dist)

    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_s = _shaped(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    if mode != "train":
        # Production serving stores bf16 weights (FP8 at the qeinsum level).
        params_s = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, jnp.bfloat16 if jnp.issubdtype(s.dtype, jnp.floating)
                else s.dtype), params_s)
    pspecs = plan.param_specs(params_s)

    meta = dict(arch=arch, shape=shape, mode=mode, n_layers=cfg.n_layers,
                n_encoder_layers=cfg.n_encoder_layers,
                d_model=cfg.d_model, seq=seq, batch=batch,
                family=cfg.family, scan_layers=cfg.scan_layers,
                n_experts=cfg.n_experts,
                experts_per_token=cfg.experts_per_token,
                d_ff=cfg.d_ff, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim,
                vocab=cfg.padded_vocab_size,
                pattern=",".join(cfg.pattern()),
                window=cfg.window,
                dist=plan.describe())

    if mode == "train":
        opt = make_optimizer_for(cfg)
        state_s = _shaped(opt.init, params_s)
        state_specs_tree = plan.train_state_specs(state_s)
        batch_s = _token_batch(cfg, batch, seq, labels=True)
        bspecs = plan.batch_specs(batch_s)
        # Roofline (unrolled) lowering: single microbatch so per-step FLOPs
        # are fully visible to cost_analysis (a microbatch scan body would be
        # counted once); memory fit is proven by the scan lowering instead.
        n_mb = 1 if unroll_layers \
            else pick_microbatches(cfg, batch, seq, plan.dp_size)
        if force_nmb is not None:
            n_mb = force_nmb
        meta["n_microbatches"] = n_mb
        # Sequence parallelism: shards the residual stream + norm/GEMM f32
        # transients over 'model'; always on for train when a model axis
        # exists (pure win: memory / TP-degree, small extra gather volume).
        if plan.tp_size > 1 and seq % plan.tp_size == 0 \
                and force_sp is not False:
            cfg = cfg.replace(sequence_parallel=True)
            meta["sequence_parallel"] = True
        # Delayed per-tensor scaling at production shapes: when the cell's
        # QuantConfig asks for it (e.g. overrides {'policy.quant.scaling':
        # 'delayed', 'policy.quant.recipe': 'hybrid'}), discover the site
        # registry from one abstract trace and thread a ScaleState through
        # the step — the dry-run then proves the hybrid delayed recipe
        # lowers, shards, and fits alongside everything else.
        scaling = None
        meta["recipe"] = cfg.policy.quant.recipe
        meta["scaling"] = cfg.policy.quant.scaling
        meta["fuse_epilogue"] = cfg.policy.quant.fuse_epilogue
        meta["fuse_attention"] = cfg.policy.quant.fuse_attention
        # Precision-health counters (obs subsystem): recorded so dry-run
        # artifacts document whether the cell's step carries the per-site
        # saturation/flush observations (overridable per cell via
        # {'policy.quant.track_health': True}).
        meta["track_health"] = cfg.policy.quant.track_health
        if cfg.policy.quant.fuse_attention:
            # Streamed-KV knobs (results are bit-invariant to them; they
            # set the kernel's VMEM working set per grid step). Unset
            # knobs resolve through the autotuner winners table exactly
            # as the kernel op will at trace time, so the dry-run artifact
            # records the schedule the cell actually runs.
            from repro.kernels import autotune as _autotune
            from repro.kernels.fp8_attention import ref as _attn_ref
            _bq, _bkv = _autotune.resolve_attn_blocks(
                "fwd", "causal", seq, seq, cfg.resolved_head_dim,
                block_q=cfg.policy.quant.attn_block_q,
                block_kv=cfg.policy.quant.attn_block_kv,
                autotune=cfg.policy.quant.autotune)
            meta["attn_block_q"] = _bq
            meta["attn_block_kv"] = _attn_ref.resolve_block_kv(seq, _bkv)
            meta["autotune"] = cfg.policy.quant.autotune
            if cfg.policy.quant.attn_block_q is not None \
                    or cfg.policy.quant.attn_block_kv is not None:
                # Explicit knobs are checked against the analytic VMEM
                # model here, at spec-build time, so an oversized config
                # fails with the modeled footprint instead of an opaque
                # Mosaic allocation error hours into a launch.
                from repro.analysis import vmem as _vmem
                _vmem.check_attn_blocks(
                    meta["attn_block_q"], meta["attn_block_kv"],
                    cfg.resolved_head_dim,
                    label=f"explicit attention blocks for cell "
                          f"({arch}, {shape})")
        if cfg.policy.quant.scaling == "delayed":
            from repro.scaling.calibrate import discover_lm_sites
            from repro.scaling.state import DelayedScaling
            registry = discover_lm_sites(cfg, params_s, batch_s)
            scaling = DelayedScaling(registry, qcfg=cfg.policy.quant)
            meta["scale_rows"] = len(registry)
        fn = make_train_step(cfg, opt, n_microbatches=n_mb,
                             scaling=scaling, plan=plan)
        wire = plan.compresses
        if wire:
            # The fp8-on-the-wire step threads the error-feedback residual
            # pytree (stacked per-wire-device, sharded over the wire axis).
            meta["wire_bytes"] = plan.wire_bytes(params_s)
            err_s = plan.wire_state_struct(state_s.master)
            espec = plan.wire_state_specs(err_s)
        if scaling is not None:
            sstate_s = _shaped(scaling.init)
            if wire:
                metrics_s = _shaped(fn, state_s, sstate_s, err_s, batch_s,
                                    jax.random.PRNGKey(0))[1]
                return dict(
                    fn=fn, args=(state_s, sstate_s, err_s, batch_s, key_s),
                    in_shardings=(state_specs_tree, replicated(sstate_s),
                                  espec, bspecs, P()),
                    out_shardings=((state_specs_tree, replicated(sstate_s),
                                    espec), replicated(metrics_s)),
                    donate_argnums=(0, 1, 2),
                    meta=meta)
            metrics_s = _shaped(fn, state_s, sstate_s, batch_s,
                                jax.random.PRNGKey(0))[1]
            return dict(
                fn=fn, args=(state_s, sstate_s, batch_s, key_s),
                in_shardings=(state_specs_tree, replicated(sstate_s),
                              bspecs, P()),
                out_shardings=((state_specs_tree, replicated(sstate_s)),
                               replicated(metrics_s)),
                donate_argnums=(0, 1),
                meta=meta)
        if wire:
            metrics_s = _shaped(fn, state_s, err_s, batch_s,
                                jax.random.PRNGKey(0))[1]
            return dict(
                fn=fn, args=(state_s, err_s, batch_s, key_s),
                in_shardings=(state_specs_tree, espec, bspecs, P()),
                out_shardings=((state_specs_tree, espec),
                               replicated(metrics_s)),
                donate_argnums=(0, 1),
                meta=meta)
        metrics_s = _shaped(fn, state_s, batch_s, jax.random.PRNGKey(0))[1]
        return dict(
            fn=fn, args=(state_s, batch_s, key_s),
            in_shardings=(state_specs_tree, bspecs, P()),
            out_shardings=(state_specs_tree, replicated(metrics_s)),
            donate_argnums=(0,),   # optimizer state updated in place
            meta=meta)

    # ---- serving cells ------------------------------------------------------
    if mode == "prefill" and plan.tp_size > 1 and seq % plan.tp_size == 0:
        cfg = cfg.replace(sequence_parallel=True)
        meta["sequence_parallel"] = True
    cache_len = min(seq, 32768) if shape != "long_500k" else cfg.window or 1
    meta["recipe"] = cfg.policy.quant.recipe
    meta["kv_cache_format"] = cfg.policy.kv_cache_format
    meta["fuse_attention"] = cfg.policy.quant.fuse_attention
    paged = bool(serve_kw.get("paged"))
    if mode == "prefill":
        states_s = _shaped(
            lambda: init_stack_state(cfg, batch, max_len=seq,
                                     n_layers=cfg.n_layers))
        batch_s = _token_batch(cfg, batch, seq, labels=False)
        fn = make_serve_prefill(cfg)
    elif paged:
        # Paged-KV decode cell: the PagedServeEngine step minus sampling —
        # block-table gather over a flat slot pool, per-row [start, n_valid]
        # chunk bounds. KV memory scales with the pool (n_pages * page_size
        # slots), not batch * max_len; chunk_size > 1 dry-runs the chunked-
        # prefill shape of the same program.
        if cfg.is_encoder_decoder:
            raise ValueError("paged serving cells do not support "
                             "encoder-decoder archs")
        psize = int(serve_kw.get("page_size", 64))
        tchunk = int(serve_kw.get("chunk_size", 1))
        n_pages = int(serve_kw.get("n_pages",
                                   batch * (cache_len // psize) + 1))
        capacity = -(-cache_len // psize) * psize
        n_slots = n_pages * psize
        states_s = _shaped(
            lambda: init_paged_stack_state(cfg, n_slots,
                                           n_layers=cfg.n_layers))
        sds = jax.ShapeDtypeStruct
        batch_s = {"tokens": sds((batch, tchunk), jnp.int32),
                   "positions": sds((batch, tchunk), jnp.int32),
                   "write_slots": sds((batch, tchunk), jnp.int32),
                   "read_slots": sds((batch, capacity), jnp.int32),
                   "slot_pos": sds((batch, capacity), jnp.int32),
                   "chunk_pos": sds((batch, 2), jnp.int32),
                   "last_row": sds((batch,), jnp.int32)}
        fn = make_serve_chunk(cfg)
        meta["paged"] = dict(page_size=psize, chunk_size=tchunk,
                             n_pages=n_pages, capacity=capacity,
                             kv_pool_tokens=n_slots)
    else:  # decode
        states_s = _shaped(
            lambda: init_stack_state(cfg, batch, max_len=cache_len,
                                     n_layers=cfg.n_layers))
        batch_s = {"tokens": jax.ShapeDtypeStruct((batch, 1), jnp.int32),
                   "positions": jax.ShapeDtypeStruct((batch, 1), jnp.int32)}
        if cfg.is_encoder_decoder:
            batch_s["enc_out"] = jax.ShapeDtypeStruct(
                (batch, 4096, cfg.d_model), jnp.bfloat16)
        fn = make_serve_decode(cfg)

    sspecs = plan.serve_state_specs(states_s, paged=paged)
    bspecs = plan.batch_specs(batch_s)
    logits_spec = plan.logits_spec(batch, cfg.padded_vocab_size)
    # Serving params are ZeRO-sharded over 'data' on top of TP (FSDP-style
    # per-layer gather) — a 123B bf16 model does not fit at TP-16 alone.
    serve_pspecs = plan.master_specs(params_s, pspecs)
    return dict(
        fn=fn, args=(params_s, batch_s, states_s),
        in_shardings=(serve_pspecs, bspecs, sspecs),
        out_shardings=(logits_spec, sspecs),
        donate_argnums=(2,),   # caches/states updated in place
        meta=meta)
