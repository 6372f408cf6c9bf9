"""Serving launcher: loads (or initializes) a model and runs a batched
decoding demo through the paged continuous-batching engine (chunked
prefill + paged KV + on-device sampling). `--legacy` selects the old
fixed-slot engine (the differential-parity oracle).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke
  PYTHONPATH=src python -m repro.launch.serve --smoke --temperature 0.8 \\
      --top-p 0.95 --page-size 8 --n-pages 32
  # Fused FP8 serving (Pallas kernels, scales calibrated then frozen):
  PYTHONPATH=src python -m repro.launch.serve --smoke \\
      --set policy.quant.backend=pallas --set policy.quant.recipe=hybrid \\
      --set policy.quant.scaling=delayed

`--set key=value` takes the same overrides as the dry-run. Under
`policy.quant.scaling=delayed` the engine serves from scales calibrated on
a few synthetic batches and frozen (`calibrated_scales`). `make_engine`
and `serve_requests` are the launcher's steps as functions; `chip_smoke.py`
calls them too.
"""
import argparse
import json
from typing import Dict, List, Sequence

import jax
import numpy as np


def calibrated_scales(cfg, params, batches):
    """(frozen scales, their formats) from forward passes over `batches`
    ({"tokens": (B, S) int32} dicts) — None, None unless the policy uses
    delayed scaling."""
    if cfg.policy.quant.scaling != "delayed":
        return None, None
    from repro.scaling import calibrate, freeze_with_formats
    ds, state = calibrate(params, cfg, batches)
    return freeze_with_formats(ds, state, cfg)


def make_engine(cfg, params, serve_cfg, *, calib_batches=(), legacy=False):
    """The paged engine (or, with `legacy`, the fixed-slot one), serving
    from frozen calibrated scales when the policy asks for delayed
    scaling."""
    from repro.serve import PagedServeEngine, ServeEngine
    frozen, formats = calibrated_scales(cfg, params, calib_batches)
    engine = ServeEngine if legacy else PagedServeEngine
    return engine(cfg, params, serve_cfg, frozen_scales=frozen,
                  frozen_formats=formats)


def serve_requests(engine, prompts: Sequence[np.ndarray], *,
                   max_new_tokens: int, on_tokens=None
                   ) -> Dict[int, List[int]]:
    """Admit `prompts` as slots free up and step the engine until all are
    served. Returns {prompt index: generated tokens}; `on_tokens(index,
    tokens)` sees each request as it finishes."""
    pending = list(enumerate(prompts))
    index_of, out = {}, {}
    while pending or any(s is not None for s in engine.slots):
        while pending and engine.free_slots():
            i, p = pending.pop(0)
            index_of[engine.add_request(p, max_new_tokens=max_new_tokens)] = i
        for uid, toks in engine.step().items():
            out[index_of[uid]] = toks
            if on_tokens:
                on_tokens(index_of[uid], toks)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--legacy", action="store_true",
                    help="use the fixed-slot ServeEngine instead of the "
                         "paged engine")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--fp8-kv", action="store_true")
    ap.add_argument("--n-requests", type=int, default=6)
    # -- paged-engine knobs --------------------------------------------------
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV tokens per page")
    ap.add_argument("--n-pages", type=int, default=64,
                    help="pool pages per layer (page 0 is the trash page)")
    ap.add_argument("--chunk-size", type=int, default=32,
                    help="prompt tokens prefilled per request per step")
    ap.add_argument("--no-prefix-cache", action="store_true")
    # -- sampling ------------------------------------------------------------
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 => greedy argmax (on device either way)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stats", action="store_true",
                    help="print the engine stats() snapshot at the end")
    ap.add_argument("--set", action="append", default=[],
                    metavar="KEY=VALUE",
                    help="config override, as in the dry-run (e.g. "
                         "policy.quant.backend=pallas)")
    args = ap.parse_args()

    import dataclasses

    from repro.checkpoint import Checkpointer
    from repro.launch.cache import use_compile_cache
    from repro.launch.specs import apply_overrides, parse_overrides
    from repro.models.registry import build_config
    from repro.models.transformer import init_lm
    from repro.serve import PagedServeConfig, ServeConfig

    use_compile_cache()
    cfg = build_config(args.arch, smoke=args.smoke)
    cfg, _, _, serve_kw = apply_overrides(cfg, parse_overrides(args.set))
    if serve_kw:
        raise ValueError("serve.* overrides configure dry-run cells; use "
                         "the --page-size/--n-pages/--chunk-size flags")
    if args.fp8_kv:
        cfg = cfg.replace(policy=dataclasses.replace(
            cfg.policy, kv_cache_format="e5m2"))
    params = init_lm(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        ck = Checkpointer(args.ckpt_dir)
        if ck.latest_step() is not None:
            state_proto = jax.eval_shape(lambda p: p, params)
            params, step = ck.restore(state_proto)
            print(f"restored params at step {step}")

    if args.legacy:
        serve_cfg = ServeConfig(max_batch=args.max_batch,
                                max_len=args.max_len,
                                temperature=args.temperature, seed=args.seed)
    else:
        serve_cfg = PagedServeConfig(
            max_batch=args.max_batch, max_len=args.max_len,
            n_pages=args.n_pages, page_size=args.page_size,
            chunk_size=args.chunk_size, temperature=args.temperature,
            top_k=args.top_k, top_p=args.top_p, seed=args.seed,
            prefix_cache=not args.no_prefix_cache)
    rng = np.random.default_rng(0)
    calib = [{"tokens": rng.integers(0, cfg.vocab_size, (args.max_batch, 16),
                                     dtype=np.int32)} for _ in range(2)]
    eng = make_engine(cfg, params, serve_cfg, calib_batches=calib,
                      legacy=args.legacy)
    prompts = [rng.integers(0, cfg.vocab_size, size=rng.integers(4, 12))
               for _ in range(args.n_requests)]
    serve_requests(eng, prompts, max_new_tokens=16,
                   on_tokens=lambda i, t: print(f"request {i}: generated {t}"))
    print("all requests served")
    if args.stats:
        print(json.dumps(eng.stats(), indent=1))


if __name__ == "__main__":
    main()
