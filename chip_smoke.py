#!/usr/bin/env python3
"""Smoke run of the fused FP8 train and serve path on TPU.

    python chip_smoke.py              # one chip: train, then serve
    python chip_smoke.py --chips 4    # four chips: fp8-wire data parallelism

One chip. Trains qwen2-1.5b at its published widths (d_model 1536, 12/2
heads of 128, d_ff 8960, vocab 151936) through `repro.launch.train`: the
fused Pallas GEMM and attention kernels, the hybrid E4M3/E5M2 recipe under
delayed scaling, FP16 master weights, Adam and enhanced loss scaling, on
synthetic batches from `--seed`. Depth is cut to what one chip's HBM holds
(the `reduced` line). Fails on a non-finite loss, or when the first loss is
further than FIRST_LOSS_RTOL from the same step's loss on the XLA path.
Then calibrates and freezes scales on the trained weights and serves
greedy requests through `PagedServeEngine` on the fused kernels
(`repro.launch.serve`); fails if a request comes back short.

Four chips (`--chips 4`). Runs only the data-parallel path: the same model
on a 4-way `data` mesh, a few steps with `policy.dist.wire=fp8_ef`, then
with `wire=full`, from the same weights and batches. Fails unless the batch
is split over all four devices and the two loss trajectories agree within
the convergence law of tests/test_strategy.py.

Every line but the last is smoke output, not a benchmark number. The last
line is the result as JSON. With no TPU, or when any phase fails, the
script exits non-zero and prints no result.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = "qwen2-1.5b"
FUSED = ("policy.quant.backend=pallas", "policy.quant.recipe=hybrid",
         "policy.quant.scaling=delayed")
# Depth cut: the train step's compiled memory analysis for one v5e (15.75
# GiB of HBM) at BATCH x SEQ tokens needs 14.88 GiB at 16 layers and 15.42
# GiB at 17, and does not fit at 18. 16 leaves room for the buffers outside
# the step program (the f32 weights at init, the batch, the serve pool).
FULL_LAYERS = 28
N_LAYERS = 16
BATCH, SEQ, STEPS, LR = 4, 1024, 4, 1e-3
# First loss vs the XLA path. Both run the same math on the same weights,
# batch, scales and loss scale; they differ in where the FP8 rounding
# happens (the fused epilogues also round each projection output to E4M3)
# and in the stochastic-rounding bits. See the Findings of PERF.md for how
# this bound was chosen.
FIRST_LOSS_RTOL = 1e-2
# Serving: greedy requests with prompts of a few hundred tokens.
N_REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, (200, 300), 32
# Convergence law of tests/test_strategy.py::test_wire_train_convergence_law.
WIRE_MAX_REL, WIRE_MEAN_REL = 2e-2, 5e-3
WIRE_BATCH, WIRE_STEPS = 16, 4


def say(label, value):
    print(f"[smoke] {label}: {value}", flush=True)


def fail(msg):
    raise SystemExit(f"chip_smoke: FAILED: {msg}")


def finite_losses(records, what):
    losses = [r["loss"] for r in records]
    if len(losses) != len(records) or not all(
            isinstance(x, float) and math.isfinite(x) for x in losses):
        fail(f"{what}: non-finite loss in {losses}")
    return losses


def run_loop(loop):
    """Run a TrainLoop; returns (result, per-step metric records)."""
    records = []
    loop.on_metrics = lambda step, rec: records.append(rec)
    out = loop.run()
    return out, records


def first_loss_reference(cfg, *, seed):
    """The first step's loss on the XLA path (forward only: its whole train
    step does not fit one chip at this depth)."""
    import jax

    from repro.data import DataConfig, synthetic_lm_batches
    from repro.launch.train import make_train_loop
    from repro.models.transformer import init_lm
    from repro.train.step import make_loss_eval

    loop = make_train_loop(cfg, steps=1, batch=BATCH, seq=SEQ, lr=LR,
                           seed=seed)
    opt = loop.optimizer
    # The loss reads the master weights and the loss scale only.
    state = dataclasses.replace(
        opt.init(init_lm(jax.random.PRNGKey(seed), cfg)), opt_state=None)
    batch = next(synthetic_lm_batches(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=SEQ, batch_size=BATCH,
        seed=seed)))
    loss = jax.jit(make_loss_eval(cfg, opt, scaling=loop.scaling))(
        state, loop.scaling.init(), batch, loop.step_key(0))
    return float(loss)


def train_phase(seed):
    import jax

    from repro.launch.train import make_train_loop, train_config

    depth = (f"n_layers={N_LAYERS}",)
    cfg, _ = train_config(ARCH, overrides=FUSED + depth)
    say("reduced", json.dumps({"n_layers": [FULL_LAYERS, N_LAYERS],
                               "why": "16 GB of HBM on one v5e"}))
    say("model", f"{ARCH} d_model={cfg.d_model} heads={cfg.n_heads}/"
        f"{cfg.n_kv_heads} head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} layers={cfg.n_layers}; "
        f"batch={BATCH}x{SEQ} tokens, steps={STEPS}")
    xla_cfg, _ = train_config(ARCH, overrides=FUSED + depth
                              + ("policy.quant.backend=xla",))
    t0 = time.perf_counter()
    ref = first_loss_reference(xla_cfg, seed=seed)
    say("xla first loss (forward only, incl. compile "
        f"{time.perf_counter() - t0:.1f} s)", ref)

    loop = make_train_loop(cfg, steps=STEPS, batch=BATCH, seq=SEQ, lr=LR,
                           seed=seed)
    out, records = run_loop(loop)
    losses = finite_losses(records, "train")
    times = [r["step_time_s"] for r in records]
    say("train losses", losses)
    say("train loss scales", [r.get("loss_scale") for r in records])
    say("train step 0 s (compile + run)", times[0])
    say("train step s after compile", times[1:])
    rel = abs(losses[0] - ref) / abs(ref)
    say("first loss rel diff vs xla", f"{rel} (bound {FIRST_LOSS_RTOL})")
    if rel > FIRST_LOSS_RTOL:
        fail(f"first loss {losses[0]} vs xla {ref}: rel {rel}")
    params = loop.optimizer.compute_params(out["state"])
    jax.block_until_ready(params)
    return cfg, params


def serve_phase(cfg, params, seed):
    import numpy as np

    from repro.launch.serve import make_engine, serve_requests
    from repro.serve import PagedServeConfig

    rng = np.random.default_rng(seed)
    max_len = PROMPT_LEN[1] + NEW_TOKENS
    calib = [{"tokens": rng.integers(0, cfg.vocab_size,
                                     (N_REQUESTS, PROMPT_LEN[1]),
                                     dtype=np.int32)} for _ in range(2)]
    page = 16
    serve_cfg = PagedServeConfig(
        max_batch=N_REQUESTS, max_len=max_len, page_size=page,
        n_pages=N_REQUESTS * -(-max_len // page) + 1, chunk_size=128,
        temperature=0.0, seed=seed)
    t0 = time.perf_counter()
    engine = make_engine(cfg, params, serve_cfg, calib_batches=calib)
    say("serve calibrate + freeze s (incl. compile)",
        time.perf_counter() - t0)
    prompts = [rng.integers(0, cfg.vocab_size, int(n), dtype=np.int32)
               for n in rng.integers(*PROMPT_LEN, N_REQUESTS)]
    t0 = time.perf_counter()
    out = serve_requests(engine, prompts, max_new_tokens=NEW_TOKENS)
    say("serve s for all requests (incl. compile)",
        time.perf_counter() - t0)
    say("serve prompt lengths", [len(p) for p in prompts])
    say("serve generated lengths", [len(out.get(i, ()))
                                    for i in range(N_REQUESTS)])
    for i in range(N_REQUESTS):
        toks = out.get(i, [])
        if len(toks) != NEW_TOKENS:
            fail(f"request {i} returned {len(toks)} of {NEW_TOKENS} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            fail(f"request {i} returned out-of-vocab tokens {toks}")
    say("serve first request tokens", out[0])


def wire_phase(seed, n_chips):
    import numpy as np

    from repro.distributed.strategy import ParallelPlan
    from repro.launch.mesh import make_mesh
    from repro.launch.train import make_train_loop, train_config

    mesh = make_mesh((n_chips,), ("data",))
    runs = {}
    for wire in ("fp8_ef", "full"):
        cfg, _ = train_config(ARCH, overrides=FUSED + (
            f"n_layers={N_LAYERS}", f"policy.dist.wire={wire}"))
        plan = ParallelPlan.build(mesh, cfg.policy.dist)
        loop = make_train_loop(cfg, steps=WIRE_STEPS, batch=WIRE_BATCH,
                               seq=SEQ, lr=LR, seed=seed, plan=plan)
        if wire == "fp8_ef":
            say("reduced", json.dumps({"n_layers": [FULL_LAYERS, N_LAYERS],
                                       "why": "the one-chip cut, kept"}))
            say("plan", json.dumps(plan.describe()))
            if not plan.compresses:
                fail("wire=fp8_ef did not select the compressed reduction")
            probe = loop.place_batch(
                {"tokens": np.zeros((WIRE_BATCH, SEQ), np.int32)})
            shards = probe["tokens"].addressable_shards
            say("batch shards", [(str(s.device), s.data.shape)
                                 for s in shards])
            if len({s.device for s in shards}) != n_chips or any(
                    s.data.shape[0] != WIRE_BATCH // n_chips
                    for s in shards):
                fail("the batch is not split over all devices")
        records = run_loop(loop)[1]   # drop the run's state before the next
        runs[wire] = finite_losses(records, f"wire={wire}")
        say(f"wire={wire} losses", runs[wire])
        say(f"wire={wire} step s", [r["step_time_s"] for r in records])
        del loop
    rels = [abs(w - f) / abs(f) for w, f in zip(runs["fp8_ef"], runs["full"])]
    say("wire rel diffs", rels)
    if max(rels) >= WIRE_MAX_REL or sum(rels) / len(rels) >= WIRE_MEAN_REL:
        fail(f"fp8_ef vs full losses outside the convergence law: {rels}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-chip data-parallel path")
    args = ap.parse_args()

    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        fail(f"no repro package under {src}")
    sys.path.insert(0, src)
    from repro.launch.cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX sees {dev.platform} devices")
    if len(devices) < args.chips:
        fail(f"{args.chips} chips asked, {len(devices)} present")
    say("device", f"{dev.platform} {dev.device_kind} x{len(devices)}")
    say("jax", f"{jax.__version__}; compile cache {cache}")

    t0 = time.perf_counter()
    if args.chips == 1:
        cfg, params = train_phase(args.seed)
        serve_phase(cfg, params, args.seed)
    else:
        wire_phase(args.seed, args.chips)
    stats = dev.memory_stats() or {}
    say("peak_bytes_in_use (device 0)", stats.get("peak_bytes_in_use"))
    say("wall s", time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
