"""Launcher helpers: `--set` overrides, the delayed-scaling train loop, the
calibrated serving engine, and the compile-cache location."""
import os

import jax
import numpy as np
import pytest

from repro.launch import cache
from repro.launch.serve import make_engine, serve_requests
from repro.launch.train import make_train_loop, train_config
from repro.models.transformer import init_lm
from repro.serve import PagedServeConfig

TINY = ("n_layers=2", "d_model=64", "n_heads=4", "n_kv_heads=2",
        "d_ff=128", "vocab_size=128")
DELAYED = ("policy.quant.recipe=hybrid", "policy.quant.scaling=delayed")


def test_train_config_takes_set_overrides():
    cfg, nmb = train_config("qwen2-1.5b", smoke=True, overrides=TINY + DELAYED
                            + ("policy.quant.backend=pallas_interpret",
                               "policy.dist.wire=fp8_ef",
                               "n_microbatches=2"))
    q = cfg.policy.quant
    assert (q.recipe, q.scaling, q.backend) == ("hybrid", "delayed",
                                                "pallas_interpret")
    assert q.fwd_format == "e4m3"
    assert cfg.policy.dist.wire == "fp8_ef"
    assert (cfg.n_layers, cfg.d_model, nmb) == (2, 64, 2)
    assert not cfg.remat          # smoke configs train without remat


def test_train_config_refuses_serve_overrides():
    with pytest.raises(ValueError, match="serve"):
        train_config("qwen2-1.5b", smoke=True, overrides=("serve.paged=1",))


def test_delayed_train_loop_runs_without_checkpoints(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    cfg, _ = train_config("qwen2-1.5b", smoke=True, overrides=TINY + DELAYED)
    loop = make_train_loop(cfg, steps=2, batch=2, seq=16)
    assert loop.scaling is not None and len(loop.scaling.registry) > 0
    assert loop.ckpt is None
    records = []
    loop.on_metrics = lambda step, rec: records.append(rec)
    out = loop.run()
    assert out["last_step"] == 2 and out["scale_state"] is not None
    assert all(np.isfinite(r["loss"]) for r in records)
    assert list(tmp_path.iterdir()) == []   # nothing written


def test_paper_recipe_loop_has_no_scaling():
    cfg, _ = train_config("qwen2-1.5b", smoke=True, overrides=TINY)
    assert make_train_loop(cfg, steps=1, batch=2, seq=16).scaling is None


def test_engine_serves_from_calibrated_frozen_scales():
    cfg, _ = train_config("qwen2-1.5b", smoke=True, overrides=TINY + DELAYED)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    calib = [{"tokens": rng.integers(0, 128, (2, 16), dtype=np.int32)}]
    engine = make_engine(cfg, params, PagedServeConfig(
        max_batch=2, max_len=48, n_pages=8, page_size=8, chunk_size=8),
        calib_batches=calib)
    assert engine.frozen_scales and engine.frozen_formats
    assert any(v != 1.0 for v in engine.frozen_scales.values())
    prompts = [rng.integers(0, 128, n, dtype=np.int32) for n in (5, 11, 7)]
    seen = []
    out = serve_requests(engine, prompts, max_new_tokens=6,
                         on_tokens=lambda i, t: seen.append(i))
    assert sorted(out) == sorted(seen) == [0, 1, 2]
    assert all(len(t) == 6 for t in out.values())


def test_engine_without_delayed_scaling_has_no_frozen_scales():
    cfg, _ = train_config("qwen2-1.5b", smoke=True, overrides=TINY)
    params = init_lm(jax.random.PRNGKey(0), cfg)
    engine = make_engine(cfg, params, PagedServeConfig(
        max_batch=2, max_len=32, n_pages=8, page_size=8, chunk_size=8))
    assert engine.frozen_scales is None


def test_compile_cache_honours_env_var(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, "/elsewhere")
    assert cache.use_compile_cache() == "/elsewhere"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    try:
        path = cache.use_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
