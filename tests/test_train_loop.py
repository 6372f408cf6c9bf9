"""Training loop: convergence, checkpoint/restart resume, preemption."""
import numpy as np
import pytest

from repro.core.loss_scale import LossScaler
from repro.data import DataConfig, synthetic_lm_batches
from repro.models.registry import build_config
from repro.train.loop import LoopConfig, TrainLoop
from repro.train.step import make_optimizer_for


def _loop(tmp_path, total_steps, vocab=128, seed=0, metrics=None,
          checkpoints=True):
    cfg = build_config("qwen2-1.5b", smoke=True).replace(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
        vocab_size=vocab, remat=False)
    opt = make_optimizer_for(cfg, name="adam", learning_rate=3e-3,
                             scaler=LossScaler(mode="dynamic",
                                               init_scale=128.0))
    data = synthetic_lm_batches(DataConfig(
        vocab_size=vocab, seq_len=32, batch_size=8, seed=seed))
    loop = LoopConfig(total_steps=total_steps, checkpoint_every=5,
                      checkpoint_dir=(str(tmp_path / "ckpt") if checkpoints
                                      else None),
                      log_every=100, metrics_path=metrics)
    return TrainLoop(cfg, opt, data, loop, seed=seed)


def test_loss_decreases(tmp_path):
    out = _loop(tmp_path, 30).run()
    assert out["metrics"]["loss"] < np.log(128) * 0.9


def test_restart_resumes_from_checkpoint(tmp_path):
    out1 = _loop(tmp_path, 10).run()
    assert out1["last_step"] == 10
    # new loop instance, same dir: resumes at step 10, ends at 15
    lp = _loop(tmp_path, 15)
    out2 = lp.run()
    assert out2["last_step"] == 15
    assert lp.ckpt.latest_step() == 15


def test_restart_is_bitwise_continuous(tmp_path):
    """Loss at step N equals loss at step N of an uninterrupted run."""
    full = _loop(tmp_path / "a", 12).run()
    _loop(tmp_path / "b", 6).run()
    resumed = _loop(tmp_path / "b", 12).run()
    np.testing.assert_allclose(full["metrics"]["loss"],
                               resumed["metrics"]["loss"], rtol=1e-5)


def test_preemption_checkpoints_and_stops(tmp_path):
    lp = _loop(tmp_path, 100)
    lp._stop = False

    orig_fn = lp._step_fn
    calls = {"n": 0}

    def wrapped(*a):
        calls["n"] += 1
        if calls["n"] == 3:
            lp._stop = True   # simulate SIGTERM mid-run
        return orig_fn(*a)

    lp._step_fn = wrapped
    out = lp.run()
    assert out["last_step"] < 100          # stopped early
    assert lp.ckpt.latest_step() is not None   # but checkpointed first


def test_metrics_jsonl_written(tmp_path):
    mpath = str(tmp_path / "metrics.jsonl")
    _loop(tmp_path, 5, metrics=mpath).run()
    import json
    lines = [json.loads(l) for l in open(mpath)]
    assert len(lines) == 5
    assert all("loss" in l and "loss_scale" in l for l in lines)


def test_straggler_detection(tmp_path):
    import time
    lp = _loop(tmp_path, 8)
    hits = []
    lp.on_straggler = lambda step, dt: hits.append(step)
    lp.loop.straggler_factor = 1.5

    orig_fn = lp._step_fn
    calls = {"n": 0}

    def wrapped(*a):
        calls["n"] += 1
        if calls["n"] == 6:
            time.sleep(0.5)
        return orig_fn(*a)

    lp._step_fn = wrapped
    out = lp.run()
    assert out["stragglers"] >= 1


def test_loss_eval_is_the_first_step_loss(tmp_path):
    """make_loss_eval reports the loss the first train step reports, from
    the forward pass alone (same weights, batch, scales, key)."""
    import dataclasses

    import jax

    from repro.launch.train import make_train_loop, train_config
    from repro.models.transformer import init_lm
    from repro.train.step import make_loss_eval

    cfg, _ = train_config("qwen2-1.5b", smoke=True, overrides=(
        "n_layers=2", "d_model=64", "n_heads=4", "n_kv_heads=2", "d_ff=128",
        "vocab_size=128", "policy.quant.recipe=hybrid",
        "policy.quant.scaling=delayed"))
    loop = make_train_loop(cfg, steps=1, batch=2, seq=16, seed=3)
    state = dataclasses.replace(
        loop.optimizer.init(init_lm(jax.random.PRNGKey(3), cfg)),
        opt_state=None)
    batch = next(synthetic_lm_batches(DataConfig(
        vocab_size=128, seq_len=16, batch_size=2, seed=3)))
    ref = jax.jit(make_loss_eval(cfg, loop.optimizer, scaling=loop.scaling))(
        state, loop.scaling.init(), batch, loop.step_key(0))
    records = []
    loop.on_metrics = lambda step, rec: records.append(rec)
    loop.run()
    np.testing.assert_allclose(records[0]["loss"], float(ref), rtol=1e-6)


def test_no_checkpoint_dir_means_no_checkpoints(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    out = _loop(tmp_path, 6, checkpoints=False).run()
    assert out["last_step"] == 6
    assert list(tmp_path.iterdir()) == []
