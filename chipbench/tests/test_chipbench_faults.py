"""A run of each cell at a small size on the CPU, with the look for a chip
skipped: sound, it is correct; with the timed path broken underneath, in
each way the cell can break, `correct` comes out false."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

import run
from conftest import tiny_cell


def run_tiny(kind, seconds=0.3):
    args = run.parse(["--workload", tiny_cell(kind)[0]["name"], "--seed",
                      str(2 ** 31 + 99), "--seconds", str(seconds),
                      "--trace", "0"])
    return run.run_cell(args, cell=tiny_cell(kind), require_chip=False)


def broken_train_step(monkeypatch, fault):
    import repro.train.loop as loop_mod
    make = loop_mod.make_train_step

    def make_broken(*a, **kw):
        step = make(*a, **kw)

        def broken(state, scale_state, batch, key):
            if fault == "half_batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return step(state, scale_state, half, key)
            if fault == "spurious_drop":
                # the first step reports an overflow that never happened
                # and keeps the weights, as the loss scaler would
                (new, new_ss), metrics = step(state, scale_state, batch, key)
                first = state.loss_scale.step == 0

                def pick(a, b):
                    return jax.tree_util.tree_map(
                        lambda x, y: jnp.where(first, y, x), a, b)
                new = dataclasses.replace(
                    new, master=pick(new.master, state.master),
                    opt_state=pick(new.opt_state, state.opt_state))
                metrics = dict(metrics, grads_finite=jnp.logical_and(
                    metrics["grads_finite"], jnp.logical_not(first)))
                return (new, new_ss), metrics
            _, metrics = step(state, scale_state, batch, key)
            return (state, scale_state), metrics
        return broken
    monkeypatch.setattr(loop_mod, "make_train_step", make_broken)


def test_train_sound_run_is_correct(no_cache):
    result, checks, _ = run_tiny("train")
    assert result["correct"], checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"]["train_tokens_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "spurious_drop"])
def test_train_fault_is_caught(no_cache, monkeypatch, fault):
    broken_train_step(monkeypatch, fault)
    result, checks, _ = run_tiny("train")
    assert not result["correct"], checks
    if fault == "spurious_drop":
        assert checks["drop_mismatch"]["value"] >= 1, checks


@pytest.mark.parametrize("mark", ["drop", "nan_loss"])
def test_window_counts_drops_apart_from_failures(no_cache, monkeypatch,
                                                 mark):
    """A window step the loss scaler drops is counted as `dropped`, and
    only a step whose loss is not finite as `failed`."""
    import repro.train.loop as loop_mod
    make = loop_mod.make_train_step

    def make_marked(*a, **kw):
        step = make(*a, **kw)

        def marked(state, scale_state, batch, key):
            out, metrics = step(state, scale_state, batch, key)
            i = state.loss_scale.step
            odd = jnp.logical_and(i >= 4, i % 2 == 1)
            if mark == "drop":
                metrics = dict(metrics, grads_finite=jnp.logical_and(
                    metrics["grads_finite"], jnp.logical_not(odd)))
            else:
                metrics = dict(metrics, loss=jnp.where(
                    odd, jnp.nan, metrics["loss"]))
            return out, metrics
        return marked
    monkeypatch.setattr(loop_mod, "make_train_step", make_marked)
    result, checks, _ = run_tiny("train")
    n = result["attempted"]
    assert n >= 2, result
    marked = n // 2
    if mark == "drop":
        assert result["failed"] == 0 and result["dropped"] == marked, result
        assert result["correct"], checks
    else:
        assert result["failed"] == marked and result["dropped"] == 0, result
