"""Training cells: the program's `TrainLoop` on the fused FP8 path.

Set-up builds one `TrainLoop` (its jitted step and its state), hands it
weights made from the seed and batches made from the seed, and drives it
through its first steps inside the same `run()` that then runs the window:
the on-metrics callback marks the window's start after the warm-up steps
and stops the loop once `seconds` have passed. While the first three steps
run, the readings that `correct` compares are taken from the state the
step returns: the first gradient from Adam's first moment after step 1,
and each leaf's change after step 3. The architecture's module
(`bench.arch`) gives the program's configuration and where each leaf lives
in the program's parameters.
"""
from __future__ import annotations

import functools
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, common, compare, weights, workload
from bench.common import say

CHECK_STEPS = 3


def batches(m: dict, mix: dict, seed: int) -> list:
    """The mix's pool of batches for a seed, over the model's vocabulary."""
    return workload.train_batches(
        seed, vocab=arch.load(m).dims(m)["V"], batch=mix["batch"],
        seq=mix["seq"], n=mix["pool"], temperature=mix["temperature"])


def to_program(w: dict, m: dict, cfg):
    """A reference-layout tree in the program's parameter layout, checked
    against the layout the program itself makes."""
    from repro.models.transformer import init_lm
    want = jax.eval_shape(lambda: init_lm(jax.random.PRNGKey(0), cfg))
    paths = arch.load(m).PROGRAM_PATH
    out: dict = {}
    for g, name, _ in weights.leaves(m):
        node = out
        path = paths[g, name]
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = weights.get(w, g, name)
    got = jax.tree_util.tree_map(lambda x: (x.shape,), out)
    exp = jax.tree_util.tree_map(lambda x: (x.shape,), want)
    if got != exp:
        raise SystemExit(f"chipbench: the program's parameter layout "
                         f"changed: want {exp}, made {got}")
    return out


def program_leaf(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def norm(x, per_layer: bool):
    """The norm of x, or of each layer's slice of a group's leaf."""
    if per_layer:
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 6))
def change_norm(init, group, name, shape, x, key, dtype):
    """Per-layer norms of a leaf's change from the value the seed gave it
    (`init`, the architecture's `leaf`; the seed's key is an argument, so
    one program serves every seed)."""
    d = x.astype(jnp.float32) - init(key, group, name, shape, dtype) \
        .astype(jnp.float32)
    return norm(d, group is not None)


class Readings:
    """Wraps the loop's jitted step for the first CHECK_STEPS calls and
    reads the compared numbers from the state each returns."""

    def __init__(self, loop, m, seed, b1, master_dtype):
        self.loop = loop
        self.inner = loop._step_fn
        self.calls = 0
        self.grad_norms = self.change_norms = None
        a = arch.load(m)
        # by name: the order in which `compare` sums the gradient's norm
        self.leaves = sorted(weights.leaves(m), key=lambda t: t[1])
        self.paths = [a.PROGRAM_PATH[g, n] for g, n, _ in self.leaves]
        self._grad = jax.jit(lambda mu: [
            norm((program_leaf(mu, path) / (1.0 - b1)).astype(jnp.float32),
                 g is not None)
            for (g, _, _), path in zip(self.leaves, self.paths)])
        self.m = m
        self.init = a.leaf
        self.key = weights.seed_key(seed)
        self.dtype = jnp.dtype(master_dtype)
        loop._step_fn = self

    def __call__(self, *args):
        out = self.inner(*args)
        carried = out[0][0] if isinstance(out[0], tuple) else out[0]
        self.calls += 1
        if self.grad_norms is None and bool(out[1]["grads_finite"]):
            # Adam's first moment after the first step the loss scaler
            # kept is (1 - b1) x that step's unscaled gradient.
            norms = self._grad(carried.opt_state["mu"])
            self.grad_norms = weights.flat(self.m, {
                (g, n): np.asarray(v)
                for (g, n, _), v in zip(self.leaves, norms)})
        if self.calls == CHECK_STEPS:
            self.change_norms = weights.flat(self.m, {
                (g, n): np.asarray(change_norm(
                    self.init, g, n, shape,
                    program_leaf(carried.master, path), self.key,
                    self.dtype))
                for (g, n, shape), path in zip(self.leaves, self.paths)})
            self.loop._step_fn = self.inner
        return out


def run(cellname, chips, m, mix, args, t_process, counter):
    import repro.train.loop as loop_mod
    from repro.launch.train import make_train_loop

    seed, seconds, tracing = args.seed, args.seconds, args.trace
    p = m["program"]
    opt = p["optimizer"]
    a = arch.load(m)
    cfg = a.program_config(m)
    bsz, seq = mix["batch"], mix["seq"]
    pool = batches(m, mix, seed)
    say(f"t+{time.perf_counter() - t_process:.1f} s: batches made")
    loop = make_train_loop(cfg, steps=1 << 40, batch=bsz, seq=seq,
                           lr=opt["lr"], seed=seed, log_every=1 << 40)
    say(f"t+{time.perf_counter() - t_process:.1f} s: train loop built")
    if loop.optimizer.master_dtype != p["master_dtype"]:
        raise SystemExit(f"chipbench: the program keeps master weights in "
                         f"{loop.optimizer.master_dtype}, the configuration "
                         f"states {p['master_dtype']}")
    stated = (p["loss_scale"]["init"], p["loss_scale"]["backoff"],
              p["delayed_scaling"]["margin"], p["delayed_scaling"]["growth"],
              p["delayed_scaling"]["history"],
              p["delayed_scaling"]["policy"])
    sc, dc = loop.optimizer.scaler, loop.scaling.config
    runs = (sc.init_scale, sc.backoff_factor, dc.margin, dc.growth,
            dc.history_len, dc.policy)
    if runs != stated:
        raise SystemExit(f"chipbench: the program scales by {runs}, the "
                         f"configuration states {stated}")
    if loop.monitor.scaler is not None:
        # The loop's health monitor reads the loss scaler's floor with an
        # eager jnp program on the first overflow it sees; compile it here,
        # so an overflow inside the window compiles nothing.
        jax.block_until_ready(loop.monitor.scaler.min_scale_at(
            np.asarray(0)))
    made = {"w": jax.jit(lambda k: to_program(weights.make_tree(
        k, m, jnp.dtype(p["master_dtype"])), m, cfg))(weights.seed_key(seed))}

    say(f"t+{time.perf_counter() - t_process:.1f} s: weights made")

    def init_from_seed(key, cfg_):
        return made.pop("w")

    def feed():
        i = 0
        while True:
            with jax.profiler.TraceAnnotation("chipbench.data", i=i):
                b = pool[i % len(pool)]
            yield b
            i += 1

    loop.data = feed()
    readings = Readings(loop, m, seed, opt["b1"], p["master_dtype"])
    warm = mix["warmup_steps"]
    st = {"losses": [], "finite": [], "scale": [], "t0": None, "steps": 0,
          "dt": [],
          "compiles": 0, "trace_on": False, "win": None}

    def on_metrics(step, rec):
        with jax.profiler.TraceAnnotation("chipbench.on_metrics", step=step):
            t = time.perf_counter()
            loss = rec.get("loss")
            st["losses"].append(loss if isinstance(loss, float)
                                else float("nan"))
            st["finite"].append(bool(rec.get("grads_finite", True)))
            st["scale"].append(rec.get("loss_scale"))
            if step == warm - 2 and tracing:
                jax.profiler.start_trace(common.trace_dir(cellname))
            if step == warm - 1:
                st["t0"] = t
                st["compiles"] = counter.n
                say(f"t+{t - t_process:.1f} s: window starts")
                if tracing:
                    st["win"] = jax.profiler.TraceAnnotation(
                        "chipbench.window")
                    st["win"].__enter__()
                return
            if st["t0"] is None:
                return
            st["steps"] += 1
            st["dt"].append(t - st.get("t1", st["t0"]))
            st["t1"] = t
            done = (st["steps"] >= mix["trace_steps"] if tracing
                    else t - st["t0"] >= seconds)
            if done:
                loop._stop = True
                st["in_window"] = counter.names[st["compiles"]:]
                st["compiles"] = counter.n - st["compiles"]
                if tracing:
                    st["win"].__exit__(None, None, None)

    loop.on_metrics = on_metrics
    saved = loop_mod.init_lm
    loop_mod.init_lm = init_from_seed
    try:
        out = loop.run()
    finally:
        loop_mod.init_lm = saved
    if tracing:
        jax.profiler.stop_trace()
    window = st["t1"] - st["t0"]
    setup_s = st["t0"] - t_process
    compiles = st["compiles"]
    peak = common.memory_peak_bytes(chips)
    say(f"losses {st['losses'][:6]} ... ({len(st['losses'])} steps); "
        f"finite {st['finite'][:8]}; loss scale {st['scale'][:8]}")
    say(f"window {window!r} s, {st['steps']} steps, compiles in window "
        f"{compiles} {st.get('in_window', [])}, memory_peak_bytes {peak}")
    say(f"window steps (s, host clock between on_metrics calls): "
        f"{[round(x, 4) for x in st['dt']]}")
    kept = st["finite"][:CHECK_STEPS]
    prog = {"losses": st["losses"][:CHECK_STEPS], "kept": kept,
            "grad": readings.grad_norms, "change": readings.change_norms}
    attempted = st["steps"]
    # A step fails when its loss is not finite: the forward pass broke. A
    # step whose scaled gradients overflow is the recipe's loss scaler at
    # work (the step is dropped and the scale backed off, as the
    # configuration states, and `drop_mismatch` holds the first steps'
    # drops to the reference's), so it is counted apart as `dropped`.
    window_losses = st["losses"][warm:warm + attempted]
    window_finite = st["finite"][warm:warm + attempted]
    failed = sum(1 for x in window_losses if not np.isfinite(x))
    dropped = [i for i, f in enumerate(window_finite) if not f]
    say(f"window: {failed} steps with a loss that is not finite; the loss "
        f"scaler dropped {len(dropped)} of {attempted} steps, at window "
        f"steps {dropped}; loss scale by step {st['scale']}")
    del out, loop, readings, made
    gc.collect()

    t_ref = time.perf_counter()
    ref = reference_readings(m, opt, pool[:CHECK_STEPS], seed,
                             p["master_dtype"], kept=kept)
    say(f"loss scaler: program kept {kept}; reference "
        f"{ref['overflow']}")
    say(f"reference: {time.perf_counter() - t_ref:.1f} s")
    rd = compare.train_readings(prog, ref)
    checks = compare.checks(rd, m["limits"]["train"])
    widest = rd.pop("widest")
    say(f"losses: program {prog['losses']}, reference {ref['losses']}")
    say(f"readings {rd}")
    say(f"widest leaves (gap, leaf, program, reference): {widest}")
    tokens = attempted * bsz * seq
    result = {"attempted": attempted, "failed": failed,
              "dropped": len(dropped), "memory_peak_bytes": peak,
              "window_s": window, "setup_s": setup_s, "tokens": tokens,
              "compiles_in_window": compiles, "widest": widest,
              "readings": rd}
    if not tracing:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / window,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    else:
        result["work"] = {"kind": "train", "arch": m["architectures"][0],
                          "z": a.dims(m), "batch": bsz, "seq": seq,
                          "steps": attempted, "tokens": tokens}
    return result, checks


def reference_readings(m, opt, batches, seed, master_dtype,
                       *, bits=None, batch_rows=None, kept=None,
                       band=None):
    """The reference's readings over the first steps: losses, first
    gradient norms, each leaf's change after the last step, and at full
    precision the loss scaler's verdict on each step (`bench.reference.
    Overflow`, over `band`, or the configuration's), with `kept`, the
    program's verdicts, taken only where the reference's own ratio lies
    within the band. `batch_rows` keeps only that many rows of each batch
    (a fault that the check has to catch)."""

    from bench.reference import Overflow, Trainer
    tr = Trainer(m, opt, bits=bits)
    mdt = jnp.dtype(master_dtype)
    w = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32),
                               weights.make(seed, m, mdt))
    st = tr.init_state(w)
    grads = {}
    p = m["program"]
    ovf = Overflow(p["delayed_scaling"], p["loss_scale"],
                   band or m["limits"]["overflow_band"], tr.site_names) \
        if tr.tapped else None

    def on_grad(group, name, i, g):
        if st["count"] == 1:
            v = float(jnp.sqrt(jnp.sum(g * g)))
            if i is None:
                grads[group, name] = v
            else:
                grads.setdefault((group, name), {})[i] = v

    losses = []
    for i, b in enumerate(batches):
        if batch_rows:
            b = {k: v[:batch_rows] for k, v in b.items()}
        decide = None if ovf is None else functools.partial(
            ovf.decide, program_kept=None if kept is None else kept[i])
        losses.append(tr.step(st, b, on_grad=on_grad, decide=decide))
    key, init = weights.seed_key(seed), arch.load(m).leaf
    change = weights.flat(m, {
        (g, n): np.asarray(change_norm(init, g, n, shape,
                                       weights.get(st["w"], g, n), key, mdt))
        for g, n, shape in weights.leaves(m)})
    grads = weights.flat(m, {
        k: [v[i] for i in range(len(v))] if isinstance(v, dict) else v
        for k, v in grads.items()})
    return {"losses": losses, "grad": grads, "change": change,
            "overflow": [] if ovf is None else ovf.log}
