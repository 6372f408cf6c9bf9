"""The plain reference: independent of the program, equal to it where both
compute in full precision, its blockwise training step equal to plain
autodiff, and the same whether the layers come as one group or several."""
import ast
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import arch, reference, weights, workload
from conftest import CHIPBENCH, tiny_cell

QWEN2 = arch.by_name("Qwen2ForCausalLM")
INDEPENDENT = [os.path.join("bench", n) for n in (
    "arch.py", "reference.py", "weights.py", "compare.py", "counts.py",
    "workload.py", "trace.py", "peaks.py")] + sorted(
    os.path.join("models", n) for n in os.listdir(
        os.path.join(CHIPBENCH, "models")) if n.endswith(".py"))


@pytest.mark.parametrize("name", INDEPENDENT)
def test_yardstick_imports_nothing_of_the_program(name):
    """Nothing imports the program, but an architecture's program-side
    functions (`arch.PROGRAM_SIDE`) inside their bodies."""
    tree = ast.parse(open(os.path.join(CHIPBENCH, name)).read())
    allowed = {id(n) for f in tree.body if isinstance(f, ast.FunctionDef)
               and f.name in arch.PROGRAM_SIDE and name.startswith("models")
               for n in ast.walk(f)}
    nodes = [n for n in ast.walk(tree) if id(n) not in allowed]
    mods = [a.name for n in nodes if isinstance(n, ast.Import)
            for a in n.names]
    mods += [n.module or "" for n in nodes if isinstance(n, ast.ImportFrom)]
    assert not [m for m in mods if m.split(".")[0] == "repro"]


def test_int4_grid():
    x = jnp.array([-1.0, -0.5, 0.0, 0.1, 0.5, 1.0])
    q = reference.quant_int(x, 4)
    assert float(jnp.max(jnp.abs(q))) == pytest.approx(1.0)
    levels = np.unique(np.round(np.asarray(q) * 7, 4))
    assert np.allclose(levels, np.round(levels))        # multiples of 1/7


def ref_logits(m, w, toks, bits=None):
    z = QWEN2.dims(m)
    pos = jnp.arange(len(toks))[None]
    x = w["embed"][jnp.asarray(toks)[None]]
    for i in range(z["L"]):
        x = QWEN2.layer(reference.layer_params(w["layers"], i), x, pos, z,
                        bits=bits)
    hm, spec = reference.head_matrix(w)
    y = reference.rmsnorm(x[0], w["final_norm"], z["eps"])
    return reference.mm(spec, y, hm, None)


@pytest.fixture(scope="module")
def tiny():
    _, _, m, _ = tiny_cell("train")
    return m, weights.make(5, m, jnp.float32)


def test_reference_matches_the_program_at_full_precision(tiny):
    """The program's XLA forward with quantization off (bf16 compute) and
    the reference (f32) agree on logits within bf16 rounding."""
    from bench.train import to_program
    from repro.models.transformer import forward
    m, w = tiny
    cfg = QWEN2.program_config(m)
    cfg = cfg.replace(policy=cfg.policy.__class__(
        quant=cfg.policy.quant.baseline(), master_weight_dtype="float32"))
    toks = np.random.default_rng(0).integers(0, m["vocab_size"], 48)
    prog = forward(to_program(w, m, cfg), jnp.asarray(toks[None], jnp.int32),
                   cfg=cfg)[0][0].astype(jnp.float32)
    ref = ref_logits(m, w, toks)
    scale = float(jnp.std(ref))

    def rms(x):
        return float(jnp.sqrt(jnp.mean(x * x)))
    # bf16 activations through two layers: about 1 % of the logits' spread
    assert rms(prog - ref) < 0.03 * scale
    assert float(jnp.max(jnp.abs(prog - ref))) < 0.15 * scale
    low = ref_logits(m, w, toks, bits=4)
    assert rms(low - ref) > 3 * rms(prog - ref)


def test_blockwise_training_step_equals_autodiff(tiny):
    m, w = tiny
    z = QWEN2.dims(m)
    b = workload.train_batches(1, vocab=m["vocab_size"], batch=2, seq=32,
                               n=1, temperature=0.3)[0]
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))

    def loss(w):
        x = w["embed"][b["tokens"]]
        for i in range(z["L"]):
            x = QWEN2.layer(reference.layer_params(w["layers"], i), x, pos,
                            z)
        y = reference.rmsnorm(x, w["final_norm"], z["eps"])
        lg = jnp.einsum("bsd,vd->bsv", y, w["embed"],
                        precision=jax.lax.Precision.HIGHEST)
        lz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, b["labels"][..., None], -1)[..., 0]
        return jnp.mean(lz - gold)

    want_loss, want = jax.value_and_grad(loss)(w)
    tr = reference.Trainer(m, {"lr": 1e-3, "b1": 0.9, "b2": 0.999,
                               "eps": 1e-8}, rows=16, q_block=16)
    got = {}

    def on_grad(group, n, i, g):
        got[(n, i)] = np.asarray(g)

    st = tr.init_state(jax.tree_util.tree_map(jnp.array, w))
    assert tr.step(st, b, on_grad=on_grad) == pytest.approx(
        float(want_loss), rel=1e-5)
    for n in w["layers"]:
        for i in range(z["L"]):
            np.testing.assert_allclose(got[(n, i)], want["layers"][n][i],
                                       rtol=2e-3, atol=1e-6)
    np.testing.assert_allclose(got[("embed", None)], want["embed"],
                               rtol=2e-3, atol=1e-6)


def test_kept_step_equals_the_plain_step_and_a_drop_changes_nothing(tiny):
    """A step the loss scaler's verdict keeps applies the same update as a
    step with no verdict; a dropped step applies none."""
    m, w = tiny
    b = workload.train_batches(2, vocab=m["vocab_size"], batch=2, seq=32,
                               n=1, temperature=0.3)[0]
    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    out = {}
    for name, decide in (("now", None), ("held", lambda a: True),
                         ("dropped", lambda a: False)):
        tr = reference.Trainer(m, opt, rows=16, q_block=16)
        st = tr.init_state(jax.tree_util.tree_map(jnp.array, w))
        loss = tr.step(st, b, decide=decide)
        out[name] = (loss, st)
    assert out["now"][0] == out["held"][0] == out["dropped"][0]
    for n in w["layers"]:
        np.testing.assert_array_equal(out["now"][1]["w"]["layers"][n],
                                      out["held"][1]["w"]["layers"][n])
        np.testing.assert_array_equal(out["dropped"][1]["w"]["layers"][n],
                                      w["layers"][n])
    np.testing.assert_array_equal(out["now"][1]["w"]["embed"],
                                  out["held"][1]["w"]["embed"])
    assert out["held"][1]["count"] == 1 and out["dropped"][1]["count"] == 0


def test_taps_read_the_backward_tensors_and_change_no_gradient(tiny):
    m, w = tiny
    z = QWEN2.dims(m)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, z["d"]))
    pos = jnp.broadcast_to(jnp.arange(32), (2, 32))
    g = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    p = reference.layer_params(w["layers"], 0)
    plain = jax.vjp(lambda pp, xx: QWEN2.layer(pp, xx, pos, z, q_block=16),
                    p, x)[1](g)
    taps = QWEN2.zero_taps(32, 16)
    assert taps["attn"].shape == (2, 3)
    gp, gx, gt = jax.vjp(
        lambda pp, xx, tt: QWEN2.layer(pp, xx, pos, z, q_block=16, taps=tt),
        p, x, taps)[1](g)
    np.testing.assert_allclose(gx, plain[1], rtol=1e-5, atol=1e-5)
    amax = np.asarray(QWEN2.tap_amax(gt))
    site = {s: amax[i] for i, s in enumerate(QWEN2.SITES)}
    for gemm in QWEN2.GEMMS:
        # a projection's weight is used once: dW is its whole gradient
        np.testing.assert_allclose(gp[gemm], plain[0][gemm], rtol=1e-5,
                                   atol=1e-5)
        assert site[f"{gemm}.dW"] == pytest.approx(
            float(jnp.max(jnp.abs(gp[gemm]))), rel=1e-5)
    # the down projection's output joins the residual: its dY is g itself
    assert site["w_down.dY"] == pytest.approx(float(jnp.max(jnp.abs(g))))
    # attention's output error is the output projection's input gradient
    assert site["attn.dO"] == pytest.approx(site["wo.dA"], rel=1e-5)
    assert site["attn.dP"] > 0 and site["attn.dS"] > 0


SPLIT = """

import dataclasses

_one_group = groups


def groups(z):
    (g,) = _one_group(z)
    return [dataclasses.replace(g, name="first", depth=1),
            dataclasses.replace(g, name="rest", depth=g.depth - 1)]
"""


def test_layers_split_into_groups_train_as_one_group(tmp_path, monkeypatch):
    """The tiny model at three layers, as one group and as groups of one
    and two layers (the qwen2 module under another name, its `groups`
    split): the same losses, gradients, taps and updated weights, to f32
    round-off, over two steps."""
    _, _, m, _ = tiny_cell("train")
    m = dict(m, num_hidden_layers=3)
    src = os.path.join(CHIPBENCH, "models", "Qwen2ForCausalLM.py")
    (tmp_path / "SplitForCausalLM.py").write_text(
        open(src).read() + SPLIT)
    monkeypatch.setattr(arch, "MODELS", tmp_path)
    split = dict(m, architectures=["SplitForCausalLM"])
    w = weights.make(6, m, jnp.float32)
    w_split = {k: v for k, v in w.items() if k != "layers"}
    w_split["first"] = {n: x[:1] for n, x in w["layers"].items()}
    w_split["rest"] = {n: x[1:] for n, x in w["layers"].items()}
    opt = {"lr": 1e-3, "b1": 0.9, "b2": 0.999, "eps": 1e-8}
    pool = workload.train_batches(3, vocab=m["vocab_size"], batch=2, seq=32,
                                  n=2, temperature=0.3)
    first = {"layers": 0, "first": 0, "rest": 1}    # a group's first layer
    out = {}
    for name, mm, ww in (("one", m, w), ("split", split, w_split)):
        tr = reference.Trainer(mm, opt, rows=16, q_block=16)
        assert [g.name for g in tr.groups] == (
            ["layers"] if name == "one" else ["first", "rest"])
        st = tr.init_state(jax.tree_util.tree_map(jnp.array, ww))
        grads, amax = {}, []

        def on_grad(group, n, i, g):
            layer = None if i is None else first[group] + i
            grads[st["count"], n, layer] = np.asarray(g)

        def decide(a):
            amax.append(a)
            return True
        losses = [tr.step(st, b, on_grad=on_grad, decide=decide)
                  for b in pool]
        out[name] = (losses, grads, np.stack(amax), tr.site_names, st["w"])
    one, two = out["one"], out["split"]
    np.testing.assert_allclose(two[0], one[0], rtol=1e-6)
    assert two[1].keys() == one[1].keys()
    for k in one[1]:
        np.testing.assert_allclose(two[1][k], one[1][k], rtol=1e-5,
                                   atol=1e-7, err_msg=str(k))
    assert two[3] == one[3]
    np.testing.assert_allclose(two[2], one[2], rtol=1e-5, atol=1e-7)
    for n, x in one[4]["layers"].items():
        np.testing.assert_allclose(
            np.concatenate([two[4]["first"][n], two[4]["rest"][n]]), x,
            rtol=1e-6, atol=1e-7)
    for n in ("embed", "final_norm"):
        np.testing.assert_allclose(two[4][n], one[4][n], rtol=1e-6,
                                   atol=1e-7)


RECIPE = {"fmax": 100.0, "margin": 2.0, "growth": 2.0}
SCALER = {"init": 10.0, "backoff": 0.5, "min": 1.0}


@pytest.mark.parametrize("amaxes,program,verdicts,kept,scales", [
    # fresh history: the cap is fmax; then margin x the largest seen
    ([1.0, 0.9, 4.5], [True] * 3, ["keep", "keep", "drop"],
     [True, True, False], [10.0, 10.0, 10.0]),
    # an overflowing site's history takes growth x cap, and the loss
    # scale halves: the next step is kept
    ([1.0, 5.0, 4.0], [True, False, True], ["keep", "drop", "keep"],
     [True, False, True], [10.0, 10.0, 5.0]),
    # within the band the program's verdict stands, either way
    ([1.0, 2.0, 10.0], [True, False, True], ["keep", "either", "either"],
     [True, False, True], [10.0, 10.0, 5.0]),
])
def test_overflow_rule(amaxes, program, verdicts, kept, scales):
    ovf = reference.Overflow(RECIPE, SCALER, (0.5, 1.5), ["site.0"])
    got = [ovf.decide(np.array([[a]]), program_kept=k)
           for a, k in zip(amaxes, program)]
    assert got == kept
    assert [e["verdict"] for e in ovf.log] == verdicts
    assert [e["loss_scale"] for e in ovf.log] == scales
