"""Qwen2 (`Qwen2ForCausalLM`): a dense decoder of identical layers.

Each layer: RMSNorm, rotary embeddings (half-split), grouped-query
attention with a bias on the q, k and v projections, RMSNorm, a SwiGLU
MLP. One layer group, `layers` (sizes: d hidden, q = heads x head size,
k = kv heads x head size, f intermediate):

    ln1 (d,)  wq (d, q)  bq (q,)  wk (d, k)  bk (k,)  wv (d, k)  bv (k,)
    wo (q, d)  ln2 (d,)  w_gate (d, f)  w_up (d, f)  w_down (f, d)

The program keeps the layers as one scanned stack (`decoder.stack_0`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import reference as ref
from bench.arch import Group
from bench.counts import causal_pairs, gemm, least_time
from bench.weights import fold

GEMMS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
SITES = tuple(f"{g}.{t}" for g in GEMMS for t in ("dY", "dA", "dW")) \
    + ("attn.dO", "attn.dP", "attn.dS")


def dims(m: dict) -> dict:
    """Sizes of a configuration file's model (HF key names)."""
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    return dict(d=d, h=h, hkv=m["num_key_value_heads"], hd=hd,
                f=m["intermediate_size"], V=m["vocab_size"],
                L=m["num_hidden_layers"], tied=bool(m["tie_word_embeddings"]),
                eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]))


def top(z: dict) -> dict:
    out = {"embed": (z["V"], z["d"]), "final_norm": (z["d"],)}
    if not z["tied"]:
        out["head"] = (z["d"], z["V"])
    return out


def groups(z: dict) -> list:
    d, q, k, f = z["d"], z["h"] * z["hd"], z["hkv"] * z["hd"], z["f"]
    leaves = {"ln1": (d,), "wq": (d, q), "bq": (q,), "wk": (d, k),
              "bk": (k,), "wv": (d, k), "bv": (k,), "wo": (q, d),
              "ln2": (d,), "w_gate": (d, f), "w_up": (d, f),
              "w_down": (f, d)}
    return [Group("layers", z["L"], leaves, layer, SITES, zero_taps,
                  tap_amax)]


def _std(name: str, shape) -> float:
    if name in ("embed",):
        return 0.02
    fan_in = shape[-2]
    scale = 0.5 if name in ("wo", "w_down") else 1.0
    if name == "head":
        scale = 0.5
    return scale / fan_in ** 0.5


def leaf(key, group, name: str, shape, dtype):
    """One leaf from the seed's key: norms about 1, biases about 0.02,
    matrices normal with a 1/sqrt(fan-in) scale, the embedding at 0.02."""
    k = fold(key, name)
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.02 * jax.random.normal(k, shape, jnp.float32)
    else:
        x = _std(name, shape) * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


# -- the f32 layer and its taps -----------------------------------------------

def zero_taps(s: int, q_block: int) -> dict:
    """The token inputs of one layer's taps."""
    t = {g: jnp.zeros((3,), jnp.float32) for g in GEMMS}
    t["attn"] = jnp.zeros((ref.attn_blocks(s, q_block), 3), jnp.float32)
    return t


def tap_amax(gt: dict):
    """One layer's taps' cotangents -> (len(SITES),) largest magnitudes."""
    return jnp.concatenate([gt[g] for g in GEMMS]
                           + [jnp.max(gt["attn"], axis=0)])


def layer(p, x, pos, z, *, bits=None, q_block=512, taps=None):
    """One decoder layer; p holds this layer's leaves (no layer axis).
    `taps` (`zero_taps`) reads the backward tensors of every product."""
    b, s, _ = x.shape
    hd = z["hd"]

    def proj(spec, a, name):
        if taps is None:
            return ref.mm(spec, a, p[name], bits)
        return ref.tap_mm(spec, a, p[name], taps[name])

    y = ref.rmsnorm(x, p["ln1"], z["eps"])
    q = proj("bsd,dn->bsn", y, "wq") + p["bq"]
    k = proj("bsd,dn->bsn", y, "wk") + p["bk"]
    v = proj("bsd,dn->bsn", y, "wv") + p["bv"]
    q = ref.rope(q.reshape(b, s, z["h"], hd), pos, z["theta"])
    k = ref.rope(k.reshape(b, s, z["hkv"], hd), pos, z["theta"])
    v = v.reshape(b, s, z["hkv"], hd)
    o = ref.attention(q, k, v, pos, pos, bits=bits, q_block=q_block,
                      tok=None if taps is None else taps["attn"])
    x = x + proj("bsn,nd->bsd", o.reshape(b, s, -1), "wo")
    y = ref.rmsnorm(x, p["ln2"], z["eps"])
    a = jax.nn.silu(proj("bsd,df->bsf", y, "w_gate")) \
        * proj("bsd,df->bsf", y, "w_up")
    return x + proj("bsf,fd->bsd", a, "w_down")


# -- the program --------------------------------------------------------------

_STACK = ("decoder", "stack_0")
PROGRAM_PATH = {
    (None, "embed"): ("embed", "table"), (None, "head"): ("embed", "head"),
    (None, "final_norm"): ("final_norm", "scale"),
    ("layers", "ln1"): _STACK + ("norm1", "scale"),
    ("layers", "wq"): _STACK + ("attn", "wq"),
    ("layers", "bq"): _STACK + ("attn", "bq"),
    ("layers", "wk"): _STACK + ("attn", "wk"),
    ("layers", "bk"): _STACK + ("attn", "bk"),
    ("layers", "wv"): _STACK + ("attn", "wv"),
    ("layers", "bv"): _STACK + ("attn", "bv"),
    ("layers", "wo"): _STACK + ("attn", "wo"),
    ("layers", "ln2"): _STACK + ("norm2", "scale"),
    ("layers", "w_gate"): _STACK + ("mlp", "gate"),
    ("layers", "w_up"): _STACK + ("mlp", "up"),
    ("layers", "w_down"): _STACK + ("mlp", "down"),
}


def program_config(m: dict):
    """The program's ModelConfig for a configuration file: the preset
    named in `program.arch`, its `program.set` overrides, and every size
    from the file."""
    from repro.launch.train import train_config
    p = m["program"]
    cfg, _ = train_config(p["arch"], overrides=p["set"])
    return cfg.replace(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["vocab_size"], head_dim=m.get("head_dim"),
        tie_embeddings=bool(m["tie_word_embeddings"]),
        norm_eps=float(m["rms_norm_eps"]), rope_theta=float(m["rope_theta"]),
        qkv_bias=True, act=m["hidden_act"])


# -- what a training step has to compute --------------------------------------
#
# As `bench.counts` counts: needed work, GEMM operands at one byte and
# outputs at two, causal attention its lower triangle.

def projections(z: dict):
    """(K, N) of the seven projections of one decoder layer."""
    d, q, k, f = z["d"], z["h"] * z["hd"], z["hkv"] * z["hd"], z["f"]
    return [(d, q), (d, k), (d, k), (q, d), (d, f), (d, f), (f, d)]


def layer_matmul_params(z: dict) -> int:
    return sum(k * n for k, n in projections(z))


def train_flops_per_token(z: dict, seq: int) -> float:
    """Model operations per trained token, forward and backward (3x the
    forward), without recomputation. Causal attention counts half."""
    dense = z["L"] * layer_matmul_params(z) + z["d"] * z["V"]
    attn = z["L"] * 4 * z["h"] * z["hd"] * causal_pairs(seq) / seq
    return 3 * (2 * dense + attn)


def train_gemm_least_time(z: dict, tokens: int, peaks: dict) -> float:
    """Least time of the fused FP8 GEMMs of one training step: each
    projection of each layer forward (M x K x N), its input gradient
    (M x N x K) and its weight gradient (K x M x N)."""
    t = 0.0
    for k, n in projections(z):
        for m_, k_, n_ in ((tokens, k, n), (tokens, n, k), (k, tokens, n)):
            t += least_time(*gemm(m_, k_, n_), peaks)
    return z["L"] * t


def train_attn_least_time(z: dict, batch: int, seq: int,
                          peaks: dict) -> float:
    """Least time of causal attention, forward plus backward (twice the
    forward's operations), over all layers."""
    h, hkv, hd = z["h"], z["hkv"], z["hd"]
    fwd_ops = 4 * batch * h * hd * causal_pairs(seq)
    qkv = batch * seq * hd * (h + 2 * hkv)
    o = batch * seq * h * hd
    fwd_bytes = qkv + 2 * o
    bwd_bytes = qkv + 2 * 2 * o + 2 * qkv
    return z["L"] * (least_time(fwd_ops, fwd_bytes, peaks)
                     + least_time(2 * fwd_ops, bwd_bytes, peaks))
