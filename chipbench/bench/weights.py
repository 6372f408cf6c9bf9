"""Weights made from a seed, in the plain reference's layout.

Every leaf is drawn from its own key, folded from the seed and the leaf's
name, so one leaf can be made again alone (`leaf`), and the whole set comes
from one jitted call (`make`). The cell runners hand the same values to the
program in its own layout (`bench.train.to_program`); the reference makes
them anew after the program's state is freed.

Layout (L layers stacked on the leading axis; d = hidden, q = heads x head
size, k = kv heads x head size, f = intermediate, V = vocab):

    embed (V, d)  head (d, V) when untied  final_norm (d,)
    layers: ln1 (L, d)  wq (L, d, q)  bq (L, q)  wk (L, d, k)  bk (L, k)
            wv (L, d, k)  bv (L, k)  wo (L, q, d)  ln2 (L, d)
            w_gate (L, d, f)  w_up (L, d, f)  w_down (L, f, d)
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_gate", "w_up", "w_down")


def dims(m: dict) -> dict:
    """Sizes of a configuration file's model (HF key names)."""
    d = m["hidden_size"]
    h = m["num_attention_heads"]
    hd = m.get("head_dim") or d // h
    return dict(d=d, h=h, hkv=m["num_key_value_heads"], hd=hd,
                f=m["intermediate_size"], V=m["vocab_size"],
                L=m["num_hidden_layers"], tied=bool(m["tie_word_embeddings"]),
                eps=float(m["rms_norm_eps"]), theta=float(m["rope_theta"]))


def shapes(m: dict) -> dict:
    z = dims(m)
    d, L, q, k, f, V = (z["d"], z["L"], z["h"] * z["hd"], z["hkv"] * z["hd"],
                        z["f"], z["V"])
    layers = {"ln1": (L, d), "wq": (L, d, q), "bq": (L, q), "wk": (L, d, k),
              "bk": (L, k), "wv": (L, d, k), "bv": (L, k), "wo": (L, q, d),
              "ln2": (L, d), "w_gate": (L, d, f), "w_up": (L, d, f),
              "w_down": (L, f, d)}
    out = {"embed": (V, d), "final_norm": (d,), "layers": layers}
    if not z["tied"]:
        out["head"] = (d, V)
    return out


def leaf_shape(m: dict, name: str) -> tuple:
    s = shapes(m)
    return tuple(s["layers"][name] if name in LAYER_LEAVES else s[name])


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey((seed >> 32) & 0x7FFFFFFF),
                              seed & 0xFFFFFFFF)


def _std(name: str, shape) -> float:
    if name in ("embed",):
        return 0.02
    fan_in = shape[-2]
    scale = 0.5 if name in ("wo", "w_down") else 1.0
    if name == "head":
        scale = 0.5
    return scale / fan_in ** 0.5


def leaf(key, name: str, shape, dtype):
    """One leaf from the seed's key: norms about 1, biases about 0.02,
    matrices normal with a 1/sqrt(fan-in) scale, the embedding at 0.02."""
    k = jax.random.fold_in(key, zlib.crc32(name.encode()))
    if name in ("ln1", "ln2", "final_norm"):
        x = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    elif name in ("bq", "bk", "bv"):
        x = 0.02 * jax.random.normal(k, shape, jnp.float32)
    else:
        x = _std(name, shape) * jax.random.normal(k, shape, jnp.float32)
    return x.astype(dtype)


def make_tree(key, m: dict, dtype):
    s = shapes(m)
    out = {n: leaf(key, n, s[n], dtype) for n in s if n != "layers"}
    out["layers"] = {n: leaf(key, n, sh, dtype)
                     for n, sh in s["layers"].items()}
    return out


def make(seed: int, m: dict, dtype=jnp.float32, *, device=None):
    """The whole set in one jitted call, on the device."""
    fn = jax.jit(lambda k: make_tree(k, m, dtype))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)
