"""Weights made from a seed, in the plain reference's layout.

The layout is the architecture's (`bench.arch`): the frame's leaves at the
top, and one sub-tree for each layer group, whose leaves stack the group's
layers on a leading axis. Every leaf is drawn from its own key, folded from
the seed and the leaf's name (`fold`), so one leaf can be made again alone
(the architecture's `leaf`), and the whole set comes from one jitted call
(`make`). The cell runners hand the same values to the program in its own
layout (`bench.train.to_program`); the reference makes them anew after the
program's state is freed.

A reading of each leaf (a norm, a gap) goes by the leaf's tag: its name
for a leaf of the frame, `name.i` for layer i of the model, counted
across the groups in order (`flat`).
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch


def leaves(m: dict) -> list:
    """(group or None, name, shape) of every leaf, the frame's first."""
    a = arch.load(m)
    z = a.dims(m)
    out = [(None, n, tuple(s)) for n, s in a.top(z).items()]
    for g in a.groups(z):
        out += [(g.name, n, (g.depth,) + tuple(s))
                for n, s in g.leaves.items()]
    return out


def get(tree: dict, group, name: str):
    return tree[name] if group is None else tree[group][name]


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey((seed >> 32) & 0x7FFFFFFF),
                              seed & 0xFFFFFFFF)


def fold(key, name: str):
    """The key of the leaf called `name`."""
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def make_tree(key, m: dict, dtype):
    a = arch.load(m)
    out: dict = {}
    for g, n, shape in leaves(m):
        node = out if g is None else out.setdefault(g, {})
        node[n] = a.leaf(key, g, n, shape, dtype)
    return out


def make(seed: int, m: dict, dtype=jnp.float32, *, device=None):
    """The whole set in one jitted call, on the device."""
    fn = jax.jit(lambda k: make_tree(k, m, dtype))
    key = seed_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return fn(key)


def flat(m: dict, values: dict) -> dict:
    """{tag: float} of readings keyed (group or None, name): a scalar for
    a leaf of the frame, one value a layer for a group's leaf. The tags
    keep the order of `values`, a group's layers in order."""
    a = arch.load(m)
    first, i = {}, 0
    for g in a.groups(a.dims(m)):
        first[g.name] = i
        i += g.depth
    out = {}
    for (g, n), v in values.items():
        v = np.atleast_1d(np.asarray(v, np.float64))
        if g is None:
            out[n] = float(v[0])
        else:
            for j, x in enumerate(v):
                out[f"{n}.{first[g] + j}"] = float(x)
    return out
