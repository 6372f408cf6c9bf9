"""Architectures, found by name: `models/<name>.py`, where the name is
`architectures[0]` of a configuration file (the HF class name).

A module gives the plain reference its layout and its layers:

    dims(m) -> z       sizes from the configuration file; every z has the
                       frame's d (hidden), V (vocabulary), tied (the head
                       is the embedding) and eps (the final norm's)
    top(z)             {leaf: shape} of the frame: `embed` (V, d),
                       `final_norm` (d,), and `head` (d, V) when untied
    groups(z)          [Group] in order from the embedding to the head
    leaf(key, group, name, shape, dtype)
                       one leaf's value from the seed's key (group None
                       for a leaf of the frame; `weights.fold` gives a
                       name its key, so two groups' leaves of one name
                       fold in the group too)

the program its configuration and where each leaf lives in it:

    program_config(m)  the program's model configuration
    PROGRAM_PATH       {(group, leaf): path in the program's parameters}

and the per-layer metrics what a training step has to compute:

    train_flops_per_token(z, seq), train_gemm_least_time(z, tokens, peaks),
    train_attn_least_time(z, batch, seq, peaks)

Only the functions named in PROGRAM_SIDE import the program (`repro`),
inside their bodies; the rest of a module is the yardstick.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

from bench import common

MODELS = common.HERE / "models"
PROGRAM_SIDE = ("program_config",)


@dataclasses.dataclass(frozen=True)
class Group:
    """`depth` consecutive layers with one block and one set of leaves.

    `leaves` maps each leaf to its shape in one layer (the reference stacks
    a group's layers on a leading axis). `block(p, x, pos, z, *, bits,
    q_block, taps)` is one layer of the float32 reference; with `taps`
    (`zero_taps(seq, q_block)`) its backward pass reads the largest
    magnitude of each backward tensor, `tap_amax` gathers them in the
    order of `sites`."""
    name: str
    depth: int
    leaves: dict
    block: Callable
    sites: tuple
    zero_taps: Callable
    tap_amax: Callable


def by_name(name: str):
    return common.load_file(MODELS / f"{name}.py", f"chipbench_model_{name}",
                            f"architecture {name!r}")


def load(m: dict):
    """The module of a configuration file's architecture."""
    return by_name(m["architectures"][0])
