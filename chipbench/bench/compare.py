"""The comparison that decides `correct`.

Training takes these numbers over the first three steps; those that
the configuration file gives a limit are compared (`checks`), the others
are printed beside them:

  loss_gap         the largest relative gap of a step's loss;
  grad_gap         the worst leaf's gap between the program's and the
                   reference's norm of the first gradient, over the larger
                   of the reference's norm of that leaf and of the median
                   leaf;
  grad_gap_median  the same gap of the median leaf (steady from seed to
                   seed; half of the batch left out moves every leaf);
  grad_norm_gap    the gap of the whole gradient's norm;
  change_gap       the worst leaf's gap for the change over three steps;
  drop_mismatch    steps of the three on which the program's loss scaler
                   decided otherwise than the reference says it must
                   (`bench.reference.Overflow`: where the reference's
                   ratio lies within its band, either verdict is sound);
  dropped_steps    steps of the three that the program's loss scaler
                   dropped on overflow.

A leaf is one layer's slice of a parameter (or a whole leaf of the
frame), and each side gives its norms as {tag: float} (`bench.weights.
flat`). Leaves whose reference first gradient is under a thousandth of the
median leaf's move under Adam by round-off alone (a key bias under
softmax), so they are left out of change_gap by that rule, not by name.
"""
from __future__ import annotations

import numpy as np

TINY_GRAD = 1e-3


def leaf_gaps(p: dict, r: dict, keep=None) -> list:
    """[(gap, leaf, program norm, reference norm)] of every kept leaf,
    widest first."""
    if set(p) != set(r):
        raise ValueError(f"leaves differ: {sorted(set(p) ^ set(r))}")
    med = float(np.median(list(r.values())))
    out = []
    for k in r:
        if keep is not None and k not in keep:
            continue
        g = abs(p[k] - r[k]) / max(r[k], med)
        out.append((g if np.isfinite(g) else float("inf"), k, p[k], r[k]))
    return sorted(out, key=lambda t: -t[0])


def moved_leaves(r: dict):
    med = float(np.median(list(r.values())))
    return {k for k, v in r.items() if v >= TINY_GRAD * med}


def train_readings(prog: dict, ref: dict) -> dict:
    """The three compared numbers (and the leaves that set them)."""
    lp, lr = np.asarray(prog["losses"], float), np.asarray(ref["losses"])
    if len(lp) != len(lr):
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    if not np.isfinite(loss_gap):
        loss_gap = float("inf")
    if prog["grad"] is None:        # no step kept: nothing was learned
        prog = dict(prog, grad={k: 0.0 for k in ref["grad"]})
    grad = leaf_gaps(prog["grad"], ref["grad"])
    change = leaf_gaps(prog["change"], ref["change"],
                       keep=moved_leaves(ref["grad"]))
    gp = np.sqrt(sum(v * v for v in prog["grad"].values()))
    gr = np.sqrt(sum(v * v for v in ref["grad"].values()))
    kept = prog.get("kept", ())
    dropped = sum(1 for k in kept if not k)
    mismatch = sum(1 for k, o in zip(kept, ref.get("overflow", ()))
                   if o["verdict"] != "either" and o["kept"] != bool(k))
    if "kept" in prog and len(kept) != len(ref.get("overflow", ())):
        mismatch = float("inf")
    return {"loss_gap": loss_gap, "grad_gap": grad[0][0],
            "drop_mismatch": mismatch, "dropped_steps": dropped,
            "grad_gap_median": float(np.median([g[0] for g in grad])),
            "grad_norm_gap": float(abs(gp - gr) / gr),
            "change_gap": change[0][0],
            "widest": {"grad": grad[:3], "change": change[:3]}}


def checks(readings: dict, limits: dict) -> dict:
    """The compared numbers: those the configuration file gives a limit."""
    return {k: {"value": readings[k], "limit": v} for k, v in limits.items()}


def passed(checks: dict) -> bool:
    return all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())
