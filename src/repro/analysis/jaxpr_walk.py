"""One canonical nested-jaxpr traversal.

Every jaxpr assertion in the repo (tests and lint passes alike) walks
nested jaxprs the same way: descend into every jaxpr found in an eqn's
params — scan/while/cond bodies, custom_vjp/custom_jvp branches,
shard_map bodies, and pallas_call kernel jaxprs — tracking whether the
current eqn sits inside a Pallas kernel body (dots inside a kernel are
the kernel's own MXU tiles, not XLA fallbacks).

The traversal is duck-typed (`hasattr(x, "eqns") / hasattr(x, "jaxpr")`):
a param holding any jaxpr-like object is descended into.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict, Iterator, List, Tuple

import jax

from jax.extend import core as _jcore

JAXPR_TYPES = (_jcore.Jaxpr, _jcore.ClosedJaxpr)

# Real 8-bit float dtypes (never uint8 stand-ins) — the payload dtypes the
# f8-payload lint pass accepts as proof a tensor is actually FP8.
F8_DTYPE_NAMES = frozenset((
    "float8_e5m2", "float8_e4m3fn", "float8_e4m3", "float8_e4m3b11_fnuz",
    "float8_e5m2fnuz", "float8_e4m3fnuz",
))


def as_jaxpr(jaxpr):
    """Accept a Jaxpr, a ClosedJaxpr, or the object `jax.make_jaxpr`
    returns; hand back the underlying Jaxpr."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def subjaxprs(eqn) -> Iterator:
    """Every jaxpr nested in `eqn.params` (ClosedJaxprs unwrapped)."""
    for v in eqn.params.values():
        for sub in jax.tree_util.tree_leaves(
                v, is_leaf=lambda x: hasattr(x, "eqns")
                or hasattr(x, "jaxpr")):
            if hasattr(sub, "jaxpr"):
                yield sub.jaxpr
            elif hasattr(sub, "eqns"):
                yield sub


def iter_jaxprs(jaxpr, *, inside_pallas: bool = False) -> Iterator:
    """Yield (jaxpr, inside_pallas) for `jaxpr` and every nested jaxpr,
    outer first.  `inside_pallas` is True for jaxprs that are (or sit
    inside) a pallas_call kernel body."""
    jaxpr = as_jaxpr(jaxpr)
    yield jaxpr, inside_pallas
    for eqn in jaxpr.eqns:
        inner = inside_pallas or eqn.primitive.name == "pallas_call"
        for sub in subjaxprs(eqn):
            yield from iter_jaxprs(sub, inside_pallas=inner)


def iter_eqns(jaxpr, *, inside_pallas: bool = False) -> Iterator[Tuple]:
    """Yield (eqn, inside_pallas) over `jaxpr` and every nested jaxpr."""
    for jx, inside in iter_jaxprs(jaxpr, inside_pallas=inside_pallas):
        for eqn in jx.eqns:
            yield eqn, inside


def walk_eqns(jaxpr) -> Iterator:
    """Flat eqn generator over `jaxpr` and every nested jaxpr."""
    for eqn, _ in iter_eqns(jaxpr):
        yield eqn


def all_eqns(jaxpr) -> List:
    """Flat eqn list over `jaxpr` and every nested jaxpr."""
    return [eqn for eqn, _ in iter_eqns(jaxpr)]


def count_prims(jaxpr, inside_pallas: bool = False,
                counts: Dict[str, int] = None) -> Dict[str, int]:
    """Count pallas_call eqns and dot_generals OUTSIDE pallas kernel
    bodies: {"pallas": n, "outside_dot": n}.  The fused-lowering law
    (`pallas == expected`, `outside_dot == 0`) is asserted through this
    single function by tests and the precision lint alike."""
    if counts is None:
        counts = {"pallas": 0, "outside_dot": 0}
    for eqn, inside in iter_eqns(jaxpr, inside_pallas=inside_pallas):
        name = eqn.primitive.name
        if name == "pallas_call":
            counts["pallas"] += 1
        elif name == "dot_general" and not inside:
            counts["outside_dot"] += 1
    return counts


# ------------------------------------------------------------------ dtypes
def is_f8(dtype) -> bool:
    """True for a REAL 8-bit float dtype (uint8 bit-carriers don't count).
    Accepts dtype instances and scalar types alike."""
    try:
        import numpy as np
        return str(np.dtype(dtype)) in F8_DTYPE_NAMES
    except TypeError:
        return str(dtype) in F8_DTYPE_NAMES


def eqn_avals(eqn) -> Iterator:
    """Shaped avals of an eqn's invars + outvars (Literals included)."""
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def touches_f8(eqn) -> bool:
    """True when any operand or output of `eqn` is a real f8 dtype."""
    return any(is_f8(a.dtype) for a in eqn_avals(eqn))


def dtype_census(jaxpr) -> Counter:
    """Counter of outvar dtype names over every eqn, nested included —
    the recipe checks read fp8-format presence/absence off this."""
    census: Counter = Counter()
    for eqn in walk_eqns(jaxpr):
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if aval is not None and hasattr(aval, "dtype"):
                census[str(aval.dtype)] += 1
    return census
