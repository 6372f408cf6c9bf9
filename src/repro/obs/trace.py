"""One measurement system for the program: device scopes, host spans on the
profiler's clock, and compile counters.

Device scopes. `SCOPES` is the vocabulary of the phases a compiled train
step is cut into; `scope(name)` applies one as a `jax.named_scope` where its
work is traced. A scope only adds metadata (`op_name` in the compiled HLO):
computed bits, fusion and kernel names are unchanged. The profiler's trace
names device ops by HLO instruction (`%fusion.12 = ...`) and carries no
scope, so `op_scopes(hlo_text)` maps each instruction of the compiled module
to the innermost scope on its path — self time: an SR draw inside a quantize
counts under `fp8.sr_bits`, not `fp8.quant`. `keep_step` / `last_step_text`
hold the compiled text of the last train step run in this process, for a
reader of the trace that runs after the loop has gone.

Host spans. `Tracer.span(name)` times a region on `time.perf_counter` (the
`span/<name>_s` record fields, popped per step by `durations()`) and opens a
`jax.profiler.TraceAnnotation("<prefix>.<name>")` around it, so a profiler
trace shows the host phase beside the device ops on one clock. TrainLoop's
prefix is `repro.train` (data_wait / step_dispatch / device_sync /
checkpoint / record / on_metrics), ServeEngine's `repro.serve`; set-up
regions use `setup_span` (`repro.setup.<name>`). With no trace active an
annotation costs about a microsecond.

Compile counters. One `jax.monitoring` listener per process sums the
backend compilations (`compiles`; a load from the persistent cache counts
too) and the wall seconds of jaxpr tracing, MLIR lowering and backend
compilation (`compile/trace_s`, `compile/lower_s`, `compile/backend_s`; nested
events, such as an inner jit traced inside an outer one, count once).
"""
from __future__ import annotations

import bisect
import contextlib
import re
import time
from typing import Callable, Dict, Optional

import jax

# -- device scopes ---------------------------------------------------------

SCOPES = (
    "train.grads",       # the loss/grad pass (value_and_grad)
    "train.optimizer",   # compute params, apply gradients, norms
    "train.scaling",     # delayed-scaling observations, history update, churn
    "train.allreduce",   # the data-parallel reduction inside the step
    "fp8.sr_bits",       # stochastic-rounding random-bit draws
    "fp8.amax",          # amax and health reductions outside the kernels
    "fp8.quant",         # quantize casts outside the kernels
)
_SCOPE_SET = frozenset(SCOPES)


def scope(name: str):
    """`jax.named_scope(name)` for a name of `SCOPES`."""
    if name not in _SCOPE_SET:
        raise ValueError(f"unknown scope {name!r}; have {SCOPES}")
    return jax.named_scope(name)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_PATH_SPLIT = re.compile(r"[/()]")


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: innermost scope} over every computation of a
    compiled module's text (entry, while bodies, branches, fusions).
    Transformations wrap path elements of an `op_name`
    (`transpose(jvp(fp8.quant))`), so a path is split at parentheses as
    well as slashes. Instructions under no scope are left out."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        op = _OP_NAME.search(line) if m else None
        if op is None:
            continue
        found = [p for p in _PATH_SPLIT.split(op.group(1))
                 if p in _SCOPE_SET]
        if found:
            out[m.group(1)] = found[-1]
    return out


_LAST_STEP: Optional[Callable[[], str]] = None


def keep_step(text: Callable[[], str]):
    """Hold `text`, a call that returns the compiled text of the train step
    a loop last ran (the last one kept wins)."""
    global _LAST_STEP
    _LAST_STEP = text


def last_step_text() -> Optional[str]:
    """The compiled text of the last train step kept, or None."""
    return None if _LAST_STEP is None else _LAST_STEP()


# -- host spans ------------------------------------------------------------

class Tracer:
    def __init__(self, prefix: str = "repro.train"):
        self.prefix = prefix
        self._pending: Dict[str, float] = {}

    @contextlib.contextmanager
    def span(self, name: str, **args):
        """Time the region and mark it `<prefix>.<name>` on the profiler's
        clock; `args` ride the annotation as metadata."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(f"{self.prefix}.{name}",
                                              **args):
                yield
        finally:
            dur = time.perf_counter() - t0
            self._pending[name] = self._pending.get(name, 0.0) + dur

    def durations(self) -> Dict[str, float]:
        """Pop the span durations accumulated since the last call — one
        step's phase breakdown, keyed `span/<name>_s`."""
        out = {f"span/{k}_s": round(v, 6) for k, v in self._pending.items()}
        self._pending = {}
        return out


def setup_span(name: str):
    """Profiler annotation `repro.setup.<name>` around a set-up region."""
    return jax.profiler.TraceAnnotation(f"repro.setup.{name}")


# -- compile counters ------------------------------------------------------

_COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower_s",
    "/jax/core/compile/backend_compile_duration": "compile/backend_s",
}


class _Spans:
    """Disjoint, sorted wall-clock intervals: an interval that overlaps
    others merges with them, so nested events count once."""

    def __init__(self):
        self.starts, self.ends = [], []

    def add(self, s: float, e: float):
        lo = bisect.bisect_right(self.ends, s)
        hi = bisect.bisect_left(self.starts, e)
        if lo < hi:
            s = min(s, self.starts[lo])
            e = max(e, self.ends[hi - 1])
        self.starts[lo:hi] = [s]
        self.ends[lo:hi] = [e]

    def seconds(self, until: Optional[float] = None) -> float:
        if until is None:
            return sum(e - s for s, e in zip(self.starts, self.ends))
        return sum(min(e, until) - s for s, e in zip(self.starts, self.ends)
                   if s < until)


class _Compiles:
    def __init__(self):
        self.count = 0
        self.spans = {k: _Spans() for k in _COMPILE_EVENTS.values()}

    def __call__(self, event, start, end, **_):
        key = _COMPILE_EVENTS.get(event)
        if key is None:
            return
        if key == "compile/backend_s":
            self.count += 1
        self.spans[key].add(start, end)


_COMPILES = _Compiles()
jax.monitoring.register_event_time_span_listener(_COMPILES)


def compiles() -> int:
    """Backend compilations in this process so far."""
    return _COMPILES.count


def compile_seconds(until: Optional[float] = None) -> Dict[str, float]:
    """Wall seconds spent tracing, lowering and compiling in this process,
    up to `until` (`time.time()` seconds) when given."""
    return {k: sp.seconds(until) for k, sp in _COMPILES.spans.items()}
