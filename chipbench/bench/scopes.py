"""Device time by the program's scopes, idle gaps by its host spans, and
its compile time before the window.

The program scopes its compiled train step by phase (`repro.obs.trace.
SCOPES`) and keeps the step's compiled text (`last_step_text()`),
whose `op_scopes` map gives each HLO instruction the innermost scope on its
path. The trace names each device op by its instruction (`%fusion.12 =
...`), so the full instance name, not `trace.op_name`'s folded one, finds
the op in the map. Ops missing from the map, or run by another module (the
device's "XLA Modules" line), count as unscoped (None).

Host spans: the program marks its loop phases `repro.train.<name>` (and
`repro.setup.*`) on the profiler's clock, the benchmark its own
`chipbench.*`. An idle gap is named by the innermost span that covers most
of it; the step annotation and the window are never a name.

A program without these pieces (an older checkout) gives nothing to read:
`from_program` returns None and the readers leave their metrics out.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

from bench import common, trace

SPAN_PREFIXES = ("repro.", "chipbench.")
MODULES_LINE = "XLA Modules"
# The map must find at least this share of the step module's op time, or it
# belongs to another program and nothing is read.
MIN_MATCHED = 0.9

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s", re.M)


def instance(text: str) -> str:
    """`%fusion.12 = f32[...] fusion(...)` -> `fusion.12`."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def module_of(text: str) -> str:
    """`jit_train_step_scaled(42)` -> `jit_train_step_scaled`; the first
    line of a module's text, `HloModule jit_train_step_scaled, ...`, too."""
    if text.startswith("HloModule "):
        text = text[len("HloModule "):]
    return re.split(r"[(,\s]", text, 1)[0]


def events(path: str) -> dict:
    """Device ops by instance name (`dev`: name, start ns, duration ns,
    device), the modules they ran in (`mod`: same), the program's and the
    benchmark's host spans (`host`: name, start ns, duration ns), and the
    trace's start on the wall clock (`start_ns`, None if not recorded)."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no trace under {path}")
    data = ProfileData.from_file(files[-1])
    dev, mod, host, start = [], [], [], None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            last = plane.name.rsplit(":", 1)[-1]
            idx = int(last) if last.isdigit() else 0
            for line in plane.lines:
                out = dev if line.name == trace.OPS_LINE else \
                    mod if line.name == MODULES_LINE else None
                if out is None:
                    continue
                for ev in line.events:
                    name = instance(ev.name) if out is dev \
                        else module_of(ev.name)
                    out.append((name, float(ev.start_ns),
                                float(ev.duration_ns), idx))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
        for k, v in plane.stats:
            if k == "profile_start_time":
                start = int(v)
    return {"dev": dev, "mod": mod, "host": host, "start_ns": start}


def _modules(ev: dict, dev: int):
    iv = sorted((s, s + d, n) for n, s, d, i in ev.get("mod", ())
                if i == dev)
    return [s for s, _, _ in iv], iv


def scope_seconds(ev: dict, scopes: dict, module: str, *, chips: int = 1,
                  names=None, ops=None) -> dict:
    """{scope or None: device seconds inside the window}, self time,
    averaged over chips; containers (`while`, ...) count for nothing.
    Ops of another module count under None. With `names` (the module's
    instruction names), the seconds of in-module ops found there come back
    under "matched", of all in-module ops under "module". A dict passed as
    `ops` collects the same seconds by (folded op name, scope)."""
    t0, t1 = trace.window_of(ev)
    out = {"matched": 0.0, "module": 0.0}
    for dev in range(chips):
        starts, mods = _modules(ev, dev)
        for name, s, d, idx in ev["dev"]:
            if idx != dev or trace.op_name(name) in trace.CONTAINERS:
                continue
            s0, e0 = max(s, t0), min(s + d, t1)
            if e0 <= s0:
                continue
            if mods:
                k = bisect.bisect_right(starts, s) - 1
                inside = k >= 0 and s < mods[k][1] and mods[k][2] == module
            else:
                inside = True
            key = scopes.get(name) if inside else None
            out[key] = out.get(key, 0.0) + (e0 - s0)
            if ops is not None:
                k = (trace.op_name(name), key)
                ops[k] = ops.get(k, 0.0) + (e0 - s0) / chips / 1e9
            if inside:
                out["module"] += e0 - s0
                if names is None or name in names:
                    out["matched"] += e0 - s0
    return {k: v / chips / 1e9 for k, v in out.items()}


def name_gaps(ev: dict, *, chips: int = 1, top: int = 10) -> list:
    """The longest idle gaps in the window, each as [span name, seconds]:
    the innermost host span among those covering most of the gap ("no_span"
    where none does)."""
    t0, t1 = trace.window_of(ev)
    spans = [(s, s + d, n) for n, s, d in ev["host"]
             if n != trace.WINDOW_SPAN]
    gaps = []
    for dev in range(chips):
        merged = trace._union([(max(s, t0), min(s + d, t1))
                               for _, s, d, i in ev["dev"]
                               if i == dev and s + d > t0 and s < t1])
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        cover = [(min(b, e) - max(a, s), e - s, n) for s, e, n in spans
                 if min(b, e) > max(a, s)]
        label = "no_span"
        if cover:
            most = max(c[0] for c in cover)
            label = min((c for c in cover if c[0] >= 0.99 * most),
                        key=lambda c: c[1])[2]
        named.append([label, (b - a) / 1e9])
    return named


def newest_trace():
    """The trace directory a traced run just wrote under the benchmark's
    trace root, or None."""
    files = glob.glob(os.path.join(str(common.TRACE_DIR), "**",
                                   "*.xplane.pb"), recursive=True)
    if not files:
        return None
    newest = max(files, key=os.path.getmtime)
    rel = os.path.relpath(newest, str(common.TRACE_DIR))
    return os.path.join(str(common.TRACE_DIR), rel.split(os.sep)[0])


_CACHE: dict = {}


def from_program(ctx):
    """{"scope_s": {scope: s}, "compile_s": s} of the traced run, read
    once per process from the newest trace and the program's kept step; None
    where the program or the trace has nothing to read."""
    if "read" not in _CACHE:
        try:
            _CACHE["read"] = _read_program(ctx)
        except Exception as e:   # a reading fault must not fail the run
            common.say(f"scopes: nothing read ({type(e).__name__}: {e})")
            _CACHE["read"] = None
    return _CACHE["read"]


def _read_program(ctx):
    try:
        from repro.obs import trace as program
    except ImportError:
        return None
    if not all(hasattr(program, f) for f in ("op_scopes", "last_step_text",
                                             "compile_seconds")):
        return None
    path = newest_trace()
    text = program.last_step_text()
    if path is None or text is None:
        return None
    ev = events(path)
    module = module_of(text)
    names = set(_INSTR.findall(text))
    ops: dict = {}
    sec = scope_seconds(ev, program.op_scopes(text), module,
                        chips=ctx["chips"], names=names, ops=ops)
    matched, total = sec.pop("matched"), sec.pop("module")
    common.say(f"scopes: {module}, {total:.6f} s of its ops in the window, "
               f"{matched:.6f} s found in its text; seconds by scope "
               + repr({str(k): round(v, 6) for k, v in sec.items()}))
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:16]
    common.say("largest ops by scope: "
               + repr([[n, str(k), round(v, 6)] for (n, k), v in top]))
    common.say(f"idle gaps by span: {name_gaps(ev, chips=ctx['chips'])}")
    out = {"scope_s": sec if total > 0 and matched >= MIN_MATCHED * total
           else None}
    if ev["start_ns"] is not None:
        t0, _ = trace.window_of(ev)
        cs = program.compile_seconds(until=(ev["start_ns"] + t0) / 1e9)
        common.say(f"compile seconds before the window: {cs}")
        out["compile_s"] = sum(cs.values())
    return out


def share(ctx, name: str):
    """Percent of the traced training window the device spent in ops whose
    innermost scope is `name`; `ctx["scopes"]`, where given, holds
    {scope: seconds} in place of the program's."""
    if ctx["work"]["kind"] != "train" or ctx["trace"]["window_s"] <= 0:
        return None
    sec = ctx.get("scopes")
    if sec is None:
        read = from_program(ctx)
        sec = None if read is None else read["scope_s"]
    if sec is None:
        return None
    return 100.0 * sec.get(name, 0.0) / ctx["trace"]["window_s"]


def setup_compile_s(ctx):
    """The program's trace + lower + compile seconds before the window;
    `ctx["compile_s"]`, where given, in place of the program's."""
    if ctx["work"]["kind"] != "train":
        return None
    if "compile_s" in ctx:
        return ctx["compile_s"]
    read = from_program(ctx)
    return None if read is None else read.get("compile_s")
