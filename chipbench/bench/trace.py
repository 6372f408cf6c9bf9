"""From the profiler's trace to busy time, kernel time and idle gaps.

`events(path)` flattens an `.xplane.pb` into plain records: device ops
(`dev`: name, start ns, duration ns, device index) and the benchmark's own
host spans (`host`: name, start ns, duration ns). `reduce` works on those
records only, so a test can feed it a small recorded trace.
"""
from __future__ import annotations

import glob
import os

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = "chipbench.window"
OPS_LINE = "XLA Ops"


def op_name(text: str) -> str:
    """The op's name from its HLO text: `%fused_quant_matmul_nn.104 = ...`
    gives `fused_quant_matmul_nn` (the numbered instance folded in)."""
    name = text.split(" = ", 1)[0].strip().lstrip("%")
    base, _, num = name.rpartition(".")
    return base if base and num.isdigit() else name


# Ops that only contain other ops (a scanned layer stack is one `while`):
# they count toward busy time but not toward any op's own time.
CONTAINERS = ("while", "conditional", "call")


def events(path: str) -> dict:
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise SystemExit(f"chipbench: no trace under {path}")
    data = ProfileData.from_file(files[-1])
    dev, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            idx = int(plane.name.rsplit(":", 1)[-1]) \
                if plane.name.rsplit(":", 1)[-1].isdigit() else 0
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    dev.append((op_name(ev.name), float(ev.start_ns),
                                float(ev.duration_ns), idx))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        host.append((ev.name, float(ev.start_ns),
                                     float(ev.duration_ns)))
    return {"dev": dev, "host": host}


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def window_of(ev: dict):
    spans = [(s, s + d) for n, s, d in ev["host"] if n == WINDOW_SPAN]
    if not spans:
        raise SystemExit("chipbench: the trace holds no window span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(ev: dict, *, chips: int = 1, top: int = 10) -> dict:
    """Busy time (union of op intervals, averaged over chips), window,
    device time per op name, and the longest idle gaps, each named by the
    host span that covers most of it ("no span" where none does)."""
    t0, t1 = window_of(ev)
    busy, op_time, gaps = 0.0, {}, []
    spans = [(s, s + d, n) for n, s, d in ev["host"] if n != WINDOW_SPAN]
    for dev in range(chips):
        iv = []
        for name, s, d, idx in ev["dev"]:
            if idx != dev:
                continue
            s0, e0 = max(s, t0), min(s + d, t1)
            if e0 <= s0:
                continue
            iv.append((s0, e0))
            if name not in CONTAINERS:
                op_time[name] = op_time.get(name, 0.0) + (e0 - s0)
        merged = _union(iv)
        busy += sum(e - s for s, e in merged)
        edges = [t0] + [x for s, e in merged for x in (s, e)] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for a, b in gaps[:top]:
        cover = {}
        for s, e, n in spans:
            o = min(b, e) - max(a, s)
            if o > 0:
                cover[n] = cover.get(n, 0.0) + o
        label = max(cover, key=cover.get) if cover else "no span"
        named.append([label, (b - a) / 1e9])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": busy / chips / 1e9,
            "op_s": {k: v / chips / 1e9 for k, v in op_time.items()},
            "device_ops": [[k, v / chips / 1e9] for k, v in ops[:top]],
            "idle_gaps": named}


def kernel_s(red: dict, prefix: str) -> float:
    """Device seconds of the ops whose name holds `prefix`."""
    return sum(v for k, v in red["op_s"].items() if prefix in k)
