"""Per-layer metrics: one small reader per metric, found by name.

`metrics/<name>.py` defines `read(ctx) -> float | None`. `ctx` holds the
reduced trace (`trace`), the work the traced window did (`work`), the
chip's published peaks (`peaks`) and the number of chips (`chips`). A
reader that finds nothing to read returns None, and the metric is left
out of the result line.
"""
from __future__ import annotations

from bench import common, peaks, trace


def end_to_end(cellname: str) -> list:
    """The end-to-end metrics of BENCHMARK.json that this cell reports."""
    return [m["name"] for m in common.benchmark()["end_to_end"]
            if cellname in m.get("workloads", [cellname])]


def selected(cellname: str) -> list:
    """The per-layer metrics of BENCHMARK.json that this cell reports."""
    bench = common.benchmark()
    e2e = set(end_to_end(cellname))
    return [p for p in bench["per_layer"]
            if (cellname in p["workloads"] if "workloads" in p
                else p["moves"] in e2e)]


def reader(name: str):
    return common.load_file(common.HERE / "metrics" / f"{name}.py",
                            f"chipbench_metric_{name}",
                            f"reader of metric {name!r}").read


def read_all(cellname, work, device, chips, *, require=True):
    """(reduced trace, {name: {"value", "unit"}}) for a traced run."""
    red = trace.reduce(trace.events(str(common.TRACE_DIR / cellname)),
                       chips=chips)
    kind = device["kind"] if require else "TPU v5 lite"
    ctx = {"trace": red, "work": work, "peaks": peaks.peaks_for(kind),
           "chips": chips}
    values = {}
    for p in selected(cellname):
        v = reader(p["name"])(ctx)
        if v is not None:
            values[p["name"]] = {"value": v, "unit": p["unit"]}
    return red, values
