"""Render a precision-health dashboard (markdown) from a metrics jsonl.

  PYTHONPATH=src python -m repro.tools.healthdash experiments/obs/metrics.jsonl
  PYTHONPATH=src python -m repro.tools.healthdash metrics.jsonl --out dash.md
  PYTHONPATH=src python -m repro.tools.healthdash metrics.jsonl --validate

Consumes the MetricsLogger stream (one record per step, sidecar
`<path>.meta.json` for run metadata — see docs/metrics_schema.md): run
summary, step-time percentiles with the span/phase breakdown, the per-site
FP8 saturation/flush table, the health-event log, and (when a serve-stats
json is passed) the serving counters. `--validate` checks every record
against the versioned schema and exits non-zero on violations — CI runs it
over the nightly smoke's artifacts.

Doubles as a library: report.py calls `render(...)` for the EXPERIMENTS.md
observability section, tests call `validate_records(...)`.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.obs.metrics import SCHEMA_VERSION

HEALTH_PREFIX = "health/"
COMM_PREFIX = "comm/"
# health/* keys that are NOT per-site [sat, flush] pairs: the dense per-site
# amax vector and the scalar scale-churn rate (fraction of sites whose scale
# moved this step).
_NON_PAIR_KEYS = ("health/amax_sites", "health/scale_churn")
# comm/* keys that carry strings (the wire-format name), not numbers.
_COMM_STR_KEYS = ("comm/wire",)


def load_metrics(path: str) -> Tuple[List[Dict[str, Any]], Dict[str, Any]]:
    """(records, meta) from a jsonl file and its sidecar meta json."""
    records = [json.loads(line)
               for line in Path(path).read_text().splitlines() if line]
    meta_path = Path(str(path) + ".meta.json")
    meta = json.loads(meta_path.read_text()) if meta_path.exists() else {}
    return records, meta


# ---------------------------------------------------------------------------
# schema validation (CI gate)
# ---------------------------------------------------------------------------

def validate_records(records: List[Dict[str, Any]],
                     meta: Optional[Dict[str, Any]] = None) -> List[str]:
    """Schema violations as human-readable strings ([] == valid)."""
    errors: List[str] = []
    if meta and meta.get("schema_version") != SCHEMA_VERSION:
        errors.append(f"meta schema_version {meta.get('schema_version')!r} "
                      f"!= {SCHEMA_VERSION}")
    prev_step = None
    for i, rec in enumerate(records):
        where = f"record {i}"
        if rec.get("v") != SCHEMA_VERSION:
            errors.append(f"{where}: v={rec.get('v')!r} != {SCHEMA_VERSION}")
        if not isinstance(rec.get("step"), int):
            errors.append(f"{where}: missing/non-int 'step'")
        else:
            if prev_step is not None and rec["step"] <= prev_step:
                errors.append(f"{where}: step {rec['step']} not increasing "
                              f"(prev {prev_step})")
            prev_step = rec["step"]
        for k in ("step_time_s", "stragglers"):
            if k in rec and not isinstance(rec[k], (int, float)):
                errors.append(f"{where}: {k} not numeric")
        for k, v in rec.items():
            if k.startswith(HEALTH_PREFIX) and k not in _NON_PAIR_KEYS:
                arr = np.asarray(v, dtype=np.float64)
                if arr.shape[-1:] != (2,):
                    errors.append(f"{where}: {k} last dim != 2 "
                                  f"(shape {arr.shape})")
            if k.startswith(COMM_PREFIX) and k not in _COMM_STR_KEYS \
                    and not isinstance(v, (int, float)):
                errors.append(f"{where}: {k} not numeric ({v!r})")
        for ev in rec.get("health_events", []):
            if "kind" not in ev or "step" not in ev:
                errors.append(f"{where}: malformed health_event {ev!r}")
    return errors


# ---------------------------------------------------------------------------
# rendering
# ---------------------------------------------------------------------------

def _pct(vals, q):
    return float(np.percentile(np.asarray(vals, np.float64), q)) \
        if len(vals) else None


def _fmt(v, spec=".4g"):
    return "—" if v is None else format(v, spec)


def _site_table(records: List[Dict[str, Any]], top: int = 12) -> List[str]:
    """Worst sites by max saturation/flush over the run. Vector-valued
    (per-layer) series reduce with max — the dashboard flags the worst
    layer; the jsonl keeps the full trajectory."""
    agg: Dict[str, Dict[str, float]] = {}
    for rec in records:
        for k, v in rec.items():
            if not k.startswith(HEALTH_PREFIX) or k in _NON_PAIR_KEYS:
                continue
            arr = np.asarray(v, np.float64).reshape(-1, 2)
            a = agg.setdefault(k[len(HEALTH_PREFIX):],
                               {"sat": 0.0, "flush": 0.0,
                                "last_sat": 0.0, "last_flush": 0.0})
            a["sat"] = max(a["sat"], float(arr[:, 0].max()))
            a["flush"] = max(a["flush"], float(arr[:, 1].max()))
            a["last_sat"] = float(arr[:, 0].max())
            a["last_flush"] = float(arr[:, 1].max())
    if not agg:
        return ["_No per-site health counters in this run "
                "(QuantConfig.track_health off)._"]
    ranked = sorted(agg.items(),
                    key=lambda kv: kv[1]["sat"] + kv[1]["flush"],
                    reverse=True)
    lines = [f"{len(agg)} sites tracked; worst {min(top, len(ranked))} by "
             "peak saturation+flush:",
             "",
             "| site | peak sat | peak flush | last sat | last flush |",
             "|---|---|---|---|---|"]
    for site, a in ranked[:top]:
        lines.append(f"| `{site}` | {a['sat']:.4f} | {a['flush']:.4f} | "
                     f"{a['last_sat']:.4f} | {a['last_flush']:.4f} |")
    return lines


def _events_section(records: List[Dict[str, Any]], cap: int = 40) -> List[str]:
    events = [ev for rec in records for ev in rec.get("health_events", [])]
    if not events:
        return ["_No health events._"]
    by_kind: Dict[str, int] = {}
    for ev in events:
        by_kind[ev["kind"]] = by_kind.get(ev["kind"], 0) + 1
    lines = [" ".join(f"`{k}`×{n}" for k, n in sorted(by_kind.items())), ""]
    for ev in events[:cap]:
        site = f" site=`{ev['site']}`" if "site" in ev else ""
        val = f" value={ev['value']:.4g}" if "value" in ev else ""
        msg = f" — {ev['msg']}" if ev.get("msg") else ""
        lines.append(f"- step {ev['step']}: **{ev['kind']}**{site}{val}{msg}")
    if len(events) > cap:
        lines.append(f"- … {len(events) - cap} more")
    return lines


def _comms_section(records: List[Dict[str, Any]],
                   meta: Optional[Dict[str, Any]] = None) -> List[str]:
    """Wire-format communication stream (distributed runs): per-step wire
    bytes of the DP gradient reduction (its device time is in a profiler
    trace, under the `train.allreduce` scope). Absent entirely for
    single-device runs."""
    comm_keys = sorted({k for r in records for k in r
                        if k.startswith(COMM_PREFIX)})
    if not comm_keys:
        return []
    last = next((r for r in reversed(records)
                 if any(k in r for k in comm_keys)), {})
    dist = (meta or {}).get("dist") or {}
    lines = ["", "## Comms", ""]
    if dist:
        lines.append(
            f"- plan: dp={dist.get('dp_axes')} (size {dist.get('dp_size')}), "
            f"zero1={dist.get('zero1_axis')}, tp={dist.get('tp_axis')}, "
            f"wire={dist.get('wire')} over axis {dist.get('wire_axis')!r}")
    bps = last.get("comm/bytes_per_step")
    ratio = last.get("comm/ratio_fp8_vs_bf16")
    n_steps = sum(1 for r in records if "comm/bytes_per_step" in r)
    if isinstance(bps, (int, float)):
        lines.append(f"- DP reduction wire bytes/step: {_fmt(bps, '.4g')} "
                     f"({_fmt(bps * n_steps, '.4g')} over {n_steps} steps)")
    if isinstance(ratio, (int, float)):
        lines.append(f"- fp8_ef vs bf16 wire ratio: {_fmt(ratio, '.3f')}")
    return lines


def render(records: List[Dict[str, Any]],
           meta: Optional[Dict[str, Any]] = None,
           serve_stats: Optional[Dict[str, Any]] = None,
           title: str = "Precision-health dashboard") -> str:
    meta = meta or {}
    lines = [f"# {title}", ""]
    if meta:
        bits = [f"{k}={meta[k]!r}" for k in
                ("arch", "recipe", "track_health", "n_microbatches")
                if k in meta]
        if "sites" in meta:
            bits.append(f"sites={len(meta['sites'])}")
        lines += ["Run: " + ", ".join(bits) if bits else "Run: (no meta)", ""]
    if records:
        steps = [r.get("step") for r in records]
        losses = [r["loss"] for r in records
                  if isinstance(r.get("loss"), (int, float))]
        times = [r["step_time_s"] for r in records
                 if isinstance(r.get("step_time_s"), (int, float))]
        oflow = [r["overflow_count"] for r in records
                 if isinstance(r.get("overflow_count"), (int, float))]
        lines += [
            "## Run summary", "",
            f"- steps: {len(records)} "
            f"(step {steps[0]} → {steps[-1]})",
            f"- loss: first {_fmt(losses[0] if losses else None)}, "
            f"last {_fmt(losses[-1] if losses else None)}",
            f"- overflow_count: "
            f"{_fmt(oflow[-1] if oflow else None, '.0f')}",
            f"- stragglers: "
            f"{records[-1].get('stragglers', 0)}",
            "", "## Step time", "",
            f"- p50 {_fmt(_pct(times, 50))} s, "
            f"p99 {_fmt(_pct(times, 99))} s "
            f"(n={len(times)}, compile step included)",
        ]
        span_keys = sorted({k for r in records for k in r
                            if k.startswith("span/")})
        if span_keys:
            lines += ["", "| span | mean s | p99 s |", "|---|---|---|"]
            for k in span_keys:
                vals = [r[k] for r in records
                        if isinstance(r.get(k), (int, float))]
                lines.append(
                    f"| {k[len('span/'):-2]} | "
                    f"{_fmt(float(np.mean(vals)) if vals else None)} | "
                    f"{_fmt(_pct(vals, 99))} |")
        lines += _comms_section(records, meta)
        lines += ["", "## FP8 site health", ""] + _site_table(records)
        lines += ["", "## Health events", ""] + _events_section(records)
    else:
        lines += ["_Empty metrics stream._"]
    if serve_stats:
        lines += ["", "## Serving", ""]
        lines += [
            f"- requests: {serve_stats.get('requests')} "
            f"({serve_stats.get('finished')} finished, "
            f"{serve_stats.get('active')} active)",
            f"- KV-slot occupancy: "
            f"{_fmt(serve_stats.get('kv_slot_occupancy'), '.2f')} "
            f"of max_batch={serve_stats.get('max_batch')}",
            f"- decode: {serve_stats.get('decode_tokens')} tokens at "
            f"{_fmt(serve_stats.get('decode_tokens_per_s'), '.1f')} tok/s",
        ]
        for name, label in (("prefill_latency_s", "prefill latency"),
                            ("decode_step_s", "decode step"),
                            ("request_latency_s", "request latency")):
            d = serve_stats.get(name) or {}
            lines.append(f"- {label}: p50 {_fmt(d.get('p50'))} s, "
                         f"p99 {_fmt(d.get('p99'))} s")
    return "\n".join(lines) + "\n"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("metrics", help="metrics jsonl path (MetricsLogger sink)")
    ap.add_argument("--serve", help="serve-stats json (ServeEngine.stats())")
    ap.add_argument("--out", help="write markdown here (default: stdout)")
    ap.add_argument("--validate", action="store_true",
                    help="schema-validate only; exit 1 on violations")
    args = ap.parse_args(argv)
    records, meta = load_metrics(args.metrics)
    if args.validate:
        errors = validate_records(records, meta)
        for e in errors:
            print(f"[healthdash] SCHEMA: {e}", file=sys.stderr)
        print(f"[healthdash] {len(records)} records, "
              f"{len(errors)} schema violations")
        return 1 if errors else 0
    serve_stats = json.loads(Path(args.serve).read_text()) \
        if args.serve else None
    md = render(records, meta, serve_stats)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(md)
        print(f"[healthdash] wrote {args.out}")
    else:
        print(md)
    return 0


if __name__ == "__main__":
    sys.exit(main())
