#!/usr/bin/env python3
"""Look at a recorded trace, and keep a small piece of it as a test input.

    python3 chipbench/trace_dump.py <trace dir> [--keep <out.json.gz>]

Prints the trace's planes and lines with their event counts, and the
device ops with the most time and their stats. With --keep, writes the
records `bench.trace.reduce` reads (device ops and the benchmark's host
spans inside the traced window) as gzipped JSON.
"""
import argparse
import collections
import glob
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import trace  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path")
    ap.add_argument("--keep")
    a = ap.parse_args()
    from jax.profiler import ProfileData
    f = sorted(glob.glob(os.path.join(a.path, "**", "*.xplane.pb"),
                         recursive=True))[-1]
    data = ProfileData.from_file(f)
    for plane in data.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print("plane", plane.name, lines[:12])
        if plane.name.startswith("/device:"):
            tot = collections.Counter()
            stats = {}
            for ln in plane.lines:
                if ln.name != trace.OPS_LINE:
                    continue
                for ev in ln.events:
                    name = trace.op_name(ev.name)
                    tot[name] += ev.duration_ns
                    stats.setdefault(name, [(k, str(v)[:80])
                                            for k, v in ev.stats])
            for name, ns in tot.most_common(25):
                print("  op", repr(name), ns / 1e6, "ms", stats[name][:8])
    ev = trace.events(a.path)
    t0, t1 = trace.window_of(ev)
    red = trace.reduce(ev)
    print("reduced", json.dumps({k: v for k, v in red.items()
                                 if k != "op_s"})[:3000])
    if a.keep:
        keep = {"dev": [e for e in ev["dev"] if e[1] + e[2] >= t0
                        and e[1] <= t1],
                "host": [e for e in ev["host"] if e[1] + e[2] >= t0
                         and e[1] <= t1]}
        with gzip.open(a.keep, "wt") as out:
            json.dump(keep, out)
        print("kept", len(keep["dev"]), "device ops,", len(keep["host"]),
              "host spans in", a.keep, os.path.getsize(a.keep), "bytes")


if __name__ == "__main__":
    main()
