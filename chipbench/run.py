#!/usr/bin/env python3
"""Chip benchmark of the FP8 training path.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of BENCHMARK.json from the root of a checkout, on the chips
the machine holds. Everything a cell needs is found by name: its
configuration file (`configs/`) and the module of its architecture
(`models/<architectures[0]>.py`), its traffic mix (`traffic/<mix>.json`,
read by `bench.workload`) and the runner of the mix's kind
(`bench/<kind>.py`), and with `--trace 1` each per-layer metric's reader
(`metrics/<name>.py`). The last line of standard output is the
result as JSON; the numbers that decide `correct` are printed beside their
limits as the last lines of standard error and under `checks`, the last
key of the result. Without an accelerator, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from bench import common, metrics  # noqa: E402
from bench.compare import passed  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    return args


def run_cell(args, *, cell=None, require_chip=True, t_process=T_PROCESS):
    """One run of one cell; returns (result, checks, the cell runner's own
    record of the run). `cell` (workload, configuration entry,
    configuration file, traffic mix) replaces what BENCHMARK.json names,
    and `require_chip=False` skips the look for an accelerator: both for
    tests at small sizes on the CPU."""
    work, conf, m, mix = cell or common.cell(args.workload)
    common.src_path()
    common.use_cache()
    device = common.device_info(work["chips"], require=require_chip)
    counter = common.CompileCounter()
    runner = common.runner(mix["kind"])
    out, checks = runner.run(args.workload, work["chips"], m, mix, args,
                             t_process, counter)
    device["memory_peak_bytes"] = out.pop("memory_peak_bytes")
    result = {"correct": passed(checks),
              "attempted": out["attempted"], "failed": out["failed"]}
    if "dropped" in out:
        result["dropped"] = out["dropped"]
    if args.trace:
        red, values = metrics.read_all(args.workload, out["work"], device,
                                       work["chips"], require=require_chip)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["metrics"] = values
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        names = metrics.end_to_end(args.workload)
        result["metrics"] = {k: out["metrics"][k] for k in names}
        common.say("also measured: " + json.dumps(
            {k: v["value"] for k, v in out["metrics"].items()
             if k not in names}))
    result["device"] = device
    common.say(f"setup_s {out['setup_s']!r}; compiles in window "
               f"{out['compiles_in_window']}")
    return result, checks, out


def main():
    args = parse()
    result, checks, _ = run_cell(args)
    common.emit(result, checks)


if __name__ == "__main__":
    main()
