#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, the
lower-precision control, and the faults the check has to catch.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \
        --seconds <s> [--out <file.jsonl>]
    python3 chipbench/control.py --workload <cell> --seeds 1,2 \
        --program-kept '{"1": [true, false, true], "2": [true, true, true]}'

For each seed, in one process: a run of the cell as the benchmark makes it
(`--seconds` may be short: training's readings need no window), then the
control: the plain reference put in the program's place at int4
(`bench.reference`, bits=4), and the fault "half of the batch left out,
the mean taken over the rest". With `--program-kept`, the loss scaler's
verdicts that earlier runs of the program printed for each seed, only the
full-precision reference runs, following those verdicts, and reports its
overflow ratio on each step (what sets the configuration's
`overflow_band`). One JSON line per seed. The benchmark's own runs never
run this.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run  # noqa: E402
from bench import common, compare, train  # noqa: E402


def batches(m, mix, seed):
    return train.batches(m, mix, seed)[:train.CHECK_STEPS]


def readings(cellname, seed, seconds, cell=None, require_chip=True):
    args = run.parse(["--workload", cellname, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", "0"])
    cell = cell or common.cell(cellname)
    _, _, m, mix = cell
    t0 = time.perf_counter()
    result, checks, out = run.run_cell(args, cell=cell,
                                       require_chip=require_chip,
                                       t_process=t0)
    line = {"seed": seed, "correct": result["correct"],
            "program": out["readings"],
            "program_widest": out.get("widest")}
    p = m["program"]
    pool = batches(m, mix, seed)
    ref = train.reference_readings(m, p["optimizer"], pool, seed,
                                   p["master_dtype"])
    for name, kw in (("control", {"bits": 4}),
                     ("half_batch", {"batch_rows": mix["batch"] // 2})):
        other = train.reference_readings(m, p["optimizer"], pool, seed,
                                         p["master_dtype"], **kw)
        r = compare.train_readings(other, ref)
        line[name + "_widest"] = r.pop("widest")
        line[name] = r
    line["metrics"] = {k: v["value"] for k, v in result["metrics"].items()}
    return line


def overflow_ratios(cellname, seed, kept, cell=None):
    """The reference alone, following the program's verdicts `kept`."""
    _, _, m, mix = cell or common.cell(cellname)
    p = m["program"]
    t0 = time.perf_counter()
    ref = train.reference_readings(m, p["optimizer"], batches(m, mix, seed),
                                   seed, p["master_dtype"], kept=kept,
                                   band=(0.0, float("inf")))
    return {"seed": seed, "program_kept": kept, "overflow": ref["overflow"],
            "losses": ref["losses"],
            "seconds": time.perf_counter() - t0}


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--program-kept")
    ap.add_argument("--out")
    a = ap.parse_args()
    kept = json.loads(a.program_kept) if a.program_kept else None
    if kept is not None:
        common.src_path()
        common.use_cache()
        common.device_info(1)
    for s in a.seeds.split(","):
        if kept is None:
            line = readings(a.workload, int(s), a.seconds)
        else:
            line = overflow_ratios(a.workload, int(s), kept[s])
        print(json.dumps(line), flush=True)
        if a.out:
            with open(a.out, "a") as f:
                f.write(json.dumps(line) + "\n")


if __name__ == "__main__":
    main()
