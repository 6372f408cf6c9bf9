"""What every cell runner shares: files found by name, the device, the
compile cache and counter, and the result line."""
from __future__ import annotations

import importlib.util
import json
import math
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]      # chipbench/
ROOT = HERE.parent                              # the checkout
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = HERE / ".traces"
RUNNERS = HERE / "bench"


def trace_dir(cellname: str) -> str:
    """An empty directory for this cell's trace (the last one is removed)."""
    import shutil
    d = TRACE_DIR / cellname
    shutil.rmtree(d, ignore_errors=True)
    d.mkdir(parents=True)
    return str(d)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> tuple:
    """(workload entry, configuration entry, configuration file, traffic
    file) for a cell named in BENCHMARK.json."""
    bench = benchmark()
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise SystemExit(f"chipbench: no workload {name!r}; have "
                         f"{sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return (w, conf, load_json(ROOT / conf["file"]),
            load_json(HERE / "traffic" / f"{w['traffic']}.json"))


def load_file(path: Path, name: str, what: str):
    """The Python module in `path`, loaded once a process under `name`;
    exits non-zero, naming the file, where there is none."""
    if name in sys.modules:
        return sys.modules[name]
    if not path.is_file():
        raise SystemExit(f"chipbench: no {what}: no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def runner(kind: str):
    """The runner of a traffic kind: `bench/<kind>.py`, whose `run(...)`
    runs one cell."""
    mod = load_file(RUNNERS / f"{kind}.py", f"bench.{kind}",
                    f"runner for traffic of kind {kind!r}")
    if not callable(getattr(mod, "run", None)):
        raise SystemExit(f"chipbench: no runner for traffic of kind "
                         f"{kind!r}: {mod.__file__} has no run()")
    return mod


def src_path():
    """Put the program under test on the import path; fail without it."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {src}")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def use_cache():
    """JAX's persistent compilation cache at a fixed path in the checkout,
    or where JAX_COMPILATION_CACHE_DIR says."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def device_info(chips: int, *, require: bool = True) -> dict:
    """The device as JAX reports it; exits non-zero without an accelerator
    or with fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if require and d.platform == "cpu":
        raise SystemExit("chipbench: no accelerator: JAX sees only the CPU")
    if len(devs) < chips:
        raise SystemExit(f"chipbench: {chips} chips asked, {len(devs)} seen")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int):
    import jax
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class CompileCounter:
    """Counts backend compilations (JAX's monitoring events; a load from
    the persistent cache counts too) and keeps their names, so a run can
    report any that fall inside its measured window."""

    def __init__(self):
        import jax
        self.n = 0
        self.names = []

        def listen(event, duration, fun_name="?", **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.names.append(f"{fun_name} {duration:.3f} s")
        jax.monitoring.register_event_duration_secs_listener(listen)


def say(*parts):
    print("[chipbench]", *parts, file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of all values."""
    v = sorted(values)
    if not v:
        return float("nan")
    k = max(0, min(len(v) - 1, math.ceil(q / 100.0 * len(v)) - 1))
    return float(v[k])


def emit(result: dict, checks: dict):
    """Print each compared number beside its limit as the last lines of
    standard error, then the result line, with `checks` as its last key,
    as the last line of standard output."""
    for k, c in checks.items():
        print(f"[chipbench] check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = checks
    print(json.dumps(result), flush=True)
