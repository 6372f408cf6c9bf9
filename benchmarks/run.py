"""Benchmark entry point: one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table2     # one

Prints name,value CSV lines; detailed JSON under experiments/bench/.
"""
import subprocess
import sys
import time

from benchmarks import paper_tables
from benchmarks.kernel_bench import bench_kernels, bench_speed


def bench_comm():
    """Wire-format collectives need an 8-device host platform, which must be
    set before jax initializes — run the comm bench in its own process."""
    import os
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               JAX_PLATFORMS="cpu", PYTHONPATH="src")
    subprocess.run([sys.executable, "-m", "benchmarks.comm_bench", "--smoke"],
                   check=True, env=env)


ALL = {
    "table1": paper_tables.bench_table1,
    "fig2a": paper_tables.bench_fig2a,
    "fig2b": paper_tables.bench_fig2b,
    "fig3_fig4": paper_tables.bench_fig3_fig4,
    "table2": paper_tables.bench_table2,
    "table3": paper_tables.bench_table3,
    "table4": paper_tables.bench_table4,
    # Perf trajectory (repo-root BENCH_*.json): kernel fused-vs-unfused +
    # reduced-scale training tokens/s and step time.
    "kernels": bench_kernels,
    "speed": bench_speed,
    # Wire-format collectives: fp8_ef vs full DP reduction (BENCH_comm.json).
    "comm": bench_comm,
}


def main() -> None:
    from repro.launch.cache import use_compile_cache
    use_compile_cache()
    names = sys.argv[1:] or list(ALL)
    for name in names:
        t0 = time.time()
        print(f"=== {name} ===")
        ALL[name]()
        print(f"{name},elapsed_s,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
