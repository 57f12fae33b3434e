"""Benchmark harness: one function per paper table/figure + kernel micro-
benchmarks. Prints ``name,us_per_call,derived`` CSV (spec format).

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig2 tab2  # subset

The parent process never imports JAX: each bench runs in a child process
of its own (`--one NAME`), so one process at a time holds the chip. Exits
non-zero when any bench fails.
"""
import importlib
import subprocess
import sys

BENCHES = {
    "fig1": ("benchmarks.paper_tables", "fig1_motivation_grid"),
    "fig2": ("benchmarks.paper_tables", "fig2_time_to_accuracy"),
    "fig3": ("benchmarks.paper_tables", "fig3_comm_consumption"),
    "tab1": ("benchmarks.paper_tables", "tab1_noniid"),
    "tab2": ("benchmarks.paper_tables", "tab2_joint_vs_single"),
    "kernels": ("benchmarks.kernel_bench", "kernel_microbench"),
    "sync": ("benchmarks.kernel_bench", "sync_crossover"),
    "podsync": ("benchmarks.kernel_bench", "podsync_rows"),
    "sim": ("benchmarks.sim_bench", "smoke_rows"),
    "chaos": ("benchmarks.chaos_bench", "smoke_rows"),
}


def run_one(name: str) -> None:
    from repro.launch import compile_cache
    compile_cache.enable()
    module, fn = BENCHES[name]
    for n, us, derived in getattr(importlib.import_module(module), fn)():
        print(f"{n},{us:.1f},{derived}", flush=True)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--one"]:
        run_one(argv[1])
        return 0
    picks = argv or list(BENCHES)
    unknown = [p for p in picks if p not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown bench(es) {unknown}; choose from "
                         f"{list(BENCHES)}")
    print("name,us_per_call,derived", flush=True)
    failed = []
    for name in picks:
        rc = subprocess.run([sys.executable, "-m", "benchmarks.run", "--one",
                             name]).returncode
        if rc:
            print(f"{name},nan,FAILED", flush=True)
            failed.append(name)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
