"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell named in BENCHMARK.json (its configuration, traffic mix and
metrics, each found by name under bench/), builds and warms the program
on the chip, measures for `--seconds`, checks what the timed path produced
against the plain reference, and prints one JSON line as the last line of
standard output. `--trace 1` records a profiler trace of the window and
reports the cell's per-layer metrics instead of its end-to-end ones. With
no TPU, or fewer chips than the cell asks for, it exits non-zero and
prints no result. JAX's compile cache is kept in `<checkout>/.jax_cache`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the script's own directory goes: bench/ modules are imported as
    # `bench.*`, never as top-level names
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    return harness.main(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)


if __name__ == "__main__":
    sys.exit(main())
