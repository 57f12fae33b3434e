"""The readings that the correctness limits are set from, on the chip.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--fault NAME] [--control]

For each seed, in one process: the cell's set-up (the program's first
steps, exactly as a benchmark run makes them, without the window), then the
plain reference in float32 at HIGHEST precision and, with `--control`, the
control (the same reference with int8 contractions, bench/precision.py).
Prints one JSON line per seed with the program's gaps (`program`, the lower
reading), the control's (`control`, the upper reading) and every per-leaf
reading (`raw`). With `--fault` (one of the path's `FAULTS`, see
bench/paths/<path>.py) the program runs with that fault planted.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--fault", default="")
    ap.add_argument("--control", action="store_true",
                    help="also compute the int8 control")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # the script's own directory goes: bench/ modules are imported as
    # `bench.*`, never as top-level names
    sys.path[:1] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    r = harness.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(harness.readings(r, seed, args.fault, args.control)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
