"""Small stand-ins of the benchmark's cells for CPU tests: the real
configuration modules, path code and limits, with the sizes of
bench/tests/data."""
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import check, harness, traffic  # noqa: E402

CELLS = {
    "pod": ("pod.mamba2-780m.sync_heavy", "mamba2-780m", "mamba2_tiny",
            "pod_tiny"),
}


def cell(path: str) -> dict:
    """The `harness.resolve` dict of the `path` cell at its tiny size."""
    workload, config, tiny_cfg, tiny_mix = CELLS[path]
    return {"cell": {"name": workload, "chips": 1},
            "cfg": json.loads((DATA / f"{tiny_cfg}.json").read_text()),
            "model": harness.load_module(
                harness.BENCH / "configs" / f"{config}.py"),
            "mix": traffic.load(tiny_mix, DATA),
            "limits": check.load_limits(workload),
            "end_to_end": [], "per_layer": []}
