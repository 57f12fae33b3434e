"""A small stand-in of the `pod.nemotron3-nano-30b-a3b.moe_8k` cell for CPU
tests: the real configuration module, path code and limits, with the
sizes of bench/tests/data/nemotron_tiny.json and moe_tiny.json."""
import json

import tiny
from bench import check, harness, traffic

WORKLOAD = "pod.nemotron3-nano-30b-a3b.moe_8k"
MODEL = harness.load_module(harness.BENCH / "configs"
                            / "nemotron3-nano-30b-a3b.py")


def cell() -> dict:
    """The `harness.resolve` dict of the cell at its tiny size."""
    return {"cell": {"name": WORKLOAD, "chips": 1},
            "cfg": json.loads((tiny.DATA / "nemotron_tiny.json").read_text()),
            "model": MODEL,
            "mix": traffic.load("moe_tiny", tiny.DATA),
            "limits": check.load_limits(WORKLOAD),
            "end_to_end": [], "per_layer": []}
