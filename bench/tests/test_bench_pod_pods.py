"""The `pod_pods` path on four virtual CPU devices, in a child process
(the device count is fixed when JAX starts): its reference, with pod q's
state on device q, reads what `pod.Cell.reference` reads with every pod on
one device, and a sound four-pod run of the tiny Mamba-2 cell is correct
by the four-pod cell's limits."""
import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1] + "/src", sys.argv[1], sys.argv[1] + "/bench/tests"]
import jax
import numpy as np
import tiny
from bench import check, harness, traffic
assert len(jax.devices()) == 4
W = "pod4.mamba2-780m.sync_heavy"
r = tiny.cell("pod")
r["cell"] = {"name": W, "chips": 4}
r["mix"] = dict(r["mix"], path="pod_pods")
r["limits"] = check.load_limits(W)
out = harness.run_cell(r, 2 ** 31 + 7, 0.2, False,
                       t_start=time.perf_counter(), on_chip=False)
pods = harness.load_module(harness.BENCH / "paths" / "pod_pods.py")
run = pods.Cell(r["cfg"], r["model"], r["mix"], 4, 2 ** 31 + 7)
run.setup()
run.free()
mine = run.reference()
one_device = pods.pod.Cell.reference(run)
print(json.dumps({"correct": out["correct"], "check": out["check"],
                  "same": {k: bool(np.array_equal(np.asarray(mine[k]),
                                                  np.asarray(one_device[k])))
                           for k in mine}}))
"""


def test_four_pod_reference_and_sound_run():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", CHILD, str(ROOT)], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["same"] == {"loss": True, "update": True, "change": True}
    assert got["correct"], got["check"]
