"""The pod cell's correctness check at a small size on the CPU: a sound run
passes; the int8 control and each planted fault fail it."""
import time

import pytest

import tiny
from bench import check, harness

FAULTS = harness.load_module(harness.BENCH / "paths" / "pod.py").FAULTS

PATH = "pod"
SEED = 2 ** 31 + 5


def _run(fault=""):
    r = tiny.cell(PATH)
    with FAULTS[fault]() if fault else _nothing():
        return harness.run_cell(r, SEED, 0.2, False,
                                t_start=time.perf_counter(),
                                on_chip=False)


class _nothing:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check" and out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = _run(fault)
    assert not out["correct"], out["check"]


def test_control_is_not_correct():
    r = tiny.cell(PATH)
    got = harness.readings(r, SEED, control=True, on_chip=False)
    assert check.verdict(got["program"], r["limits"])[0], got
    assert not check.verdict(got["control"], r["limits"])[0], got
