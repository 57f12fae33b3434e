"""`pod.scatter_apply_ms` against hand-computed values, on a made-up trace:
it reads the ops under `pod_sync.scatter_apply`, whether the sync applies
its payload in the `expand_blocks` kernel or in XLA's scatter-add and its
sort, and nothing else of `pod.sync_ms`."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402
from bench.reduce import Event, Reduction  # noqa: E402

APPLY = "jit(step)/pod_sync.scatter_apply/"
PACK = "jit(step)/pod_sync.compact_pack/jit(compact_shard_topk)/" \
    "compact_shard_topk/topk.pack/jit(compact_blocks)/pallas_call"


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def ctx(trace, chips=1, rounds=1):
    return types.SimpleNamespace(trace=trace, chips=chips,
                                 work={"rounds": rounds})


def test_returns_nothing_without_the_scope():
    r = reader("pod.scatter_apply_ms")
    assert r.read(ctx(None)) is None
    other = Reduction({0: [Event("compact_blocks.1", 0.0, 0.1, PACK)]}, [],
                      (0.0, 1.0))
    assert r.read(ctx(other)) is None


@pytest.mark.parametrize("ops", [
    # the kernel: one custom call
    [("expand_blocks.1", 0.2, APPLY + "jit(expand_blocks)/pallas_call")],
    # XLA's scatter-add, the sort of its indices, the parameter update
    [("sort", 0.05, APPLY + "scatter-add"),
     ("fusion.3", 0.12, APPLY + "scatter-add"),
     ("multiply_subtract_fusion", 0.03, APPLY + "sub")],
])
def test_reads_the_apply_and_only_the_apply(ops):
    evs, t = [Event("compact_blocks.1", 0.0, 0.3, PACK)], 0.3
    for name, dur, op_name in ops:
        evs.append(Event(name, t, t + dur, op_name))
        t += dur
    # two chips that ran the same ops, over two rounds: per chip and round
    tr = Reduction({0: evs, 1: list(evs)}, [], (0.0, 1.0))
    got = reader("pod.scatter_apply_ms").read(ctx(tr, chips=2, rounds=2))
    assert got == pytest.approx(1e3 * 0.2 / 2)
    sync = reader("pod.sync_ms").read(ctx(tr, chips=2, rounds=2))
    assert sync == pytest.approx(1e3 * 0.5 / 2)
