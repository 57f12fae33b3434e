"""The pod round's layer readers (`pod.threshold_ms`, `pod.pack_ms`,
`pod.optimizer_ms`, `pod.layout_ms`, `pod.unscoped_ms`) against
hand-computed values, on a made-up trace."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, peaks  # noqa: E402
from bench.reduce import Event, Reduction  # noqa: E402

LAYER_READERS = ("pod.threshold_ms", "pod.pack_ms", "pod.optimizer_ms",
                 "pod.layout_ms", "pod.unscoped_ms")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def ctx(**kw):
    base = dict(peaks=peaks.lookup("TPU v5 lite"), chips=1,
                mix={"rate": 0.05}, trace=None, setup_s=12.5,
                run=types.SimpleNamespace(n_blocks=16, blk=1024))
    base.update(kw)
    return types.SimpleNamespace(**base)


@pytest.mark.parametrize("name", LAYER_READERS)
def test_layer_readers_return_nothing_without_a_trace(name):
    assert reader(name).read(ctx(work={"rounds": 1})) is None
    empty = Reduction({0: []}, [], (0.0, 1.0))
    assert reader(name).read(ctx(trace=empty, work={"rounds": 1})) is None


def test_layer_readers_tile_the_round():
    sync = "jit(step)/pod_sync.compact_pack/jit(compact_shard_topk)/" \
        "compact_shard_topk/"
    evs = [Event("fusion.1", 0.0, 0.1, "jit(step)/vmap(local_round)/while/"
                 "body/closed_call/opt.update/mul"),
           Event("fusion.2", 0.1, 0.2, "jit(step)/vmap(local_round)/while/"
                 "body/dot_general"),
           Event("magnitude_hist.3", 0.2, 0.3, sync + "jit(solve_threshold)/"
                 "topk.threshold/jit(magnitude_hist)/pallas_call"),
           Event("compact_blocks.1", 0.3, 0.5, sync + "topk.pack/"
                 "jit(compact_blocks)/pallas_call"),
           Event("fusion.3", 0.5, 0.55, "jit(step)/pod_sync.scatter_apply/"
                 "scatter-add"),
           Event("slice.4", 0.55, 0.6, "jit(step)/pod_round.unflatten/slice"),
           Event("dynamic-update-slice.5", 0.6, 0.65,
                 "jit(step)/vmap(pod_round.flatten)/concatenate"),
           Event("copy.6", 0.65, 0.7),
           Event("fusion.7", 0.7, 0.72, "jit(step)/other"),
           # one op the compiler merged from a sync op and a layout op:
           # pod.sync_ms counts it, so pod.layout_ms leaves it out
           Event("reshape.8", 0.72, 0.74, "jit(step)/pod_sync.compact_pack/"
                 "squeeze;jit(step)/pod_round.flatten/reshape")]
    # two chips that ran the same ops: every reading is per chip
    tr = Reduction({0: evs, 1: list(evs)}, [], (0.0, 1.0))
    c = ctx(trace=tr, chips=2, work={"rounds": 2})
    got = {name: reader(name).read(c) for name in LAYER_READERS +
           ("pod.local_round_ms", "pod.sync_ms")}
    assert got == pytest.approx({
        "pod.threshold_ms": 50.0, "pod.pack_ms": 100.0,
        "pod.optimizer_ms": 50.0, "pod.layout_ms": 50.0,
        "pod.unscoped_ms": 35.0, "pod.local_round_ms": 100.0,
        "pod.sync_ms": 185.0})
    tiled = sum(got[n] for n in ("pod.local_round_ms", "pod.sync_ms",
                                 "pod.layout_ms", "pod.unscoped_ms"))
    assert tiled == pytest.approx(1e3 * tr.busy_s / 2)
