"""The metric readers against hand-computed values, on a made-up trace."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, peaks  # noqa: E402
from bench.reduce import Event, Reduction  # noqa: E402

PEAKS = peaks.lookup("TPU v5 lite")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def ctx(**kw):
    base = dict(peaks=PEAKS, chips=1, mix={"rate": 0.05}, trace=None,
                setup_s=12.5,
                run=types.SimpleNamespace(n_blocks=16, blk=1024))
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_magnitude_hist_counts_and_share():
    mod = reader("magnitude_hist_roofline")
    n = 16 * 1024
    assert mod.counts(n) == (4.0 * n, 2.0 * n)
    least = 4.0 * n / 819e9          # HBM-bound: 2n ops are far under peak
    evs = [Event("magnitude_hist.1", 0.0, 4 * least),
           Event("fusion.2", 0.5, 0.6, "jit(step)/pod_sync.compact_pack"),
           Event("custom-call", 0.7, 0.7 + 4 * least,
                 "jit(step)/jit(magnitude_hist)/pallas_call"),
           Event("broadcast.5", 0.9, 0.95, "jit(step)/jit(magnitude_hist)")]
    tr = Reduction({0: evs}, [], (0.0, 1.0))
    assert reader("magnitude_hist_roofline").read(ctx(trace=tr)) == \
        pytest.approx(25.0)


def test_compact_blocks_counts_and_share():
    mod = reader("compact_blocks_roofline")
    nb, blk, budget = 16, 1024, 51                  # round(0.05 * 1024)
    nbytes, ops = mod.counts(nb, blk, budget)
    assert nbytes == 8 * nb * blk + 8 * nb * budget + 4 * nb
    assert ops == 2 * nb * blk
    least = nbytes / 819e9
    # one call that the trace records as two abutting events counts once
    tr = Reduction({0: [Event("compact_blocks.1", 0.0, 4 * least),
                        Event("compact_blocks.1", 4 * least, 10 * least)]},
                   [], (0.0, 1.0))
    assert mod.read(ctx(trace=tr)) == pytest.approx(10.0)


def test_readers_return_nothing_without_their_source():
    for name in ("magnitude_hist_roofline", "compact_blocks_roofline",
                 "pod.local_round_ms", "pod.sync_ms", "pod.idle_share",
                 "pod.step_mfu"):
        assert reader(name).read(ctx(work={"rounds": 1})) is None, name
    empty = Reduction({0: []}, [], (0.0, 1.0))
    for name in ("magnitude_hist_roofline", "compact_blocks_roofline",
                 "pod.idle_share", "pod.sync_ms", "pod.step_mfu"):
        assert reader(name).read(ctx(trace=empty, work={"rounds": 1})) \
            is None, name


def test_end_to_end_readers():
    work = {"elapsed_s": 20.0, "rounds": 100,
            "flops": 197e12 * 20.0 * 0.05}
    c = ctx(work=work)
    assert reader("round_ms").read(c) == 200.0
    assert reader("mfu").read(c) == pytest.approx(5.0)
    assert reader("setup_s").read(c) == 12.5


def test_scope_and_idle_readers():
    evs = [Event("fusion.1", 0.0, 0.1, "jit(step)/local_round/while/body"),
           Event("while.9", 0.1, 0.2, "jit(step)/vmap(local_round)/while"),
           Event("fusion.2", 0.2, 0.5, "jit(step)/pod_sync.scatter_apply/x"),
           Event("fusion.3", 0.5, 0.6, "jit(step)/pod_sync.compact_pack"),
           Event("copy.4", 0.8, 0.9, "jit(step)/other")]
    tr = Reduction({0: evs}, [], (0.0, 1.0))
    c = ctx(trace=tr, work={"rounds": 2, "flops": 197e12 * 0.7 * 0.04})
    assert reader("pod.local_round_ms").read(c) == pytest.approx(100.0)
    assert reader("pod.sync_ms").read(c) == pytest.approx(200.0)
    assert reader("pod.idle_share").read(c) == pytest.approx(30.0)
    # 0.7 s busy of a 1 s window: the step's share is taken over the busy
    # time, the end-to-end mfu over the whole window
    assert reader("pod.step_mfu").read(c) == pytest.approx(4.0)
