"""The readers added with the Nemotron and four-pod cells against
hand-computed values, on made-up traces: `pod.moe_route_ms`,
`pod.moe_experts_ms`, `pod.attention_ms`, and `pod4.sync_ms` /
`pod4.scatter_apply_ms`, which read what `pod.sync_ms` /
`pod.scatter_apply_ms` read."""
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness  # noqa: E402
from bench.reduce import Event, Reduction  # noqa: E402

LOCAL = "jit(step)/vmap(local_round)/while/body/"
NEW = ("pod.moe_route_ms", "pod.moe_experts_ms", "pod.attention_ms",
       "pod4.sync_ms", "pod4.scatter_apply_ms")


def reader(name):
    return harness.load_module(harness.BENCH / "metrics" / f"{name}.py")


def ctx(trace, chips=1, rounds=1):
    return types.SimpleNamespace(trace=trace, chips=chips,
                                 work={"rounds": rounds})


@pytest.mark.parametrize("name", NEW)
def test_new_readers_return_nothing_without_their_scopes(name):
    assert reader(name).read(ctx(None)) is None
    other = Reduction({0: [Event("fusion.1", 0.0, 0.1,
                                 LOCAL + "opt.update/mul")]}, [], (0.0, 1.0))
    assert reader(name).read(ctx(other)) is None


def test_moe_and_attention_readers_split_the_layer():
    ops = [("fusion.1", 0.010, "checkpoint/moe.route/dot_general"),
           ("sort.2", 0.004, "checkpoint/moe.dispatch/sort"),
           ("fusion.3", 0.003, "transpose(jvp(moe.dispatch))/scatter-add"),
           ("gmm.4", 0.020, "checkpoint/moe.experts/jit(gmm)/pallas_call"),
           ("tgmm.5", 0.030, "transpose(jvp())/checkpoint/moe.experts/"
            "jit(tgmm)/pallas_call"),
           ("convolution.6", 0.015, "checkpoint/moe.shared/dot_general"),
           ("fusion.7", 0.040, "checkpoint/attn/while/body/dot_general"),
           ("fusion.8", 0.050, "checkpoint/ssd/einsum"),
           ("fusion.9", 0.001, "checkpoint/attn_norm/mul")]
    evs, t = [], 0.0
    for name, dur, op_name in ops:
        evs.append(Event(name, t, t + dur, LOCAL + op_name))
        t += dur
    # two chips that ran the same ops, over two rounds: per chip and round
    tr = Reduction({0: evs, 1: list(evs)}, [], (0.0, 1.0))
    got = {n: reader(n).read(ctx(tr, chips=2, rounds=2)) for n in NEW[:3]}
    assert got["pod.moe_route_ms"] == pytest.approx(1e3 * 0.017 / 2)
    assert got["pod.moe_experts_ms"] == pytest.approx(1e3 * 0.065 / 2)
    assert got["pod.attention_ms"] == pytest.approx(1e3 * 0.040 / 2)


@pytest.mark.parametrize("new,old", [("pod4.sync_ms", "pod.sync_ms"),
                                     ("pod4.scatter_apply_ms",
                                      "pod.scatter_apply_ms")])
def test_four_pod_readers_read_what_the_one_pod_readers_read(new, old):
    ops = [("magnitude_hist.1", 0.05, "jit(step)/pod_sync.compact_pack/"
            "topk.threshold/pallas_call"),
           ("all-gather.2", 0.02, "jit(step)/pod_sync.all_gather/"
            "all_gather"),
           ("expand_blocks.3", 0.08, "jit(step)/pod_sync.scatter_apply/"
            "jit(expand_blocks)/pallas_call"),
           ("fusion.4", 0.30, LOCAL + "dot_general")]
    evs, t = [], 0.0
    for name, dur, op_name in ops:
        evs.append(Event(name, t, t + dur, op_name))
        t += dur
    tr = Reduction({q: list(evs) for q in range(4)}, [], (0.0, 1.0))
    got = reader(new).read(ctx(tr, chips=4, rounds=3))
    assert got == reader(old).read(ctx(tr, chips=4, rounds=3))
    want = {"pod4.sync_ms": 0.15, "pod4.scatter_apply_ms": 0.08}[new]
    assert got == pytest.approx(1e3 * want / 3)


def test_experts_reader_counts_the_grouped_matmul_kernels():
    """The held experts' Pallas kernels carry the `moe.experts` scope in
    the forward, its recompute and the backward (op names as the v5e
    compile gives them); ops of no MoE scope stay out."""
    body = "/while/body/closed_call/"
    evs = [Event("gmm.1", 0.0, 0.2, LOCAL + "jvp()" + body + "moe.experts"
                 + body + "jit(gmm)/pallas_call"),
           Event("gmm.2", 0.2, 0.3, LOCAL + "transpose(jvp())" + body
                 + "checkpoint/rematted_computation/moe.experts" + body
                 + "jit(gmm)/pallas_call"),
           Event("tgmm.3", 0.3, 0.45, LOCAL + "transpose(jvp())" + body
                 + "checkpoint/moe.experts" + body + "jit(tgmm)/pallas_call"),
           Event("fusion.1", 0.45, 0.5, LOCAL + "moe.shared/dot_general"),
           Event("fusion.2", 0.5, 0.6, ""),
           Event("fusion.3", 0.6, 0.7, LOCAL + "moe.dispatch/gather")]
    tr = Reduction({0: evs}, [], (0.0, 1.0))
    assert reader("pod.moe_experts_ms").read(ctx(tr)) == \
        pytest.approx(1e3 * 0.5)
