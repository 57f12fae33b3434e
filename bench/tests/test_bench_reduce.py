"""bench/reduce.py on a trace recorded on a TPU v5e (bench/testdata/
tiny.xplane.pb): a small jitted program (a matmul, then a magnitude
histogram through the Pallas kernel) run three times, each call inside a
`bench.stepN` host span and followed by a 3 ms `bench.sleep` span. The
expected values are worked out by hand from the trace's raw events."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import reduce  # noqa: E402

TRACE = str(ROOT / "bench" / "testdata" / "tiny.xplane.pb")
NS = 1e-9
# the device clock runs 1,280,430 ns behind the host's: run 7 was enqueued
# at 61,680,479 ns and started on the device at 60,400,049 ns
SHIFT = 1280430
# first host span start, last host span end
WINDOW = (52343927, 62307837 + 3281020)
# per call, the union of its 7 op intervals, from starts and durations
BUSY = (2534 + 366 + 172 + 33 + 1938 + 448,
        2760 + 1 + 366 + 171 + 32 + 1937 + 446,
        2545 + 1 + 365 + 171 + 35 + 1938 + 446)


@pytest.fixture(scope="module")
def tr():
    return reduce.reduce_file(TRACE, 1)


def test_window_and_busy_union(tr):
    assert tr.window_s == pytest.approx((WINDOW[1] - WINDOW[0]) * NS,
                                        abs=1e-12)
    assert tr.busy_s == pytest.approx(sum(BUSY) * NS, abs=1e-12)
    assert len(tr.devices[0]) == 21
    idle = 1 - tr.busy_s / tr.window_s
    assert idle == pytest.approx(1 - sum(BUSY) / (WINDOW[1] - WINDOW[0]))


def test_clock_shift(tr):
    first = min(e.start for e in tr.devices[0])
    assert first == pytest.approx((51620470 + SHIFT) * NS, abs=1e-12)


def test_ops_by_name(tr):
    ops = tr.by_op()
    assert ops["magnitude_hist.1"] == pytest.approx(
        (1938 + 1937 + 1938) * NS, abs=1e-12)
    assert ops["reduce"] == pytest.approx((448 + 446 + 446) * NS, abs=1e-12)
    assert len(tr.kernel_calls(("magnitude_hist",))) == 3


def test_gaps_are_put_down_to_the_host_span_over_them(tr):
    gaps = tr.gaps()
    # call 1 ends at 51,625,965 + shift; call 2 starts at 56,033,310 +
    # shift; the 3.3 ms sleep covers three quarters of that gap
    assert gaps[0] == ("bench.sleep",
                       pytest.approx((56033310 - 51625965) * NS, abs=1e-12))
    # before call 1: only bench.step0 was open
    before = [g for g in gaps if g[0] == "bench.step0"]
    assert before[0][1] == pytest.approx(
        (51620470 + SHIFT - WINDOW[0]) * NS, abs=1e-12)
    assert sum(g[1] for g in gaps) == pytest.approx(
        tr.window_s - tr.busy_s, abs=1e-12)
    b = tr.breakdown()
    assert b["device_ops"][0][0] == "abs_reduce_fusion"
    assert b["idle_gaps"][0][0] == "bench.sleep"


def test_scopes_from_hlo_text():
    hlo = "\n".join([
        '  %fusion.3 = f32[9,1]{1,0} fusion(f32[] %p), kind=kLoop, '
        'calls=%fc, metadata={op_name="jit(tiny)/outer_b/mul" '
        'source_file="t.py" source_line=3}',
        '  ROOT %magnitude_hist.1 = f32[9,1]{1,0} custom-call(f32[64,1024] '
        '%r), custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(tiny)/outer_b/jit(magnitude_hist)/pallas_call"}',
        '  %abs_reduce_fusion = (f32[], f32[256,256]) fusion(%x), '
        'metadata={op_name="jit(tiny)/outer_a/dot_general"}',
        '  %copy-start = (f32[256,256]) copy-start(%y)'])
    names = reduce.op_names(hlo)
    assert names == {
        "fusion.3": "jit(tiny)/outer_b/mul",
        "magnitude_hist.1":
            "jit(tiny)/outer_b/jit(magnitude_hist)/pallas_call",
        "abs_reduce_fusion": "jit(tiny)/outer_a/dot_general"}
    tr = reduce.reduce_file(TRACE, 1, names)
    assert tr.by_scope("outer_b") == pytest.approx(
        (366 + 366 + 365 + 1938 + 1937 + 1938) * NS, abs=1e-12)
    assert tr.by_scope("outer_a") == pytest.approx(
        (2531 + 2760 + 2545) * NS, abs=1e-12)
    assert tr.by_scope("outer") == 0.0


def test_union():
    assert reduce.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
