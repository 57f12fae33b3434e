"""The hybrid's named scopes in the compiled step's op metadata, on the
tiny Nemotron pod step (bench/tests/tiny_hybrid.py), built once: every
scope that `pod.moe_route_ms`, `pod.moe_experts_ms` and `pod.attention_ms`
read is there, forward and backward, and nests inside `local_round`."""
import pytest

import tiny_hybrid
from bench import harness
from test_bench_scopes import event_scopes, under

READERS = ("pod.moe_route_ms", "pod.moe_experts_ms", "pod.attention_ms")


def scopes(reader):
    return harness.load_module(harness.BENCH / "metrics"
                               / f"{reader}.py").LAYER_SCOPES


@pytest.fixture(scope="module")
def names():
    r = tiny_hybrid.cell()
    path = harness.load_module(harness.BENCH / "paths" / "pod.py")
    run = path.Cell(r["cfg"], r["model"], r["mix"], 1, 2 ** 31 + 13)
    run.build()
    return event_scopes(run.hlo_text())


@pytest.mark.parametrize("scope", [s for r in READERS for s in scopes(r)])
def test_each_scope_is_in_the_metadata_forward_and_backward(names, scope):
    ops = [n for n in names if under(n, scope)]
    assert any("transpose(" not in n for n in ops), scope
    assert any("transpose(" in n for n in ops), scope


@pytest.mark.parametrize("scope", [s for r in READERS for s in scopes(r)])
def test_each_scope_nests_in_the_local_round(names, scope):
    ops = [n for n in names if under(n, scope)]
    assert ops and all(under(n, "local_round") for n in ops), \
        [n for n in ops if not under(n, "local_round")][:5]


def test_the_readers_share_no_op(names):
    for n in names:
        assert sum(any(under(n, s) for s in scopes(r)) for r in READERS) \
            <= 1, n
