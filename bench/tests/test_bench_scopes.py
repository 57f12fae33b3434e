"""The pod round's named scopes in the compiled step's op metadata, on the
tiny pod step (bench/tests/tiny.py), built once: every scope that a
`pod.*` reader reads is there, the finer scopes nest inside the layers the
older readers count, and every op the program's own code made carries
exactly one layer scope."""
import ast
import re

import pytest

import tiny
from bench import harness, reduce
from bench.reduce import Event, Reduction

METRICS = harness.BENCH / "metrics"
LAYOUT = harness.load_module(METRICS / "pod.layout_ms.py")
UNSCOPED = harness.load_module(METRICS / "pod.unscoped_ms.py")
# ops that do no work of their own
FREE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast"}
COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.-]+) = .*?\b([a-z][\w-]*)\(")
OP_NAME = re.compile(r'op_name="([^"]*)"')
WIRES = ("compact", "dense")


def reader_scopes(name: str) -> set[str]:
    """The scopes a reader gives `Reduction.by_scope`: the strings of its
    `SCOPES`, and those it passes to `by_scope` itself."""
    tree = ast.parse((METRICS / f"{name}.py").read_text())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and any(
                getattr(t, "id", "") == "SCOPES" for t in node.targets):
            out |= {c.value for c in ast.walk(node.value)
                    if isinstance(c, ast.Constant)}
        elif isinstance(node, ast.Call) and \
                getattr(node.func, "attr", "") == "by_scope":
            out |= {a.value for a in node.args if isinstance(a, ast.Constant)}
    return out


READERS = sorted(p.stem for p in METRICS.glob("pod.*.py")
                 if reader_scopes(p.stem))


def under(op_name: str, scope: str) -> bool:
    """Whether `by_scope(scope)` counts an op with this op name."""
    return Reduction({0: [Event("op", 0.0, 1.0, op_name)]}, [],
                     (0.0, 1.0)).by_scope(scope) > 0


def top_level_ops(hlo: str) -> list[tuple[str, str, str]]:
    """(instruction, opcode, op name) of each op outside a fusion body or a
    reducer: the ops the device trace records as events."""
    comps: dict[str, list[str]] = {}
    cur = None
    for line in hlo.splitlines():
        m = COMPUTATION.match(line)
        if m and not line.startswith(" "):
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None:
            cur.append(line)
    inner = set()
    for line in hlo.splitlines():
        if " fusion(" in line:
            inner |= set(re.findall(r"calls=%?([\w.-]+)", line))
        inner |= set(re.findall(r"to_apply=%?([\w.-]+)", line))
    out = []
    for comp, lines in comps.items():
        if comp in inner:
            continue
        for line in lines:
            m = INSTRUCTION.match(line)
            if m and m.group(2) not in FREE:
                name = OP_NAME.search(line)
                out.append((m.group(1), m.group(2),
                            name.group(1) if name else ""))
    return out


@pytest.fixture(scope="module")
def hlos():
    """The compiled step's HLO on each wire of the sync, built once."""
    r = tiny.cell("pod")
    path = harness.load_module(harness.BENCH / "paths" / "pod.py")
    out = {}
    for wire in WIRES:
        run = path.Cell(r["cfg"], r["model"], dict(r["mix"], wire=wire), 1,
                        2 ** 31 + 13)
        run.build()
        out[wire] = run.hlo_text()
    return out


def event_scopes(hlo: str) -> list[str]:
    """The scope the trace reduction gives each top-level op's events."""
    names = reduce.op_names(hlo)
    return [names[op] for op, _, _ in top_level_ops(hlo) if op in names]


@pytest.mark.parametrize("reader", READERS)
def test_every_scope_a_reader_reads_is_in_the_metadata(hlos, reader):
    names = [n for text in hlos.values() for n in event_scopes(text)]
    for scope in reader_scopes(reader):
        assert any(under(n, scope) for n in names), (reader, scope)


@pytest.mark.parametrize("wire,inner,outer", [
    ("compact", "topk.threshold", "pod_sync.compact_pack"),
    ("compact", "topk.pack", "pod_sync.compact_pack"),
    ("dense", "topk.threshold", "pod_sync.dense"),
    ("compact", "opt.update", "local_round"),
])
def test_finer_scopes_nest_in_the_layer_the_older_reader_counts(
        hlos, wire, inner, outer):
    ops = [n for n in event_scopes(hlos[wire]) if under(n, inner)]
    assert ops, inner
    assert all(under(n, outer) for n in ops), \
        [n for n in ops if not under(n, outer)][:5]


@pytest.mark.parametrize("wire", WIRES)
def test_every_op_of_the_program_has_one_layer(hlos, wire):
    older = reader_scopes("pod.local_round_ms") | reader_scopes("pod.sync_ms")
    # Exception: an op with no op name at all. The compiler made it (a
    # layout copy, a dot rewritten by the CPU backend, a reduce-window it
    # wraps), no named scope can reach it, and on the chip its time is
    # what pod.unscoped_ms reads.
    ops = [op for op in top_level_ops(hlos[wire]) if op[2]]
    assert len(ops) > 100
    wrong = [(name, opcode, op_name) for name, opcode, op_name in ops
             if sum(under(op_name, s) for s in older)
             + LAYOUT.counts(op_name) != 1
             or not UNSCOPED.LAYER.search(op_name)]
    assert not wrong, wrong[:10]
