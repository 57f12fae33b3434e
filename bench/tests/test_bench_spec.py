"""BENCHMARK.json resolves: every cell's configuration, traffic mix, limits
and metric readers are found by name, and every name, unit and text
field keeps to the benchmark's rules."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import harness, traffic  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
CELLS = [w["name"] for w in SPEC["workloads"]]


def text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_size():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert (ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= len(SPEC["command"]) <= 32 and all(map(text, SPEC["command"]))
    assert 1 <= len(SPEC["paths"]) <= 16
    for p in SPEC["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (ROOT / p).is_dir()
    for word in SPEC["command"][1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert any(word.startswith(p + "/") for p in SPEC["paths"])


def test_run_seconds_fits_the_check_with_24_cells():
    s = SPEC["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units():
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for group in (SPEC["configs"], SPEC["workloads"], metrics):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    for m in metrics:
        assert UNIT.match(m["unit"]), m["name"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


@pytest.mark.parametrize("conf", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert text(conf["source"]) and text(conf["why"])
    f = ROOT / conf["file"]
    assert any(conf["file"].startswith(p + "/") for p in SPEC["paths"])
    cfg = json.loads(f.read_text())
    assert isinstance(cfg, dict)
    assert f.with_suffix(".py").is_file()
    assert len(conf["reduced"]) <= 16
    assert all(NAME.match(k) for k in conf["reduced"])
    assert any(w["config"] == conf["name"] for w in SPEC["workloads"])
    files = [c["file"] for c in SPEC["configs"]]
    assert files.count(conf["file"]) == 1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves(cell):
    w = next(w for w in SPEC["workloads"] if w["name"] == cell)
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert w["chips"] in (1, 4) and text(w["why"])
    assert NAME.match(w["traffic"])
    r = harness.resolve(cell, SPEC)
    assert (harness.BENCH / "paths" / f"{r['mix']['path']}.py").is_file()
    e2e = {m["name"] for m in r["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert r["per_layer"]
    for m in r["end_to_end"] + r["per_layer"]:
        assert hasattr(harness.load_module(
            harness.BENCH / "metrics" / f"{m['name']}.py"), "read")
    for m in r["per_layer"]:
        assert m["moves"] in e2e
    assert set(r["limits"]) <= {"loss.r1", "loss.r2", "loss.r3", "update",
                                "change"} and r["limits"]
    assert all(v > 0 for v in r["limits"].values())


def test_pairs_unique_and_four_chip_share():
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(CELLS) // 2)


def test_metric_fields():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in SOURCES_E2E
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert text(m["layer"]) and m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)


def test_traffic_mixes_load():
    for w in SPEC["workloads"]:
        path = traffic.load(w["traffic"])["path"]
        assert (harness.BENCH / "paths" / f"{path}.py").is_file()


def test_traffic_mix_without_a_path_module_is_refused(tmp_path):
    (tmp_path / "odd.json").write_text('{"path": "nowhere"}')
    with pytest.raises(ValueError, match="nowhere"):
        traffic.load("odd", tmp_path)
