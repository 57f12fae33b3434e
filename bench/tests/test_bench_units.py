"""The benchmark's own arithmetic: peak lookup, exact selection, the
comparison's gaps and verdict, the traffic generators, and the run's
refusal to report without a TPU."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import check, peaks, traffic  # noqa: E402


def test_peaks_known_kind():
    p = peaks.lookup("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["ici_bits_per_s"] == 1600e9 and "Google Cloud" in p["source"]


def test_peaks_unknown_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.lookup("TPU v9 imaginary")


@pytest.mark.parametrize("k", [1, 7, 64, 500])
def test_topk_mask_matches_a_stable_sort(k):
    import jax.numpy as jnp

    from bench.selection import kth_largest_abs, topk_mask
    rng = np.random.RandomState(k)
    x = rng.randn(1000).astype(np.float32)
    x[::50] = x[0]                     # ties, resolved by index
    order = np.argsort(-np.abs(x), kind="stable")
    want = np.zeros(1000, bool)
    want[order[:k]] = True
    got = np.asarray(topk_mask(jnp.asarray(x), k))
    assert got.sum() == k and np.array_equal(got, want)
    assert float(kth_largest_abs(jnp.asarray(x), k)) == abs(x[order[k - 1]])


def test_block_budget_mask_truncates_in_index_order():
    import jax.numpy as jnp

    from bench.selection import block_budget_mask
    acc = np.zeros((2, 8), np.float32)
    acc[0] = [5, 4, 3, 2, 1, 0, 0, 0]
    acc[1] = [0, 0, 6, 0, 0, 0, 0, 7]
    m = np.asarray(block_budget_mask(jnp.asarray(acc), 5, 2))
    # the 5th largest is 3: survivors 5,4,3 | 6,7; budget 2 keeps 5,4 | 6,7
    assert m.tolist() == [[True, True, False, False, False, False, False,
                           False],
                          [False, False, True, False, False, False, False,
                           True]]


def test_gaps_and_verdict():
    ref = {"loss": [2.0, 1.0, 0.5], "update": [1.0, 2.0, 3.0, 1e-9],
           "change": [1.0, 4.0, 4.0, 5.0]}
    prog = {"loss": [2.2, 1.0, 0.5], "update": [1.5, 2.0, 3.0, 1.0],
            "change": [1.0, 4.0, 4.0, 4.0]}
    g = check.gaps(prog, ref)
    assert g["loss.r1"] == pytest.approx(0.1) and g["loss.r2"] == 0.0
    # leaf 0 is measured against the median leaf (1.5): 0.5 / 1.5; the
    # last leaf moves by round-off in the reference and is left out
    assert g["update"] == pytest.approx(0.5 / 1.5)
    assert g["change"] == 0.0
    ok, table = check.verdict(g, {"loss.r1": 0.2, "update": 0.3})
    assert not ok and table["update"]["limit"] == 0.3
    assert check.verdict(g, {"loss.r1": 0.2, "update": 0.4})[0]
    assert not check.verdict({"x": float("nan")}, {"x": 1.0})[0]


def test_leaf_layout_and_norms():
    import jax.numpy as jnp
    tree = {"b": jnp.ones((2, 3)), "a": jnp.ones((4,))}
    names, spans = check.leaf_layout(tree)
    assert names == ["['a']", "['b']"] and spans == [(0, 4), (4, 10)]
    flat = jnp.arange(10.0)
    got = np.asarray(check.leaf_norms_fn(spans)(flat))
    assert np.allclose(got, [np.linalg.norm(np.arange(4.0)),
                             np.linalg.norm(np.arange(4.0, 10.0))])


def test_token_stream_is_seeded_and_in_range():
    mix = dict(traffic.load("sync_heavy"), stream_rounds=3)
    a = traffic.token_stream(mix, 5, 1000, 1)
    assert a.shape == (3, 1, 2, 4, 129) and a.dtype == np.int32
    assert np.array_equal(a, traffic.token_stream(mix, 5, 1000, 1))
    assert not np.array_equal(a, traffic.token_stream(mix, 6, 1000, 1))
    assert a.min() >= 0 and a.max() < 1000


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         json.loads((ROOT / "BENCHMARK.json").read_text())
         ["workloads"][0]["name"], "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    p = _run(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "TPU" in p.stderr


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and "{" not in p.stdout
