"""The Pallas kernels compile for a TPU v5e at real widths, and the chip
entry point refuses to run without a chip.

No chip is needed to compile: the TPU compiler builds for the `v5e:2x2`
topology described in the `one_chip` fixture and refuses what the chip
would refuse (blocks not aligned to the (8, 128) tile, primitives Mosaic
cannot lower, too much VMEM). Widths are those of `chip_smoke.py`'s
kernels phase: the flat dim of the cnn_fmnist model (conv 32/64, fc 512)
and the pod-sync block of 1024 at δ = 0.05.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
D = 1_663_370            # cnn_fmnist flat dim
BLK, BUDGET = 1024, 51   # pod-sync block and its δ = 0.05 budget
NB = -(-D // BLK)


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compile cache
    off (an entry compiled for a described chip cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)


def _cases(sharding):
    from repro.kernels import ops
    from repro.kernels.compact_topk import compact_blocks
    from repro.kernels.ef_topk import ef_topk
    from repro.kernels.fused_momentum import fused_momentum
    from repro.kernels.magnitude_hist import magnitude_hist

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    return {
        "magnitude_hist": (lambda g, e: magnitude_hist(g, e),
                           (f32(D), f32(49))),
        "ef_topk": (lambda g, r, t: ef_topk(g, r, t),
                    (f32(D), f32(D), f32())),
        "compact_blocks": (lambda a, t: compact_blocks(a, t, budget=BUDGET),
                           (f32(NB, BLK), f32())),
        "fused_momentum": (lambda w, m, g: fused_momentum(w, m, g, lr=0.05),
                           (f32(D), f32(D), f32(D))),
        "compact_shard_topk": (lambda a: ops.compact_shard_topk(
            a, budget=BUDGET, interpret=False), (f32(NB, BLK),)),
    }


@pytest.mark.parametrize("name", ["magnitude_hist", "ef_topk",
                                  "compact_blocks", "fused_momentum",
                                  "compact_shard_topk"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _cases(one_chip)[name]
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


# ------------------------------------------------------ chip entry point
def _run_smoke(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("PYTHONPATH", None)     # the script finds `repro` on its own
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _ok_lines(stdout: str) -> list:
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("ok") is True:
            out.append(obj)
    return out


def test_chip_smoke_fails_without_a_chip():
    r = _run_smoke(ROOT)
    assert r.returncode != 0, r.stdout
    assert not _ok_lines(r.stdout), r.stdout
    assert "needs 1 TPU chip" in r.stderr


def test_chip_smoke_fails_outside_the_repo(tmp_path):
    """Alone in a directory, the script finds no `repro` to run: it fails
    at that import, before it looks for a chip."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_smoke(tmp_path)
    assert r.returncode != 0, r.stdout
    assert not _ok_lines(r.stdout), r.stdout
    assert "No module named 'repro'" in r.stderr, r.stderr[-2000:]
    assert "needs 1 TPU chip" not in r.stderr


# ------------------------------------------------ no hidden device choice
def _python(code: str, **env_extra):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_importing_the_pod_sync_starts_no_backend():
    out = _python("import repro.dist.collectives, repro.kernels.ops\n"
                  "from jax._src import xla_bridge\n"
                  "print(xla_bridge.backends_are_initialized())")
    assert out.strip() == "False"


def test_bench_harness_parent_stays_off_jax():
    code = ("import sys\n"
            f"sys.path.insert(0, {os.path.realpath(ROOT)!r})\n"
            "import benchmarks.run\n"
            "print('jax' in sys.modules)")
    assert _python(code).strip() == "False"


def test_compile_cache_goes_where_the_variable_says(tmp_path):
    out = _python(
        "import jax\n"
        "from repro.launch import compile_cache\n"
        "print(compile_cache.enable())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(1.0).block_until_ready()",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert out.split() == [str(tmp_path)] * 2
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout():
    from repro.launch import compile_cache
    out = _python("from repro.launch import compile_cache\n"
                  "print(compile_cache.enable())",
                  JAX_COMPILATION_CACHE_DIR="")
    want = os.path.join(os.path.realpath(ROOT), ".jax_cache")
    assert out.strip() == want == str(compile_cache.DEFAULT_DIR)
