"""Kernel microbenchmarks: the Pallas pipeline against the pure-jnp oracle
and the exact lax.top_k path. Kernels compile for a TPU and run in the
Pallas interpreter on the CPU backend, where the timings say nothing about
the chip.

The pod-sync section measures the compact (values, indices, count) wire
format of `dist.collectives.make_pod_sync` across the density crossover on
an 8-device host mesh (P=4 pods × 2 shards): per-device bytes-on-wire from
the *actual payload arrays*, the analytic `all_gather_bytes` model, the
dense-carrier cost and the CPU wall time per sync of both paths — plus the
compact-vs-reference equivalence gate (fp32 params, bitwise EF residuals).
It counts bytes, so it runs on the CPU backend only: `use_host_mesh`
pins the CPU platform and the host device count before a backend starts.

  PYTHONPATH=src python benchmarks/kernel_bench.py --smoke
  PYTHONPATH=src python benchmarks/kernel_bench.py --out BENCH_podsync.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np


def _time(fn, *args, iters=3):
    import jax
    fn(*args)                      # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def kernel_microbench():
    import jax
    import jax.numpy as jnp
    from repro.core.compression import topk
    from repro.kernels import ops, ref

    backend = jax.default_backend()
    rows = []
    rng = np.random.RandomState(0)
    for d in (1 << 16, 1 << 20):
        g = jnp.asarray(rng.randn(d).astype(np.float32))
        res = jnp.zeros(d)

        us = _time(lambda g, r: ops.topk_compress(g, r, rate=0.01), g, res)
        rows.append((f"pallas_topk_compress_d{d}", us, backend))

        exact = jax.jit(lambda g: topk(g, 0.01).dense())
        rows.append((f"exact_lax_topk_d{d}", _time(exact, g), "oracle"))

        mu = jnp.zeros(d)
        us = _time(lambda w, m, gg: ops.momentum_update(
            w, m, gg, lr=0.01), g, mu, g)
        rows.append((f"pallas_fused_momentum_d{d}", us, backend))

        unfused = jax.jit(lambda w, m, gg: ref.ref_fused_momentum(
            w, m, gg, lr=0.01))
        rows.append((f"unfused_momentum_d{d}", _time(unfused, g, mu, g),
                     "oracle"))
    return rows


def sync_crossover():
    """δ-adaptive collective: analytic wire bytes per sync vs δ (documents
    the sparse/dense crossover used by dist.collectives)."""
    from repro.dist.collectives import all_gather_bytes, density_crossover
    d, P = 100_000_000, 2
    rows = []
    for rate in (1e-4, 1e-3, 1e-2, density_crossover(P), 0.5, 1.0):
        b = all_gather_bytes(d, P, rate, n_blocks=12_500)  # blk = 8192
        rows.append((f"sync_wire_bytes_delta{rate:g}", 0.0,
                     f"{b/1e6:.1f}MB"))
    return rows


# ---------------------------------------------------------------- pod-sync
def run_podsync(smoke: bool = False) -> tuple[dict, list[str]]:
    """Sweep the compact pod-sync across the density crossover.

    Returns (report, failures). Needs >= 8 jax devices (use `main`, which
    forces the host platform device count before importing jax).
    """
    import jax
    import jax.numpy as jnp
    from repro.dist import collectives as col
    from repro.launch.mesh import device_info
    from repro.kernels import ops

    n_pods, n_data, n_model = 4, 2, 1
    mesh = jax.make_mesh(
        (n_pods, n_data, n_model), ("pod", "data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 3)
    n_shards = n_data * n_model
    if smoke:
        nb, blk, rates, iters, rounds = 8, 128, (0.05, 0.4), 2, 3
    else:
        nb, blk = 64, 512
        rates = (0.01, 0.02, 0.05, 0.1, 0.2, 0.25, 0.4, 0.6)
        iters, rounds = 3, 3
    dim = nb * blk
    nbl = nb // n_shards
    dim_local = dim // n_shards
    crossover = col.density_crossover(n_pods)
    rng = np.random.RandomState(0)
    params = jnp.asarray(rng.randn(nb, blk).astype(np.float32))
    deltas = jnp.asarray(rng.randn(n_pods, nb, blk).astype(np.float32))
    zeros = jnp.zeros((n_pods, nb, blk), jnp.float32)

    def wall(sync):
        fn = jax.jit(sync)
        out = fn(params, deltas, zeros)           # compile
        jax.block_until_ready(out)
        t0 = time.time()
        for _ in range(iters):
            out = fn(params, deltas, zeros)
        jax.block_until_ready(out)
        return (time.time() - t0) / iters * 1e6

    failures: list[str] = []
    cells = []
    for rate in rates:
        compact = col.make_pod_sync(mesh, dim, rate=rate, n_blocks=nb,
                                    wire="compact")
        reference = col.make_pod_sync(mesh, dim, rate=rate, n_blocks=nb,
                                      wire="reference")
        dense = col.make_pod_sync(mesh, dim, rate=rate, n_blocks=nb,
                                  wire="dense")
        auto = col.make_pod_sync(mesh, dim, rate=rate, n_blocks=nb)

        # measured bytes: the concrete payload arrays one shard ships to
        # each of the P-1 peers (values + indices + count headers)
        acc_shard = deltas[0, :nbl].astype(jnp.float32)
        v, i, c, _ = ops.compact_shard_topk(acc_shard,
                                            budget=compact.wire.budget)
        measured = (n_pods - 1) * (np.asarray(v).nbytes
                                   + np.asarray(i).nbytes
                                   + np.asarray(c).nbytes)
        model = col.all_gather_bytes(dim_local, n_pods, rate, n_blocks=nbl)
        dense_bytes = 2.0 * (n_pods - 1) / n_pods * dim_local * 4

        # equivalence gate: compact vs dense-carrier reference, EF carried
        pc, rc = params, zeros
        pr, rr = params, zeros
        jc, jr = jax.jit(compact), jax.jit(reference)
        for rnd in range(rounds):
            d_r = deltas if rnd == 0 else jnp.roll(deltas, rnd, axis=0)
            pc, rc = jc(pc, d_r, rc)
            pr, rr = jr(pr, d_r, rr)
        params_close = bool(np.allclose(np.asarray(pc), np.asarray(pr),
                                        rtol=1e-5, atol=1e-6))
        res_equal = bool(jnp.array_equal(rc, rr))
        if not (params_close and res_equal):
            failures.append(f"equivalence δ={rate}: params_close="
                            f"{params_close} res_equal={res_equal}")
        if rate < crossover and abs(measured - model) > 0.05 * model:
            failures.append(f"wire model mismatch δ={rate}: measured="
                            f"{measured}B model={model}B")

        cells.append({
            "rate": rate,
            "auto_path": auto.path,
            "budget_per_block": compact.wire.budget,
            "measured_bytes_per_device": int(measured),
            "model_bytes_per_device": float(model),
            "dense_bytes_per_device": float(dense_bytes),
            "compact_over_dense": round(measured / dense_bytes, 4),
            "wall_us_compact": round(wall(compact), 1),
            "wall_us_dense": round(wall(dense), 1),
            "params_match_reference": params_close,
            "residuals_bitwise_reference": res_equal,
        })

    by_rate = {c["rate"]: c for c in cells}
    if 0.05 in by_rate:
        c05 = by_rate[0.05]
        ratio = c05["dense_bytes_per_device"] / \
            c05["measured_bytes_per_device"]
        if ratio < 4.0:
            failures.append(f"δ=0.05 compact only {ratio:.2f}x smaller "
                            "than dense (need >= 4x)")

    report = {
        "bench": "podsync_wire_bytes",
        "mode": "smoke" if smoke else "full",
        "device": device_info(),
        "mesh": {"pod": n_pods, "data": n_data, "model": n_model},
        "dim": dim, "n_blocks": nb, "blk": blk,
        "dim_per_shard": dim_local,
        "density_crossover": crossover,
        "unit": "bytes per device per sync; interpret-mode wall us "
                "(relative only on CPU)",
        "methodology": "measured bytes come from the concrete compact "
                       "payload arrays (values+indices+count headers) "
                       f"x (P-1) peers; equivalence gate runs {rounds} "
                       "EF rounds compact vs dense-carrier reference",
        "cells": cells,
        "failures": failures,
    }
    return report, failures


def use_host_mesh() -> None:
    """Put the pod mesh on 8 host devices of the CPU backend. Call before
    JAX initializes a backend."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")


def podsync_rows():
    """CSV rows for benchmarks.run, whose `--one podsync` child has not
    initialized a backend yet."""
    use_host_mesh()
    rep, failures = run_podsync(smoke=True)
    if failures:
        raise RuntimeError("podsync smoke failed: " + "; ".join(failures))
    rows = []
    for c in rep["cells"]:
        rows.append((f"podsync_bytes_delta{c['rate']:g}",
                     c["wall_us_compact"],
                     f"{c['measured_bytes_per_device']}B/"
                     f"{int(c['dense_bytes_per_device'])}B"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dim / two rates (CI smoke job)")
    ap.add_argument("--out", default="",
                    help="write the JSON report here (default: stdout only)")
    args = ap.parse_args(argv)

    use_host_mesh()
    from repro.launch import compile_cache
    compile_cache.enable()
    report, failures = run_podsync(smoke=args.smoke)
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        print(f"[kernel_bench] wrote {args.out}", file=sys.stderr)
    if failures:
        print("[kernel_bench] podsync FAIL:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
