"""Jit'd public wrappers around the Pallas kernels.

`topk_compress` is the full TPU-native top-k pipeline (DESIGN.md §3):

  pass 0  gmax = max|g|                       (XLA reduce)
  pass 1  coarse log2-bucket histogram        (magnitude_hist kernel)
  pass 2  fine linear histogram inside bucket (magnitude_hist kernel)
  solve   threshold t s.t. #{|g+r| >= t} ~= δ·d   (O(buckets), on-chip)
  pass 3  fused EF select                     (ef_topk kernel)

`interpret=None` (the default) is resolved when a wrapper is traced: the
kernels run in the Pallas interpreter on the CPU backend and compile to
Mosaic on TPU. Importing this module never starts a backend. All wrappers
are shape-polymorphic over flat [d] inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.compact_topk import compact_blocks
from repro.kernels.ef_topk import ef_topk
from repro.kernels.fused_momentum import fused_momentum
from repro.kernels.magnitude_hist import magnitude_hist


def resolve_interpret(interpret: bool | None) -> bool:
    """`interpret` as given, else True exactly on the CPU backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def _solve_threshold(counts_ge: jax.Array, edges: jax.Array, k) -> tuple:
    """Pick (lo, hi) bracket: largest edge with count >= k and the edge
    above it. edges descending; counts_ge monotone nondecreasing."""
    reached = counts_ge >= k
    sel = jnp.argmax(reached)                  # first True (or 0 if none)
    any_reached = jnp.any(reached)
    sel = jnp.where(any_reached, sel, edges.shape[0] - 1)
    hi = edges[jnp.maximum(sel - 1, 0)]
    lo = edges[sel]
    return lo, hi


@functools.partial(jax.jit,
                   static_argnames=("coarse_buckets", "fine_buckets",
                                    "block", "interpret", "axis_name"))
def solve_threshold(acc: jax.Array, k, *, coarse_buckets: int = 48,
                    fine_buckets: int = 128, block: int = 8 * 1024,
                    interpret: bool | None = None,
                    axis_name=None) -> jax.Array:
    """Histogram-pipeline threshold t with #{|acc| >= t} ≈ k (passes 0–2 of
    the top-k pipeline; `k` may be traced). Shared by `topk_compress` and
    the pod-sync compact path, so both select against identical thresholds.

    With `axis_name` (inside `shard_map`), `acc` is this chip's shard of a
    vector split over those mesh axes, and t is the whole vector's
    threshold: the max and the histogram counts are reduced over the axes.
    """
    interpret = resolve_interpret(interpret)

    def hist(edges):
        counts = magnitude_hist(acc, edges, block=block, interpret=interpret)
        return counts if axis_name is None else \
            jax.lax.psum(counts, axis_name)

    with jax.named_scope("topk.threshold"):
        gmax = jnp.max(jnp.abs(acc))
        if axis_name is not None:
            gmax = jax.lax.pmax(gmax, axis_name)
        gmax = gmax + 1e-30

        # pass 1: coarse log2 buckets
        coarse_edges = gmax * 2.0 ** (-jnp.arange(coarse_buckets + 1,
                                                  dtype=jnp.float32))
        lo, hi = _solve_threshold(hist(coarse_edges), coarse_edges, k)

        # pass 2: fine linear buckets inside [lo, hi]
        frac = jnp.arange(fine_buckets + 1, dtype=jnp.float32) / fine_buckets
        fine_edges = hi - (hi - lo) * frac         # descending hi -> lo
        fine_edges = jnp.maximum(fine_edges, 1e-30)
        _, t = _solve_threshold(hist(fine_edges), fine_edges, k)
    return t


@functools.partial(jax.jit,
                   static_argnames=("rate", "coarse_buckets", "fine_buckets",
                                    "block", "interpret"))
def topk_compress(g: jax.Array, residual: jax.Array, *, rate: float,
                  coarse_buckets: int = 48, fine_buckets: int = 128,
                  block: int = 8 * 1024, interpret: bool | None = None):
    """Error-feedback threshold top-k at density `rate` (δ = k/d).

    Returns (out_dense, new_residual, nnz, threshold). Selection matches
    exact top-|.|-k up to threshold-resolution ties: nnz ∈ [~k, k(1+ε)]
    with ε bounded by the fine bucket width (tested in test_kernels).
    """
    interpret = resolve_interpret(interpret)
    d = g.shape[0]
    k = max(1, min(d, int(round(rate * d))))
    # NOTE: threshold statistics must be over the EF accumulator, since
    # pass 3 selects on |g + residual|.
    acc_stat_src = g.astype(jnp.float32) + residual.astype(jnp.float32)
    t = solve_threshold(acc_stat_src, k, coarse_buckets=coarse_buckets,
                        fine_buckets=fine_buckets, block=block,
                        interpret=interpret)
    out, new_res, nnz = ef_topk(g, residual, t, block=block,
                                interpret=interpret)
    return out, new_res, nnz, t


def compact_topk(dense: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """Compact a dense masked vector to the (values, indices) wire format.

    Picks the `k` largest-|.| coordinates of `dense`; when nnz(dense) <= k
    the extra slots carry zero values (scatter-adding them is a no-op), so
    `zeros(d).at[indices].add(values)` reconstructs `dense` exactly. This is
    the compact pair the simulator ships off-device instead of a d-length
    vector, and the wire format the ROADMAP pod-sync item calls for.
    jit-safe and vmap-safe (k static).
    """
    _, idx = jax.lax.top_k(jnp.abs(dense), k)
    return dense[idx], idx.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("rate", "coarse_buckets", "fine_buckets",
                                    "block", "interpret", "slack"))
def topk_compress_sparse(g: jax.Array, residual: jax.Array, *, rate: float,
                         coarse_buckets: int = 48, fine_buckets: int = 128,
                         block: int = 8 * 1024, interpret: bool | None = None,
                         slack: float = 1.05):
    """`topk_compress` returning the compact (values, indices) wire pair.

    Returns (values, indices, new_residual, nnz, threshold) with
    len(values) == ceil(slack·k)+8: the histogram threshold can overshoot k
    by ties within one fine bucket, so the capacity carries a small slack.
    Callers can check `nnz` against the capacity; coordinates beyond it
    (never observed at the tested rates) would be dropped from the wire but
    remain accounted in `new_residual` only via the dense pipeline output.
    """
    out, new_res, nnz, t = topk_compress(
        g, residual, rate=rate, coarse_buckets=coarse_buckets,
        fine_buckets=fine_buckets, block=block, interpret=interpret)
    d = g.shape[0]
    k = max(1, min(d, int(round(rate * d))))
    k_cap = min(d, int(k * slack) + 8)
    vals, idx = compact_topk(out, k_cap)
    return vals, idx, new_res, nnz, t


@functools.partial(jax.jit,
                   static_argnames=("budget", "coarse_buckets",
                                    "fine_buckets", "block", "interpret"))
def compact_shard_topk(acc: jax.Array, *, budget: int,
                       coarse_buckets: int = 48, fine_buckets: int = 128,
                       block: int = 8 * 1024, interpret: bool | None = None):
    """Per-shard compact top-k over a blocked EF accumulator [nb, blk].

    Runs the histogram threshold pipeline over the whole shard targeting
    `nb · budget` keeps, then packs each block's survivors into `budget`
    fixed slots (compact_topk kernel). Returns (values [nb, budget],
    indices [nb, budget] i32 shard-local flat, counts [nb] i32 header,
    residual [nb, blk]) — the pod-sync wire payload plus the EF carry.
    """
    interpret = resolve_interpret(interpret)
    with jax.named_scope("compact_shard_topk"):
        nb, blk = acc.shape
        acc = acc.astype(jnp.float32)
        t = solve_threshold(acc.reshape(-1), nb * budget,
                            coarse_buckets=coarse_buckets,
                            fine_buckets=fine_buckets, block=block,
                            interpret=interpret)
        with jax.named_scope("topk.pack"):
            return compact_blocks(acc, t, budget=budget, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("lr", "momentum", "block", "interpret"))
def momentum_update(w: jax.Array, mu: jax.Array, g: jax.Array, *, lr: float,
                    momentum: float = 0.9, block: int = 8 * 1024,
                    interpret: bool | None = None):
    interpret = resolve_interpret(interpret)
    with jax.named_scope("fused_momentum"):
        return fused_momentum(w, mu, g, lr=lr, momentum=momentum,
                              block=block, interpret=interpret)
