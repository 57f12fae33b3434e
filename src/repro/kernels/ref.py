"""Pure-jnp oracles for every kernel in repro.kernels (tests diff vs these)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ref_magnitude_hist(g: jax.Array, edges: jax.Array) -> jax.Array:
    """counts_ge[j] = #{ |g| >= edges[j] }, float32[n_edges]."""
    mag = jnp.abs(g.astype(jnp.float32))
    return jnp.sum(mag[None, :] >= edges.astype(jnp.float32)[:, None],
                   axis=1).astype(jnp.float32)


def ref_ef_topk(g: jax.Array, residual: jax.Array, threshold) -> tuple:
    acc = g.astype(jnp.float32) + residual.astype(jnp.float32)
    keep = jnp.abs(acc) >= jnp.asarray(threshold, jnp.float32)
    out = jnp.where(keep, acc, 0.0)
    res = acc - out
    return out.astype(g.dtype), res.astype(residual.dtype), \
        jnp.sum(keep.astype(jnp.float32))


def ref_fused_momentum(w, mu, g, *, lr: float, momentum: float = 0.9):
    mu_new = momentum * mu.astype(jnp.float32) + g.astype(jnp.float32)
    w_new = w.astype(jnp.float32) - lr * mu_new
    return w_new.astype(w.dtype), mu_new.astype(mu.dtype)


def ref_exact_topk_dense(g: jax.Array, k: int) -> jax.Array:
    """Exact top-k as a dense masked vector (selection oracle)."""
    _, idx = jax.lax.top_k(jnp.abs(g), k)
    out = jnp.zeros_like(g)
    return out.at[idx].set(g[idx])


def ref_threshold_from_hist(counts_ge: jax.Array, edges: jax.Array,
                            k: int) -> jax.Array:
    """Smallest edge whose >=-count reaches k (edges descending)."""
    sel = jnp.argmax(counts_ge >= k)
    return edges[sel]


def ref_compact_blocks(acc: jax.Array, threshold, budget: int) -> tuple:
    """Oracle for kernels.compact_topk.compact_blocks: per-block fixed-budget
    front-pack of the |acc| >= t survivors in index order, shard-local flat
    indices, kept-count header, and the bitwise EF residual."""
    acc = acc.astype(jnp.float32)
    n_blocks, blk = acc.shape
    keep = jnp.abs(acc) >= jnp.asarray(threshold, jnp.float32)
    kf = keep.astype(jnp.float32)
    pos = jnp.cumsum(kf, axis=1) - kf
    in_budget = keep & (pos < budget)
    shipped = jnp.where(in_budget, acc, 0.0)
    cnt = jnp.sum(in_budget, axis=1).astype(jnp.int32)
    # stable pack: kept entries sort to the front by their slot position,
    # dropped entries by a unique key past every slot
    offs = jnp.arange(blk, dtype=jnp.float32)[None, :]
    key = jnp.where(in_budget, pos, blk + offs)
    order = jnp.argsort(key, axis=1)[:, :budget]
    slot_live = jnp.arange(budget, dtype=jnp.int32)[None, :] < cnt[:, None]
    vals = jnp.where(slot_live,
                     jnp.take_along_axis(acc, order, axis=1), 0.0)
    gidx = order.astype(jnp.int32) \
        + (jnp.arange(n_blocks, dtype=jnp.int32) * blk)[:, None]
    idx = jnp.where(slot_live, gidx, 0)
    return vals, idx, cnt, acc - shipped


def ref_expand_blocks(p: jax.Array, values: jax.Array, indices: jax.Array,
                      eta_g, n_pods) -> jax.Array:
    """Oracle for kernels.compact_topk.expand_blocks: every pod's payload
    (values / indices [P, n_blocks, budget], shard-flat indices) scatter-
    added onto zeros, and p − eta_g · (that sum / n_pods) in p's dtype."""
    dense = jnp.zeros((p.size,), jnp.float32).at[indices.reshape(-1)].add(
        values.reshape(-1).astype(jnp.float32))
    return (p.astype(jnp.float32)
            - eta_g * (dense.reshape(p.shape) / n_pods)).astype(p.dtype)
