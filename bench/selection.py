"""Exact magnitude selection for the references: no histogram, no sort.

`kth_largest_abs` finds the k-th largest |x| exactly by bisection on the
bit pattern of |x| (for non-negative floats the uint32 view is ordered
like the values): 31 counting passes over x.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _bits(x):
    return jax.lax.bitcast_convert_type(jnp.abs(x).astype(jnp.float32),
                                        jnp.uint32)


def kth_largest_abs(x, k):
    """The k-th largest |x| (k >= 1, may be traced) as float32."""
    u = _bits(x).reshape(-1)

    def body(i, t):
        cand = t | (jnp.uint32(1) << (30 - i).astype(jnp.uint32))
        return jnp.where(jnp.sum(u >= cand, dtype=jnp.int32) >= k, cand, t)

    t = jax.lax.fori_loop(0, 31, body, jnp.uint32(0))
    return jax.lax.bitcast_convert_type(t, jnp.float32)


def topk_mask(x, k):
    """Mask of the k largest |x| of a flat x; among equal magnitudes the
    lower index wins (the tie order of `lax.top_k`)."""
    t = kth_largest_abs(x, k)
    a = jnp.abs(x)
    above = a > t
    tie = a == t
    room = k - jnp.sum(above, dtype=jnp.int32)
    return above | (tie & (jnp.cumsum(tie, dtype=jnp.int32) <= room))


def block_budget_mask(acc, k, budget):
    """Pod-sync selection over a blocked accumulator [nb, blk]: keep every
    |acc| at or above the k-th largest, then in each block only the first
    `budget` survivors in index order (the rest stays in the residual)."""
    keep = jnp.abs(acc) >= kth_largest_abs(acc, k)
    return keep & (jnp.cumsum(keep, axis=1, dtype=jnp.int32) <= budget)
