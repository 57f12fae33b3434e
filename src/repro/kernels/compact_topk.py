"""Pallas TPU kernel: fixed-budget block compaction (compact wire format).

Given a blocked EF accumulator [n_blocks, blk] and a threshold t (from the
magnitude-histogram pipeline), each grid step packs the survivors
(|acc| >= t, in index order) of ROWS blocks into a fixed `budget` of slots
per block and emits the pod-sync wire payload:

    values   f32[n_blocks, budget]   front-packed kept entries
    indices  i32[n_blocks, budget]   shard-local flat coordinates
    counts   i32[n_blocks]           kept-count header (<= budget)
    residual f32[n_blocks, blk]      acc − shipped (EF carry, bitwise)

Padding slots carry (0.0, 0) so a scatter-add of the full payload onto
zeros reconstructs the shipped selection exactly. Blocks with more
survivors than `budget` truncate in index order; the overflow stays in the
residual and ships next round (bounded deferral — the same EF contract the
threshold pipeline already relies on).

The pack is sort-free. Each survivor's output slot is the exclusive prefix
count of the keep mask, computed per 128-lane tile as a matmul with a
strictly-upper-triangular ones matrix plus a running per-row carry (the
TPU compiler has no cumsum; 0/1 operands and sums <= blk are exact in any
matmul precision). A one-hot [budget, blk] matrix built from those slots
lowers the gather to MXU `dot_general`s at HIGHEST precision: each output
slot is one survivor times 1.0 plus zeros, so the values are bitwise exact,
and so are the in-block offsets (< 2^24), which become int32 shard-flat
indices by integer adds.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

ROWS = tiling.ROWS   # blocks per grid step (the f32 sublane tile)
_NT = (((1,), (1,)), ((), ()))   # contract lanes of both operands: A · Bᵀ
_EXACT = dict(precision=jax.lax.Precision.HIGHEST,
              preferred_element_type=jnp.float32)


def _exclusive_prefix(keep: jax.Array) -> jax.Array:
    """Per-row exclusive prefix sum along lanes of a 0/1 [rows, blk] mask."""
    rows, blk = keep.shape
    tile = tiling.LANES if blk % tiling.LANES == 0 else blk
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32, (tile, tile))
    upper = (iota(0) < iota(1)).astype(jnp.float32)
    carry = jnp.zeros((rows, 1), jnp.float32)
    parts = []
    for c in range(0, blk, tile):
        kt = keep[:, c:c + tile]
        parts.append(jnp.dot(kt, upper, preferred_element_type=jnp.float32)
                     + carry)
        carry = carry + jnp.sum(kt, axis=1, keepdims=True)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _compact_kernel(acc_ref, t_ref, vals_ref, idx_ref, cnt_ref, res_ref, *,
                    budget: int):
    rows, blk = acc_ref.shape
    acc = acc_ref[...]
    keep = jnp.where(jnp.abs(acc) >= t_ref[0], 1.0, 0.0)
    pos = _exclusive_prefix(keep)                # output slot per survivor
    ship = jnp.where(pos < budget, keep, 0.0)
    cnt = jnp.sum(ship, axis=1, keepdims=True).astype(jnp.int32)  # [rows, 1]
    cnt_ref[...] = cnt
    res_ref[...] = acc - jnp.where(ship > 0, acc, 0.0)

    # integer iotas (the TPU compiler has no float iota), compared as f32
    slot = jax.lax.broadcasted_iota(jnp.int32, (budget, blk), 0) \
        .astype(jnp.float32)
    offset = jax.lax.broadcasted_iota(jnp.int32, (1, blk), 1) \
        .astype(jnp.float32)
    live = jax.lax.broadcasted_iota(jnp.int32, (1, budget), 1)
    base = pl.program_id(0) * (rows * blk)
    for r in range(rows):
        onehot = jnp.where((slot == pos[r:r + 1]) & (ship[r:r + 1] > 0),
                           1.0, 0.0)                     # [budget, blk]
        vals_ref[r:r + 1, :] = jax.lax.dot_general(acc[r:r + 1], onehot, _NT,
                                                   **_EXACT)
        off = jax.lax.dot_general(offset, onehot, _NT, **_EXACT)
        idx_ref[r:r + 1, :] = jnp.where(live < cnt[r:r + 1],
                                        base + r * blk + off.astype(jnp.int32),
                                        0)


@functools.partial(jax.jit, static_argnames=("budget", "interpret"))
def compact_blocks(acc: jax.Array, threshold: jax.Array, *, budget: int,
                   interpret: bool = False):
    """Returns (values, indices, counts, residual) for acc [n_blocks, blk].

    `indices` are shard-local flat coordinates (block index · blk + offset),
    so `zeros(acc.size).at[indices.ravel()].add(values.ravel())` equals the
    shipped selection `acc − residual` exactly.
    """
    n_blocks, blk = acc.shape
    if not 1 <= budget <= blk:
        raise ValueError(f"budget={budget} outside [1, blk={blk}]")
    acc = acc.astype(jnp.float32)
    pad = (-n_blocks) % ROWS
    if pad:
        acc = jnp.concatenate([acc, jnp.zeros((pad, blk), jnp.float32)])
    n_pad = acc.shape[0]

    def rows_of(width):
        return pl.BlockSpec((ROWS, width), lambda i: (i, 0))

    vals, idx, cnt, res = pl.pallas_call(
        functools.partial(_compact_kernel, budget=budget),
        grid=(n_pad // ROWS,),
        in_specs=[rows_of(blk), tiling.scalar_spec()],
        out_specs=[rows_of(budget), rows_of(budget), rows_of(1),
                   rows_of(blk)],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, budget), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, budget), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, blk), jnp.float32),
        ],
        interpret=interpret,
    )(acc, tiling.scalar(threshold))
    return (vals[:n_blocks], idx[:n_blocks], cnt[:n_blocks, 0],
            res[:n_blocks])
