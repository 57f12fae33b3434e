"""Pallas TPU kernel: fused error-feedback threshold select (top-k pass 2).

Given the threshold t from pass 1, performs in ONE streaming pass:

    acc       = g + residual            (error feedback accumulate)
    keep      = |acc| >= t
    out       = acc * keep              (what ships to the server)
    residual' = acc * (1 - keep)        (what stays on device)

HBM traffic: read g + residual, write out + residual' — 2R+2W, the minimum.
The unfused reference does accumulate / compare / two selects as separate
HLO ops (>=3R+3W). A second output `nnz` (per-call count) feeds the wire-
bytes accounting and the optional exact-k correction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

DEFAULT_BLOCK = 8 * 1024


def _ef_topk_kernel(g_ref, r_ref, t_ref, out_ref, res_ref, nnz_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        nnz_ref[...] = jnp.zeros_like(nnz_ref)

    acc = g_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
    keep = jnp.abs(acc) >= t_ref[0]
    kept = jnp.where(keep, acc, 0.0)
    out_ref[...] = kept.astype(out_ref.dtype)
    res_ref[...] = (acc - kept).astype(res_ref.dtype)
    per_row = jnp.sum(keep.astype(jnp.float32), axis=1, keepdims=True)
    nnz_ref[...] += jnp.sum(per_row, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def ef_topk(g: jax.Array, residual: jax.Array, threshold: jax.Array, *,
            block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Returns (out, new_residual, nnz) — flat, same dtype as g."""
    d = g.shape[0]
    g2 = tiling.to_tiles(g, block)
    r2 = tiling.to_tiles(residual, block)
    spec = tiling.tile_spec(block)
    nnz_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))

    out, res, nnz = pl.pallas_call(
        _ef_topk_kernel,
        grid=(g2.shape[0] // tiling.ROWS,),
        in_specs=[spec, spec, tiling.scalar_spec()],
        out_specs=[spec, spec, nnz_spec],
        out_shape=[
            jax.ShapeDtypeStruct(g2.shape, g.dtype),
            jax.ShapeDtypeStruct(r2.shape, residual.dtype),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(g2, r2, tiling.scalar(threshold))
    return out.reshape(-1)[:d], res.reshape(-1)[:d], nnz[0, 0]
