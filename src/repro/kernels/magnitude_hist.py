"""Pallas TPU kernel: streaming magnitude histogram (top-k pass 1).

Computes counts_ge[j] = #{ |g| >= edges[j] } over a flat gradient, streamed
through VMEM tile by tile (`kernels.tiling`). This is the first pass of the
TPU-native threshold top-k (DESIGN.md §3): the paper's GPU sort-based top-k
does not map to the TPU memory hierarchy, so we select by threshold instead.

Grid iterations on TPU run sequentially per core, so the kernel accumulates
into a single output block (index_map pinned to 0); iteration 0 initializes.

Edges arrive as an [n_edges, 1] column, so each tile row compares against
every edge as one [n_edges, lanes] broadcast and reduces along lanes — no
lane↔sublane relayout. VMEM per step (defaults): the (8, 1024) f32 tile plus
one [129, 1024] compare, well under 1 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

DEFAULT_BLOCK = 8 * 1024


def _hist_kernel(x_ref, edges_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    mag = jnp.abs(x_ref[...].astype(jnp.float32))      # [ROWS, lanes]
    edges = edges_ref[...]                              # [n_edges, 1]
    counts = out_ref[...]
    for r in range(mag.shape[0]):
        ge = (mag[r:r + 1, :] >= edges).astype(jnp.float32)  # [n_edges, lanes]
        counts = counts + jnp.sum(ge, axis=1, keepdims=True)
    out_ref[...] = counts


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def magnitude_hist(g: jax.Array, edges: jax.Array, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool = False) -> jax.Array:
    """counts_ge: float32[n_edges]; g: flat [d] (any float dtype),
    edges: [n_edges] strictly positive descending thresholds. Zero padding
    sits below every (positive) edge, so it never counts."""
    n_edges = edges.shape[0]
    g2 = tiling.to_tiles(g, block)
    edge_spec = pl.BlockSpec((n_edges, 1), lambda i: (0, 0))
    out = pl.pallas_call(
        _hist_kernel,
        grid=(g2.shape[0] // tiling.ROWS,),
        in_specs=[tiling.tile_spec(block), edge_spec],
        out_specs=edge_spec,
        out_shape=jax.ShapeDtypeStruct((n_edges, 1), jnp.float32),
        interpret=interpret,
    )(g2, edges.astype(jnp.float32).reshape(n_edges, 1))
    return out[:, 0]
