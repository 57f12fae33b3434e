"""Flat-vector tiling shared by the streaming kernels.

A flat [d] vector is zero-padded to a multiple of `block` and viewed as
[rows, block // ROWS]; each grid step streams one (ROWS, block // ROWS)
tile through VMEM. The TPU compiler accepts a block only when its last two
dims are multiples of the (8, 128) tile or the whole array dims, so
`block` must be a multiple of ROWS · 128 = 1024. (8, ·) tiles of 16-bit
dtypes compile too, checked against a described v5e.

Scalars (thresholds) ride in SMEM as shape-(1,) arrays.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8
LANES = 128


def lanes(block: int) -> int:
    if block <= 0 or block % (ROWS * LANES):
        raise ValueError(f"block={block} must be a positive multiple of "
                         f"{ROWS * LANES}")
    return block // ROWS


def to_tiles(x: jax.Array, block: int) -> jax.Array:
    """Zero-pad flat `x` to a multiple of `block`; view as [rows, lanes]."""
    width = lanes(block)
    pad = (-x.shape[0]) % block
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
    return x.reshape(-1, width)


def tile_spec(block: int) -> pl.BlockSpec:
    """Grid step i streams tile rows [i·ROWS, (i+1)·ROWS)."""
    return pl.BlockSpec((ROWS, lanes(block)), lambda i: (i, 0))


def scalar_spec() -> pl.BlockSpec:
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def scalar(x) -> jax.Array:
    return jnp.asarray(x, jnp.float32).reshape(1)
