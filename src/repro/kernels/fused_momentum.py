"""Pallas TPU kernel: fused momentum-SGD update (server/device hot loop).

    mu' = momentum * mu + g
    w'  = w - lr * mu'

One streaming pass: read (w, mu, g), write (w', mu') — 3R+2W HBM traffic
versus >=5R+4W for the unfused tree_map pair. lr/momentum are compile-time
constants (closed over), matching how the update is jitted per plan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tiling

DEFAULT_BLOCK = 8 * 1024


def _make_kernel(lr: float, momentum: float):
    def kernel(w_ref, mu_ref, g_ref, w_out, mu_out):
        mu = momentum * mu_ref[...].astype(jnp.float32) \
            + g_ref[...].astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32) - lr * mu
        mu_out[...] = mu.astype(mu_out.dtype)
        w_out[...] = w.astype(w_out.dtype)
    return kernel


@functools.partial(jax.jit,
                   static_argnames=("lr", "momentum", "block", "interpret"))
def fused_momentum(w: jax.Array, mu: jax.Array, g: jax.Array, *,
                   lr: float, momentum: float = 0.9,
                   block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Flat [d] update. Returns (w', mu')."""
    d = w.shape[0]
    w2, mu2, g2 = (tiling.to_tiles(x, block) for x in (w, mu, g))
    spec = tiling.tile_spec(block)

    w_new, mu_new = pl.pallas_call(
        _make_kernel(lr, momentum),
        grid=(w2.shape[0] // tiling.ROWS,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[jax.ShapeDtypeStruct(w2.shape, w.dtype),
                   jax.ShapeDtypeStruct(mu2.shape, mu.dtype)],
        interpret=interpret,
    )(w2, mu2, g2)
    return w_new.reshape(-1)[:d], mu_new.reshape(-1)[:d]
