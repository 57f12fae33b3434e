"""The precision a reference computes in.

  "float32"  every contraction in float32 at HIGHEST precision: the plain
             reference;
  "int8"     the control: every contraction of the forward and the backward
             pass on int8 operands, each rounded with one symmetric scale per
             tensor (the forward's two operands; in the backward the
             incoming cotangent with the forward's rounded operands), the
             products summed exactly; the rest float32. It is the step below
             the configurations' bfloat16 matmul inputs, which is what
             float32 at JAX's default precision computes with on a TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

MODES = ("float32", "int8")


def _int8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _exact(spec, *operands):
    return jnp.einsum(spec, *operands, precision=jax.lax.Precision.HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _int8_einsum(spec, a, b):
    return _exact(spec, _int8(a), _int8(b))


def _int8_fwd(spec, a, b):
    qa, qb = _int8(a), _int8(b)
    return _exact(spec, qa, qb), (qa, qb)


def _int8_bwd(spec, res, ct):
    _, vjp = jax.vjp(functools.partial(_exact, spec), *res)
    return vjp(_int8(ct))


_int8_einsum.defvjp(_int8_fwd, _int8_bwd)


def einsum(mode: str, spec: str, a, b):
    """`jnp.einsum` of two operands at float32/HIGHEST, with int8-rounded
    operands and cotangents in the "int8" mode."""
    if mode not in MODES:
        raise ValueError(f"unknown precision mode {mode!r}")
    if mode == "int8":
        return _int8_einsum(spec, a, b)
    return _exact(spec, a, b)
