"""Pallas TPU kernels: fixed-budget block compaction (compact wire format)
and its inverse, the apply of P pods' payloads to the blocked parameters.

Given a blocked EF accumulator [n_blocks, blk] and a threshold t (from the
magnitude-histogram pipeline), each grid step packs the survivors
(|acc| >= t, in index order) of ROWS = 128 blocks into a fixed `budget` of
slots per block and emits the pod-sync wire payload:

    values   f32[n_blocks, budget]   front-packed kept entries
    indices  i32[n_blocks, budget]   shard-local flat coordinates
    counts   i32[n_blocks]           kept-count header (<= budget)
    residual f32[n_blocks, blk]      acc − shipped (EF carry, bitwise)

Padding slots carry (0.0, 0) so a scatter-add of the full payload onto
zeros reconstructs the shipped selection exactly. Blocks with more
survivors than `budget` truncate in index order; the overflow stays in the
residual and ships next round (bounded deferral — the same EF contract the
threshold pipeline already relies on).

Both kernels work slot-major: one block a lane, 128 blocks a grid step
(the last one partial, its lanes past n_blocks dropped on write), so that
slot j of every block is one sublane row. The pack transposes its
[128 blocks, blk] tile in VMEM so that offsets run down the sublanes. A
survivor's slot is the exclusive prefix count of the keep mask down the
sublanes, per 128 offsets a matmul with a strictly-lower-triangular ones
matrix plus a running per-block carry (the TPU compiler has no cumsum).
Then, offset by offset, the slot row is compared with the sublane iota of
the [budget, 128] payload tile, and where it hits, the value and the
offset are selected in. The payload is transposed back, the offsets
become int32 shard-flat indices by integer adds, and the residual is
written in row layout.

No MXU precision bears on the payload. The one matmul is the prefix
count, whose 0/1 bfloat16 operands and sums <= blk are exact in f32 at
the default precision. Values and offsets are moved by selects, never
multiplied, so each live slot holds its survivor bitwise (an ±Inf too),
and a NaN or Inf in a block leaves its other slots alone.

`expand_blocks` inverts the pack without a sort or a scatter: block b's
slots sit in row b, so each grid step rebuilds its ROWS blocks from their
own rows of the P payloads. It transposes them slot-major; slot j's row,
broadcast down the sublanes, is compared with the sublane iota of a
[128 offsets, 128 blocks] tile of the transposed dense update, selected
and added in, for every slot and chunk of offsets, on the vector unit.
The update is transposed back and applied in the same pass. (Taking
slot j out of a [8, budget] row-major tile instead needs a cross-lane
reduction per slot, whose latency made that form as slow as the
scatter-add on a v5e.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling

ROWS = tiling.LANES   # blocks per grid step of either kernel: one a lane


def _slots_t(acc_t: jax.Array, threshold, budget: int) -> jax.Array:
    """Output slot of each survivor of an offsets-by-blocks [blk, rows]
    tile, or -1 where the entry does not ship: the exclusive prefix count
    of the keep mask down the sublanes, per chunk of offsets a matmul with
    a strictly-lower-triangular ones matrix plus a running per-block carry
    (the TPU compiler has no cumsum; 0/1 bfloat16 operands summed in f32
    are exact at the default precision)."""
    blk, rows = acc_t.shape
    chunk = tiling.LANES if blk % tiling.LANES == 0 else blk
    iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                             (chunk, chunk))
    lower = (iota(0) > iota(1)).astype(jnp.bfloat16)
    carry = jnp.zeros((1, rows), jnp.float32)
    parts = []
    for c in range(0, blk, chunk):
        keep = jnp.abs(acc_t[c:c + chunk]) >= threshold
        kf = keep.astype(jnp.float32)
        pos = jnp.dot(lower, kf.astype(jnp.bfloat16),
                      preferred_element_type=jnp.float32) + carry
        parts.append(jnp.where(keep & (pos < budget), pos.astype(jnp.int32),
                               -1))
        carry = carry + jnp.sum(kf, axis=0, keepdims=True)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def _gather(acc_t, slot_t, budget: int):
    """Slot-major payload [budget, rows] of one grid step: for each offset
    i, the slots that row i's survivors fill take its value and i by a
    compare and two selects. A slot is filled by at most one offset, so
    each live slot holds its survivor bitwise and a dead one (0.0, 0)."""
    blk, rows = acc_t.shape
    slots = jax.lax.broadcasted_iota(jnp.int32, (budget, rows), 0)
    step = tiling.ROWS if blk % tiling.ROWS == 0 else 1

    def take_offsets(g, carry):              # offsets [i0, i0 + step)
        vals, offs = carry
        i0 = pl.multiple_of(g * step, step)
        for r in range(step):
            hit = slots == slot_t[pl.ds(i0 + r, 1), :]
            vals = jnp.where(hit, acc_t[pl.ds(i0 + r, 1), :], vals)
            offs = jnp.where(hit, i0 + r, offs)
        return vals, offs

    return jax.lax.fori_loop(
        0, blk // step, take_offsets,
        (jnp.zeros((budget, rows), jnp.float32),
         jnp.zeros((budget, rows), jnp.int32)))


def _compact_kernel(acc_ref, t_ref, vals_ref, idx_ref, cnt_ref, res_ref,
                    acc_t, slot_t, *, budget: int):
    rows, blk = acc_ref.shape
    acc = acc_ref[...]
    acc_t[...] = acc.T                           # offsets down the sublanes
    slot_t[...] = _slots_t(acc_t[...], t_ref[0], budget)
    cnt = jnp.sum((slot_t[...] >= 0).astype(jnp.int32), axis=0,
                  keepdims=True)                 # [1, rows]
    cnt_ref[...] = cnt
    res_ref[...] = acc - jnp.where(slot_t[...].T >= 0, acc, 0.0)

    vals, offs = _gather(acc_t, slot_t, budget)
    block = pl.program_id(0) * rows \
        + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
    live = jax.lax.broadcasted_iota(jnp.int32, (budget, rows), 0) < cnt
    vals_ref[...] = vals.T
    idx_ref[...] = jnp.where(live, block * blk + offs, 0).T


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

    def rows_of(width):
        return pl.BlockSpec((ROWS, width), lambda i: (i, 0))

    vals, idx, cnt, res = pl.pallas_call(
        functools.partial(_compact_kernel, budget=budget),
        grid=(pl.cdiv(n_blocks, ROWS),),
        in_specs=[rows_of(blk), tiling.scalar_spec()],
        out_specs=[rows_of(budget), rows_of(budget),
                   pl.BlockSpec((1, ROWS), lambda i: (0, i)), rows_of(blk)],
        out_shape=[
            jax.ShapeDtypeStruct((n_blocks, budget), jnp.float32),
            jax.ShapeDtypeStruct((n_blocks, budget), jnp.int32),
            jax.ShapeDtypeStruct((1, n_blocks), jnp.int32),
            jax.ShapeDtypeStruct((n_blocks, blk), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((blk, ROWS), jnp.float32),
                        pltpu.VMEM((blk, ROWS), jnp.int32)],
        interpret=interpret,
    )(acc.astype(jnp.float32), tiling.scalar(threshold))
    return vals, idx, cnt[0], res


def _expand_kernel(p_ref, vals_ref, idx_ref, eta_ref, inv_n_ref, out_ref,
                   vals_t, off_t, dense_t):
    rows, blk = p_ref.shape
    n_pods, _, budget = vals_ref.shape
    block = pl.program_id(0) * rows \
        + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    for q in range(n_pods):                  # slot-major: [budget, rows]
        vals_t[q] = vals_ref[q].T
        off_t[q] = (idx_ref[q] - block * blk).T      # in-block offsets
    chunk = tiling.LANES if blk % tiling.LANES == 0 else blk
    sub = jax.lax.broadcasted_iota(jnp.int32, (chunk, rows), 0)

    def rebuild_chunk(c, carry):             # offsets [c0, c0 + chunk)
        c0 = pl.multiple_of(c * chunk, chunk)
        dense = jnp.zeros((chunk, rows), jnp.float32)
        for q in range(n_pods):              # pods sum in pod order
            def add_slot(j, dense, q=q):
                v = vals_t[q, pl.ds(j, 1), :]          # [1, rows]
                o = off_t[q, pl.ds(j, 1), :] - c0
                return dense + jnp.where(sub == o, v, 0.0)

            dense = jax.lax.fori_loop(0, budget, add_slot, dense)
        dense_t[pl.ds(c0, chunk), :] = dense
        return carry

    jax.lax.fori_loop(0, blk // chunk, rebuild_chunk, 0)
    p = p_ref[...].astype(jnp.float32)
    out_ref[...] = (p - eta_ref[0] * (dense_t[...].T * inv_n_ref[0])) \
        .astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def expand_blocks(p: jax.Array, values: jax.Array, indices: jax.Array, *,
                  eta_g, n_pods, interpret: bool = False) -> jax.Array:
    """p − eta_g · (Σ_pods dense_pod) / n_pods, in p's dtype: the inverse of
    `compact_blocks`, applied to the blocked parameters p [n_blocks, blk].

    values f32 / indices i32 [P, n_blocks, budget] are P pods' payloads
    with shard-flat indices, as `compact_blocks` emits them: block b's
    slots sit in row b and point into [b·blk, (b+1)·blk), so each block is
    rebuilt from its own P · budget slots alone. A dense update is
    `zeros.at[indices].add(values)` bitwise for one pod: each slot adds
    into a zero tile, the live offsets of a block are distinct, and a
    (0.0, 0) padding slot adds +0.0 (to offset 0 of block 0, and to no
    offset of any other block). The pods sum in pod order; the mean takes
    1 / n_pods as a factor, exact for a power of two.
    """
    n_blocks, blk = p.shape
    n_payloads, _, budget = values.shape
    rows = ROWS
    payload = pl.BlockSpec((n_payloads, rows, budget), lambda i: (0, i, 0))
    tile = pl.BlockSpec((rows, blk), lambda i: (i, 0))
    return pl.pallas_call(
        _expand_kernel,
        grid=(pl.cdiv(n_blocks, rows),),
        in_specs=[tile, payload, payload, tiling.scalar_spec(),
                  tiling.scalar_spec()],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct(p.shape, p.dtype),
        scratch_shapes=[pltpu.VMEM((n_payloads, budget, rows), jnp.float32),
                        pltpu.VMEM((n_payloads, budget, rows), jnp.int32),
                        pltpu.VMEM((blk, rows), jnp.float32)],
        input_output_aliases={0: 0},
        interpret=interpret,
    )(p, values.astype(jnp.float32), indices.astype(jnp.int32),
      tiling.scalar(eta_g), tiling.scalar(1.0 / n_pods))
