"""Cross-pod sync: FedLuck Eq. 6 as a δ-adaptive EF top-k sparse reduce.

Each pod finishes its k local steps with a pseudo-gradient delta (Eq. 4);
the sync compresses every pod's EF accumulator (delta + residual) to
density δ and applies the server rule

    w  ←  w − η_g · mean_pods(kept)          (Eq. 6)
    r' =  (delta + r) − kept                 (error feedback)

Wire format (compact path)
--------------------------
Below the density crossover the kept entries ship as a **compact
fixed-budget block payload** instead of a dense zero-filled carrier. Per
owned block of `blk` coordinates each chip emits

    values   f32[budget]   kept entries, front-packed in index order
    indices  i32[budget]   shard-local flat coordinates of the values
    count    i32           kept-count header (<= budget)

with `budget = block_budget(blk, δ) = max(1, min(blk, round(δ·blk)))`.
Every chip thresholds only the blocks it owns: one histogram threshold
solve per shard (`kernels.ops.compact_shard_topk`) targeting
`budget · n_owned_blocks` keeps, then the `compact_topk` Pallas kernel
packs each block's survivors into the fixed budget. Padding slots carry
(0.0, 0) — adding them is a no-op — so the payload reconstructs the
selection exactly, and blocks whose survivors overflow the budget defer
the excess to the next round through the EF residual
(`residual' = acc − shipped`, bitwise). The collective is a `shard_map`
all-gather of ONLY these payloads over the `pod` axis: wire bytes scale
with δ, not with d. Each chip then applies the gathered payloads of every
pod in the `compact_topk.expand_blocks` Pallas kernel, the inverse of the
pack: block b's slots sit in row b and point into block b, so each block
is rebuilt from its own P · budget slots (summed in pod order, for one
pod bitwise `zeros.at[indices].add(values)`), with no global sort or
scatter, and written as `w − η_g · sum / P` in one pass over w.

Above the crossover a dense ring all-reduce is cheaper and the compression
only serves the EF contract; that path keeps the exact per-pod threshold
of `kernels.ops.topk_compress`: each chip histograms its own shard, the
in-pod shards sum their counts, and each selects its shard against the
whole pod's threshold (`ef_topk`) and psums the dense carrier over `pod`.

`make_pod_sync(..., wire=...)` picks the path: "auto" dispatches at build
time on `density_crossover`, "compact"/"dense" force one, and "reference"
is the dense-carrier oracle of the compact selection semantics (same
thresholds and budgets, a dense psum over pods instead of the sparse
gather, and `kernels.ref` instead of the pack kernel) that the
equivalence tests and the `podsync` benchmark gate diff against.

`CompactWire` / `all_gather_bytes` / `density_crossover` are the wire-cost
model. With `n_blocks` given, `all_gather_bytes` counts the actual compact
payload — budget slots plus count headers — so the model and the kernel
agree on the per-block budget by construction
(benchmarks/kernel_bench.py sweeps the crossover into BENCH_podsync.json).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.compact_topk import expand_blocks
from repro.kernels.ef_topk import ef_topk

VALUE_BYTES = 4    # fp32 payload
INDEX_BYTES = 4    # int32 shard-local flat coordinate
HEADER_BYTES = 4   # i32 kept-count per block


def block_budget(blk: int, rate: float) -> int:
    """Fixed per-block slot count of the compact wire format (also the EF
    selection cap): max(1, min(blk, round(rate·blk))). Both the wire-cost
    model and the kernel use this, so they agree by construction."""
    return max(1, min(int(blk), int(round(rate * blk))))


@dataclasses.dataclass(frozen=True)
class CompactWire:
    """Payload shape of one shard's compact sync upload."""
    n_blocks: int   # blocks this shard owns
    blk: int        # coordinates per block
    budget: int     # slots per block (block_budget)

    @property
    def dim(self) -> int:
        return self.n_blocks * self.blk

    def payload_bytes(self) -> int:
        """Bytes one shard ships to one peer: values + indices + headers."""
        return self.n_blocks * (self.budget * (VALUE_BYTES + INDEX_BYTES)
                                + HEADER_BYTES)

    def payload_bits(self) -> int:
        return 8 * self.payload_bytes()


def density_crossover(n_pods: int, *, value_bytes: int = VALUE_BYTES,
                      index_bytes: int = INDEX_BYTES) -> float:
    """Density δ* where compact all-gather bytes == dense ring all-reduce
    bytes. Compact ships (P−1)·δ·d·(val+idx) per device (headers add a
    constant ~HEADER_BYTES/blk per coordinate, negligible for blk ≫ 1);
    the ring costs 2·(P−1)/P·d·val. With 4-byte values/indices δ* = 1/P."""
    return 2.0 * value_bytes / (n_pods * (value_bytes + index_bytes))


def all_gather_bytes(dim: int, n_pods: int, rate: float, *,
                     n_blocks: int = 1, value_bytes: int = VALUE_BYTES,
                     index_bytes: int = INDEX_BYTES) -> float:
    """Per-device wire bytes of one Eq. 6 sync at density `rate` over `dim`
    coordinates in `n_blocks` blocks — the cheaper of the compact gather
    (actual payload: `block_budget` slots + count header per block) and the
    dense ring all-reduce."""
    if dim % n_blocks != 0:
        raise ValueError(f"dim={dim} not divisible by n_blocks={n_blocks}")
    blk = dim // n_blocks
    budget = block_budget(blk, rate)
    compact = (n_pods - 1) * n_blocks * (budget * (value_bytes + index_bytes)
                                         + HEADER_BYTES)
    dense = 2.0 * (n_pods - 1) / n_pods * dim * value_bytes
    return float(min(compact, dense))


def make_pod_sync(mesh, dim: int, *, rate: float, eta_g: float = 1.0,
                  n_blocks: int, wire: str = "auto",
                  interpret: bool | None = None):
    """Build sync(params, deltas, residuals) -> (new_params, new_residuals).

    params     [n_blocks, blk]            global model (flat, blocked)
    deltas     [n_pods, n_blocks, blk]    per-pod Eq. 4 pseudo-gradients
    residuals  [n_pods, n_blocks, blk]    per-pod EF carry

    dim = n_blocks · blk; the blocked 2D layout shards n_blocks over the
    in-pod axes and the pod dim over `pod`, so the mean over pods lowers
    to the cross-pod collective.

    wire: "auto" picks "compact" below `density_crossover` and "dense"
    above; "reference" is the dense-carrier oracle of the compact
    selection (tests / bench gate). The returned fn carries `.path` (the
    resolved wire mode), `.wire` (the per-shard `CompactWire`, None on the
    dense path), `.bytes_per_device` (wire-cost model for one sync), and
    `.payload_bits_per_pod` (bits one pod's whole update occupies on the
    wire — what `dist.steps.make_pod_round_step` charges).
    """
    n_pods = int(mesh.shape["pod"]) if "pod" in mesh.shape else 1
    if dim % n_blocks != 0:
        raise ValueError(f"dim={dim} not divisible by n_blocks={n_blocks}")
    blk = dim // n_blocks
    inpod = tuple(a for a in mesh.axis_names if a != "pod")
    n_shards = int(math.prod(mesh.shape[a] for a in inpod)) if inpod else 1
    has_pod = "pod" in mesh.shape
    if wire == "auto":
        wire = ("compact" if rate < density_crossover(max(n_pods, 2))
                else "dense")
    if wire not in ("compact", "dense", "reference"):
        raise ValueError(f"unknown wire mode {wire!r}")

    if n_blocks % n_shards != 0:
        raise ValueError(f"n_blocks={n_blocks} not divisible by the "
                         f"in-pod shard count {n_shards}")
    nbl = n_blocks // n_shards          # blocks each chip owns
    budget = block_budget(blk, rate)
    if wire in ("compact", "reference"):
        k_shard = nbl * budget          # shard threshold target
        wire_fmt = CompactWire(nbl, blk, budget)
    else:
        wire_fmt = None

    inpod_entry = inpod if inpod else None
    pod_entry = "pod" if has_pod else None
    pspec = jax.sharding.PartitionSpec(inpod_entry, None)
    dspec = jax.sharding.PartitionSpec(pod_entry, inpod_entry, None)

    def pod_sum(x):
        return jax.lax.psum(x, "pod") if has_pod else x

    # Every path runs its kernels inside a shard_map: a Pallas call is a
    # custom call that the SPMD partitioner cannot split, so under plain
    # GSPMD it would gather every pod's state onto every chip.
    if wire == "compact":
        def shard_fn(p_l, d_l, r_l):
            with jax.named_scope("pod_sync.compact_pack"):
                acc = d_l[0].astype(jnp.float32) + r_l[0].astype(jnp.float32)
                vals, idx, _, res = ops.compact_shard_topk(
                    acc, budget=budget, interpret=interpret)
            with jax.named_scope("pod_sync.all_gather"):
                if has_pod:
                    vals = jax.lax.all_gather(vals, "pod")  # [P, nbl, budget]
                    idx = jax.lax.all_gather(idx, "pod")
                else:
                    vals, idx = vals[None], idx[None]
            with jax.named_scope("pod_sync.scatter_apply"):
                new_p = expand_blocks(
                    p_l, vals, idx, eta_g=eta_g, n_pods=n_pods,
                    interpret=ops.resolve_interpret(interpret))
            return new_p, res[None].astype(r_l.dtype)

        in_specs, out_specs = (pspec, dspec, dspec), (pspec, dspec)

    elif wire == "reference":
        def shard_fn(p_l, d_l, r_l):
            acc = d_l[0].astype(jnp.float32) + r_l[0].astype(jnp.float32)
            t = ops.solve_threshold(acc.reshape(-1), k_shard,
                                    interpret=interpret)
            _, _, _, res = ref.ref_compact_blocks(acc, t, budget)
            kept = acc - res                 # shipped selection, dense carrier
            update = pod_sum(kept) / n_pods  # Eq. 6 reduce
            return p_l - eta_g * update, res[None]

        in_specs, out_specs = (pspec, dspec, dspec), (pspec, dspec)

    else:  # dense ring: exact per-pod threshold, dense cross-pod all-reduce
        k_pod = max(1, min(dim, round(rate * dim)))

        def shard_fn(p_l, d_l, r_l):
            with jax.named_scope("pod_sync.dense"):
                g = d_l[0].astype(jnp.float32).reshape(-1)
                res = r_l[0].astype(jnp.float32).reshape(-1)
                # the whole pod's threshold: in-pod shards reduce their
                # max and histogram counts, then each selects its own part
                t = ops.solve_threshold(g + res, k_pod,
                                        axis_name=inpod or None,
                                        interpret=interpret)
                out, res, _ = ef_topk(g, res, t,
                                      interpret=ops.resolve_interpret(
                                          interpret))
                kept = out.reshape(p_l.shape)
                update = pod_sum(kept) / n_pods      # Eq. 6 cross-pod reduce
                return p_l - eta_g * update, res.reshape(r_l.shape)

        in_specs, out_specs = (pspec, dspec, dspec), (pspec, dspec)

    sync = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
    sync.path = wire
    sync.wire = wire_fmt
    if wire_fmt is not None:
        sync.bytes_per_device = float(
            (max(n_pods, 1) - 1) * wire_fmt.payload_bytes())
        sync.payload_bits_per_pod = float(n_shards * wire_fmt.payload_bits())
    else:
        dim_local = dim // n_shards
        sync.bytes_per_device = \
            2.0 * (n_pods - 1) / max(n_pods, 1) * dim_local * VALUE_BYTES
        sync.payload_bits_per_pod = float(dim) * 8.0 * VALUE_BYTES
    return sync
