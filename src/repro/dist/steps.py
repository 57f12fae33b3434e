"""Jit-able step builders for the sharded launchers.

Each builder closes over an `LM` facade (and optimizer) and returns a pure
function the caller jits with explicit in/out shardings (see
launch/dryrun.py). The builders add exactly the structure GSPMD cannot
infer on its own:

  make_train_step        fwd/bwd/update; optional ZeRO-3 whole-tree gather
                         (one explicit all-gather per param at step start)
                         and a `microbatches=` lax.scan gradient-accumulation
                         path with fp32 accumulators.
  make_local_round_step  FedLuck Alg. 1 device loop: k SGD steps over a
                         stacked [k, B, ...] batch, returning the Eq. 4
                         pseudo-gradient delta = w0 − wk in fp32.
  make_pod_round_step    one full FedLuck datacenter round: vmapped per-pod
                         local rounds feeding the Eq. 6 cross-pod sync from
                         dist.collectives, with wire bits taken from the
                         sync's actual compact payload shape.
  make_prefill_step /    thin inference wrappers (the KV-cache layout work
  make_decode_step       lives in sharding.cache_specs).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _strip_axes(spec: P, axes) -> P:
    """Remove mesh axes in `axes` from a PartitionSpec (→ gather them)."""
    drop = set(axes)

    def one(entry):
        if entry is None:
            return None
        names = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        kept = tuple(a for a in names if a not in drop)
        if not kept:
            return None
        return kept[0] if len(kept) == 1 else kept

    return P(*[one(e) for e in spec])


def _zeros_f32_like(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def make_train_step(lm, opt, *, microbatches: int = 1, pspec=None,
                    zero3_axes=None):
    """step(params, opt_state, batch) -> (new_params, new_opt_state, loss).

    zero3_axes: mesh axes the params are *additionally* sharded over at
    rest; the step gathers them once up front (a single per-param
    all-gather in the schedule) by re-constraining to `pspec` with those
    axes stripped. microbatches: split the batch leading dim into n chunks
    and accumulate grads/loss in fp32 — same numbers as the full-batch
    step, ~n× less activation memory.
    """
    if zero3_axes and pspec is None:
        raise ValueError("zero3_axes requires pspec")
    gather_spec = None
    if zero3_axes:
        gather_spec = jax.tree.map(lambda s: _strip_axes(s, zero3_axes),
                                   pspec, is_leaf=lambda x: isinstance(x, P))

    def step(params, opt_state, batch):
        if gather_spec is not None:
            params = jax.lax.with_sharding_constraint(params, gather_spec)
        if microbatches <= 1:
            loss, grads = jax.value_and_grad(lm.loss)(params, batch)
        else:
            stacked = jax.tree.map(
                lambda x: x.reshape((microbatches, x.shape[0] // microbatches)
                                    + x.shape[1:]), batch)

            def accum(carry, mb):
                loss_sum, gsum = carry
                l, g = jax.value_and_grad(lm.loss)(params, mb)
                gsum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), gsum, g)
                return (loss_sum + l.astype(jnp.float32), gsum), None

            (loss_sum, gsum), _ = jax.lax.scan(
                accum, (jnp.zeros((), jnp.float32), _zeros_f32_like(params)),
                stacked)
            loss = loss_sum / microbatches
            grads = jax.tree.map(lambda g: g / microbatches, gsum)
        new_params, new_state = opt.update(grads, opt_state, params)
        return new_params, new_state, loss

    return step


def make_local_round_step(lm, opt, k: int):
    """round(params, opt_state, batches) -> (params_k, opt_state_k, delta,
    mean_loss) where batches is a pytree of [k, B, ...] arrays and
    delta = w0 − wk (fp32) is the Eq. 4 pseudo-gradient the caller
    compresses and ships (train.py datacenter mode, Eq. 6 server rule
    w ← w − η_g/|S| Σ g̃)."""

    def round_fn(params, opt_state, batches):
        def body(carry, batch):
            p, s = carry
            loss, grads = jax.value_and_grad(lm.loss)(p, batch)
            with jax.named_scope("opt.update"):
                p, s = opt.update(grads, s, p)
            return (p, s), loss

        with jax.named_scope("local_round"):
            (p_k, s_k), losses = jax.lax.scan(body, (params, opt_state),
                                              batches, length=k)
            delta = jax.tree.map(
                lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                params, p_k)
            return p_k, s_k, delta, jnp.mean(losses)

    return round_fn


def make_pod_round_step(lm, opt, k: int, sync, *, spec, dim: int,
                        n_blocks: int):
    """Compose local rounds and the cross-pod sync into one jit-able round.

    `sync` comes from `dist.collectives.make_pod_sync`; `spec` is the
    flatten spec of the params pytree (`compression.flatten_pytree`);
    `dim` is the true flat dim (padded up to n_blocks · blk inside).

    step(params_blocked [nb, blk], opt_states (pod-stacked pytree),
         batches (pod-stacked [P, k, B, ...] pytree),
         residuals [P, nb, blk])
      -> (new_params_blocked, new_opt_states, new_residuals, mean_loss)

    The per-round communication cost is static — `step.wire_bits_per_pod`
    re-exports `sync.payload_bits_per_pod`, the bits one pod's update
    actually occupies on the wire (compact payload: budget slots + count
    headers), replacing the analytic δ·d·32 estimate.
    """
    from repro.core import compression as C

    local = make_local_round_step(lm, opt, k)

    def step(params_blocked, opt_states, batches, residuals):
        nb, blk = params_blocked.shape
        with jax.named_scope("pod_round.unflatten"):
            params = C.unflatten_pytree(params_blocked.reshape(-1)[:dim],
                                        spec)

        def one_pod(opt_state, pod_batches):
            _, s_k, delta, loss = local(params, opt_state, pod_batches)
            with jax.named_scope("pod_round.flatten"):
                flat_delta, _ = C.flatten_pytree(delta)
            return s_k, flat_delta, loss

        new_states, flat_deltas, losses = jax.vmap(one_pod)(opt_states,
                                                            batches)
        with jax.named_scope("pod_round.flatten"):
            pad = nb * blk - dim
            if pad:
                flat_deltas = jnp.pad(flat_deltas, ((0, 0), (0, pad)))
            deltas = flat_deltas.reshape(-1, nb, blk)
        new_blocked, new_residuals = sync(params_blocked, deltas, residuals)
        with jax.named_scope("pod_round.flatten"):
            loss = jnp.mean(losses)
        return new_blocked, new_states, new_residuals, loss

    step.wire_bits_per_pod = float(getattr(sync, "payload_bits_per_pod",
                                           0.0))
    return step


def make_prefill_step(lm):
    """prefill(params, batch) -> (last-position logits [B,1,V], cache)."""
    def prefill(params, batch):
        return lm.prefill(params, batch)
    return prefill


def make_decode_step(lm):
    """decode(params, cache, token [B,1], cur_index) -> (logits, cache).
    The cache arrives sequence-sharded over `model` (sharding.cache_specs);
    the length-S attention reduction runs flash-decoding style, one shard
    per TP device."""
    def decode(params, cache, token, cur_index):
        return lm.decode_step(params, cache, token, cur_index)
    return decode
