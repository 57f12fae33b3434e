"""Mixture-of-Experts layer: top-k router + capacity-buffer grouped GEMM.

Dispatch is the sort → position-in-group → scatter-to-[E, C, d] formulation:
the grouped matmuls are plain einsums over the expert axis and the FLOPs
are active-only (E·C·d_ff with C ≈ top_k·T/E·capacity_factor) — no
[T, E, C] one-hot tensor and no dense all-experts compute. Over-capacity
tokens are dropped (standard Switch-style; the router's softmax weights of
dropped slots are lost, tested to be < a few % at cf=1.25).

Distributed path (`shard_tokens_axes`): the dispatch's argsort/scatter are
token-order-dependent, so under plain GSPMD they replicate the token
stream (observed +70 GiB/device on qwen3 train_4k). The sharded path runs
the WHOLE layer inside a fully-manual shard_map:

  tokens   sharded over the batch axes (dispatch is shard-local),
  experts  TP-in-expert: d_ff sharded over `model`, d_model over the FSDP
           axis — weights are explicitly all-gathered over FSDP (ZeRO-3)
           and the partial outputs psum'd over `model`.

(A partial-manual shard_map variant tickles an XLA-CPU AllReducePromotion
crash — "Invalid binary instruction opcode copy" — hence fully manual.)

The expert-share layer (`share_init` / `share_apply`, the "E" layers of a
`layer_pattern` model) is model-configs §4's chip's share: DeepSeek-V3-style
sigmoid routing over all n_experts (a selection-only bias, the chosen
weights normalised and scaled), then the experts [first, first + held)
that this chip holds, each a non-gated relu² MLP, computed for exactly the
token slots routed to them (dropless: a held expert takes every slot that
chose it), plus a shared expert on every token. The absent experts' part
of the result is left out; on the chips that hold them it is theirs.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from jax.experimental.pallas.ops.tpu.megablox.ops import backend as megablox

from repro.kernels import ops
from repro.models import nn

# The router's matmul: on a TPU a float32 matmul at the default precision
# rounds its inputs to bfloat16, and a rounded logit flips near-ties of the
# top-k, which sends a token's gradient to another expert.
ROUTER_PRECISION = jax.lax.Precision.HIGHEST

# The shared expert's forward matmuls. An expert-share layer's output,
# the shared expert's above all, makes up most of the residual stream that
# the next layer's router reads (the embedding and a Mamba-2 layer's output
# are small beside it), and a bfloat16 rounding of its inputs is the same
# for every copy of a token: at the default precision a near-tie of the
# next layer's top-k flips many copies at once. Three bfloat16 passes; the
# gradients, which nothing routes on, take the default precision. (The held
# experts' forward stays at the default: on a TPU its time follows the
# seed's routing, and more passes over the held slots widen that spread.)
FORWARD_PRECISION = jax.lax.Precision.HIGH


def router_logits(x: jax.Array, kernel: jax.Array) -> jax.Array:
    """[T, d] @ [d, E] in float32 at ROUTER_PRECISION."""
    return jnp.matmul(x.astype(jnp.float32), kernel.astype(jnp.float32),
                      precision=ROUTER_PRECISION)


def moe_init(key, d_model: int, d_ff: int, n_experts: int, *,
             param_dtype=jnp.float32):
    kr, kg, ku, kd = jax.random.split(key, 4)
    lim = 1.0 / math.sqrt(d_model)
    init = nn.trunc_normal(lim)
    return {
        "router": nn.linear_init(kr, d_model, n_experts, use_bias=False,
                                 param_dtype=param_dtype),
        "w_gate": init(kg, (n_experts, d_model, d_ff), param_dtype),
        "w_up": init(ku, (n_experts, d_model, d_ff), param_dtype),
        "w_down": nn.trunc_normal(1.0 / math.sqrt(d_ff))(
            kd, (n_experts, d_ff, d_model), param_dtype),
    }


def _dispatch_compute(xf, router_k, w_gate, w_up, w_down, *, n_experts: int,
                      top_k: int, capacity_factor: float, dtype):
    """Core token-choice dispatch + grouped GEMMs on FULL-d weights.
    xf: [T, d]; w_gate/w_up: [E, d, f(maybe a TP slice)]; w_down: [E, f, d].
    Returns [T, d] (a PARTIAL sum if f is a TP slice — caller psums)."""
    T, d = xf.shape

    # ---- router (fp32 for numerics)
    logits = router_logits(xf, router_k)
    gate_vals, sel = jax.lax.top_k(logits, top_k)                 # [T, k]
    probs = jax.nn.softmax(gate_vals, axis=-1)                    # renormalized

    # ---- flatten slots: slot j = token t, choice i  (token-major)
    TK = T * top_k
    flat_eid = sel.reshape(TK)
    flat_w = probs.reshape(TK)

    # ---- sort slots by expert, position within expert group
    sort_idx = jnp.argsort(flat_eid)
    sorted_eid = flat_eid[sort_idx]
    counts = jnp.bincount(flat_eid, length=n_experts)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(TK) - starts[sorted_eid]

    cap = int(math.ceil(top_k * T / n_experts * capacity_factor))
    cap = max(8, ((cap + 7) // 8) * 8)                            # lane align
    keep = pos < cap
    safe_pos = jnp.where(keep, pos, 0)

    # ---- scatter tokens into the [E, C, d] buffer
    tok_of_slot = sort_idx // top_k
    gathered = xf[tok_of_slot].astype(dtype)
    buf = jnp.zeros((n_experts, cap, d), dtype)
    buf = buf.at[sorted_eid, safe_pos].add(
        jnp.where(keep[:, None], gathered, 0))

    # ---- grouped GEMMs (f may be a TP slice)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate.astype(dtype))) \
        * jnp.einsum("ecd,edf->ecf", buf, w_up.astype(dtype))
    y_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(dtype))

    # ---- gather back to slots, weight, combine over top_k
    y_sorted = y_buf[sorted_eid, safe_pos]
    y_sorted = jnp.where(keep[:, None], y_sorted, 0)
    inv = jnp.argsort(sort_idx)
    # y_sorted[inv] is slot-(token-major-)ordered; flat_w already is.
    y_slots = y_sorted[inv] * flat_w[:, None].astype(dtype)
    return y_slots.reshape(T, top_k, d).sum(axis=1)


def moe_apply(p, x: jax.Array, *, n_experts: int, top_k: int,
              capacity_factor: float = 1.25, dtype=jnp.bfloat16,
              shard_tokens_axes: tuple | None = None,
              fsdp_axis: str = "data",
              expert_tp_axis: str = "model") -> jax.Array:
    """x: [B, S, d] -> [B, S, d]. See module docstring for the sharded path.

    Sharded-path weight layout (must match repro.dist.sharding rules):
      router  [d, E]     replicated
      w_gate  [E, d, f]  P(None, fsdp, tp)
      w_up    [E, d, f]  P(None, fsdp, tp)
      w_down  [E, f, d]  P(None, tp, fsdp)
    """
    B, S, d = x.shape
    if not shard_tokens_axes:
        xf = x.reshape(B * S, d)
        y = _dispatch_compute(xf, p["router"]["kernel"], p["w_gate"],
                              p["w_up"], p["w_down"], n_experts=n_experts,
                              top_k=top_k, capacity_factor=capacity_factor,
                              dtype=dtype)
        return y.reshape(B, S, d).astype(x.dtype)

    from jax.sharding import PartitionSpec as P
    baxes = tuple(shard_tokens_axes)
    manual = set(baxes) | {fsdp_axis, expert_tp_axis}

    def local(router_k, wg, wu, wd, x_loc):
        # explicit ZeRO-3 gather of the FSDP (d_model) slices
        wg = jax.lax.all_gather(wg, fsdp_axis, axis=1, tiled=True)
        wu = jax.lax.all_gather(wu, fsdp_axis, axis=1, tiled=True)
        wd = jax.lax.all_gather(wd, fsdp_axis, axis=2, tiled=True)
        b_loc = x_loc.shape[0]
        xf = x_loc.reshape(b_loc * S, d)
        # token-chunked dispatch: the [E, C, d] capacity buffer and the
        # [T·k, d] gathered-slot tensors scale 1/n_chunks (2.7 GiB → 0.7
        # on qwen3); chunks are checkpointed so backward recomputes them.
        T_loc = xf.shape[0]
        nch = 1
        for cand in (4, 2, 1):
            if T_loc % cand == 0 and T_loc // cand >= 1024:
                nch = cand
                break

        @jax.checkpoint
        def one(xc):
            return _dispatch_compute(xc, router_k, wg, wu, wd,
                                     n_experts=n_experts, top_k=top_k,
                                     capacity_factor=capacity_factor,
                                     dtype=dtype)

        if nch > 1:
            y = jax.lax.map(one, xf.reshape(nch, T_loc // nch, d))
            y = y.reshape(T_loc, d)
        else:
            y = one(xf)
        # f was a TP slice → partial sums over the expert TP axis
        y = jax.lax.psum(y, expert_tp_axis)
        return y.reshape(b_loc, S, d)

    f = jax.shard_map(
        local,
        in_specs=(P(), P(None, fsdp_axis, expert_tp_axis),
                  P(None, fsdp_axis, expert_tp_axis),
                  P(None, expert_tp_axis, fsdp_axis),
                  P(baxes, None, None)),
        out_specs=P(baxes, None, None),
        axis_names=manual,
        check_vma=False,
    )
    return f(p["router"]["kernel"], p["w_gate"], p["w_up"], p["w_down"],
             x).astype(x.dtype)


def moe_aux_loss(p, x: jax.Array, *, n_experts: int, top_k: int) -> jax.Array:
    """Switch-style load-balancing auxiliary loss (mean_prob · mean_assign · E)."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    logits = router_logits(xf, p["router"]["kernel"])
    probs = jax.nn.softmax(logits, axis=-1)                       # [T, E]
    _, sel = jax.lax.top_k(logits, top_k)
    assign = jnp.zeros_like(probs).at[
        jnp.arange(xf.shape[0])[:, None], sel].set(1.0)
    return n_experts * jnp.mean(jnp.mean(probs, 0) * jnp.mean(assign, 0))


# ------------------------------------------------------------ expert share
def relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


# The held experts' grouped matmul: megablox's Pallas `gmm` (x rows times
# their group's weights) and `tgmm` (the weights' gradient). Their grids run
# only the row tiles of the held groups; sizes carry one more group than
# the weights, the absent experts' slots and the padding, which is never
# multiplied and reads back as zeros.
TILE_ROWS = 128
_VMEM_TILE_BYTES = 4 << 20


def _lanes(n: int, rows: int, budget: int) -> int:
    """The widest tile over n (a multiple of 128 dividing n, else 512, or
    all of a narrow n) whose [rows, tile] bfloat16 block fits `budget`."""
    if n <= 512:
        return n
    fit = [t for t in range(128, n + 1, 128)
           if n % t == 0 and rows * t * 2 <= budget]
    return max(fit) if fit else 512


def _gmm_tiles(m: int, k: int, n: int):
    """(rows, contraction, output) tiles: the whole contraction a step, so
    a weight tile is fetched once per group and output tile."""
    return TILE_ROWS, k, _lanes(n, k, _VMEM_TILE_BYTES)


def _tgmm_tiles(m: int, k: int, n: int):
    """(rows, output rows, output columns) tiles of the weight gradient,
    whose float32 accumulator and output stay resident across a group."""
    return TILE_ROWS, min(k, 512), min(n, 512)


def _mxu_dtype(dtype, interpret: bool):
    """The operands' dtype in the kernel: on a TPU, float32 goes in as
    bfloat16, as XLA's float32 dots at the default precision take it (and
    the model's other linears with it); the interpreter keeps `dtype`."""
    return jnp.bfloat16 if not interpret and dtype == jnp.float32 else dtype


def _gmm(x, w, sizes, interpret):
    dt = _mxu_dtype(x.dtype, interpret)
    return megablox.gmm(x.astype(dt), w.astype(dt), sizes, jnp.float32,
                        _gmm_tiles, interpret=interpret)


def _gmm_grads(x, w, sizes, g, interpret):
    """dx in x's dtype and dw in w's; the cotangent is taken in x's dtype,
    as the model's other linears take theirs."""
    xdt, dt = x.dtype, _mxu_dtype(x.dtype, interpret)
    x, g = x.astype(dt), g.astype(xdt).astype(dt)
    dx = megablox.gmm(g, w.astype(dt), sizes, xdt, _gmm_tiles,
                      transpose_rhs=True, interpret=interpret)
    dw = megablox.tgmm(x.T, g, sizes, jnp.float32, _tgmm_tiles,
                       num_actual_groups=w.shape[0], interpret=interpret)
    return dx, dw.astype(w.dtype)


@jax.custom_vjp
def forward_precise(x: jax.Array, w: jax.Array) -> jax.Array:
    """x [T, a] @ w [a, b], the product at FORWARD_PRECISION and both
    gradients at the default precision."""
    return jnp.matmul(x, w, precision=FORWARD_PRECISION)


def _forward_precise_fwd(x, w):
    return forward_precise(x, w), (x, w)


def _forward_precise_bwd(res, g):
    x, w = res
    return (g @ w.T).astype(x.dtype), (x.T @ g).astype(w.dtype)


forward_precise.defvjp(_forward_precise_fwd, _forward_precise_bwd)


# A Pallas call with a data-dependent grid takes no batch dimension, and
# the pod round vmaps the local round over pods: a batched call runs pod by
# pod.
@functools.cache
def _pod_by_pod(interpret: bool):
    seq = jax.custom_batching.sequential_vmap
    return (seq(functools.partial(_gmm, interpret=interpret)),
            seq(functools.partial(_gmm_grads, interpret=interpret)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped(x, w, sizes, interpret):
    return _pod_by_pod(interpret)[0](x, w, sizes)


def _grouped_fwd(x, w, sizes, interpret):
    return _grouped(x, w, sizes, interpret), (x, w, sizes)


def _grouped_bwd(interpret, res, g):
    dx, dw = _pod_by_pod(interpret)[1](*res, g)
    return dx, dw, None


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, *,
                   interpret: bool | None = None) -> jax.Array:
    """x [M, K] rows sorted by group, w [G, K, N], sizes [G + 1] int32
    summing to M -> [M, N] float32: rows [sum(sizes[:g]), sum(sizes[:g+1]))
    times w[g] for g < G, in x's dtype (w is cast to it, and its gradient
    comes back in w's own); the rows of group G are zeros. M is a multiple
    of TILE_ROWS. `interpret` None: Pallas's interpreter on the CPU
    backend, Mosaic on a TPU."""
    return _grouped(x, w, sizes, ops.resolve_interpret(interpret))


def share_init(key, d_model: int, d_ff: int, shared_d_ff: int,
               n_experts: int, held: int, *, param_dtype=jnp.float32):
    """Router over all `n_experts`, `held` relu² experts of width d_ff and
    a shared relu² expert of width shared_d_ff."""
    kr, ku, kd, ksu, ksd = jax.random.split(key, 5)
    init = nn.trunc_normal(1.0 / math.sqrt(d_model))
    return {
        "router": nn.linear_init(kr, d_model, n_experts, use_bias=False,
                                 param_dtype=param_dtype),
        "router_bias": jnp.zeros((n_experts,), param_dtype),
        "w_up": init(ku, (held, d_model, d_ff), param_dtype),
        "w_down": nn.trunc_normal(1.0 / math.sqrt(d_ff))(
            kd, (held, d_ff, d_model), param_dtype),
        "shared_up": nn.linear_init(ksu, d_model, shared_d_ff,
                                    use_bias=False, param_dtype=param_dtype),
        "shared_down": nn.linear_init(ksd, shared_d_ff, d_model,
                                      use_bias=False, param_dtype=param_dtype),
    }


def route(xf: jax.Array, kernel: jax.Array, bias: jax.Array, *, top_k: int,
          scale: float):
    """Sigmoid scores of all experts; the top_k of score + bias (the bias
    picks, it does not weigh; ties go to the lower index); their scores
    normalised to sum 1 and times `scale`. -> (weights [T, k] float32,
    experts [T, k] int32)."""
    with jax.named_scope("moe.route"):
        scores = jax.nn.sigmoid(router_logits(xf, kernel))
        _, sel = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return w, sel


def share_apply(p, x: jax.Array, *, top_k: int, scale: float, first: int = 0,
                dtype=jnp.bfloat16, interpret: bool | None = None) -> jax.Array:
    """x [B, S, d] -> [B, S, d]: the held experts' part of the routed sum
    plus the shared expert. p["w_up"] holds experts [first, first + held).

    Dispatch: the T·top_k slots are sorted by held expert, the slots of
    absent experts last; the first T·min(top_k, held) rows (rounded up to
    whole row tiles) hold every held slot (a token picks each expert once),
    so no slot is dropped. The grouped matmuls compute the held experts'
    rows and no others (`interpret` as `grouped_matmul` takes it). The
    shared expert's forward runs at FORWARD_PRECISION."""
    B, S, d = x.shape
    xf = x.reshape(B * S, d)
    T = xf.shape[0]
    held = p["w_up"].shape[0]
    w, sel = route(xf, p["router"]["kernel"], p["router_bias"], top_k=top_k,
                   scale=scale)
    with jax.named_scope("moe.dispatch"):
        e = sel.reshape(-1) - first
        key = jnp.where((e >= 0) & (e < held), e, held)
        rows_n = -(-T * min(top_k, held) // TILE_ROWS) * TILE_ROWS
        order = jnp.argsort(key, stable=True)
        order = jnp.pad(order, (0, max(rows_n - order.shape[0], 0)))[:rows_n]
        sizes = jnp.bincount(key, length=held + 1).astype(jnp.int32)
        n_held = jnp.sum(sizes[:held])
        # the uncomputed last group ends the rows: the held groups' rows
        # then the zeros past them
        sizes = sizes.at[held].set(rows_n - n_held)
        valid = (jnp.arange(rows_n) < n_held)[:, None]
        tok = order // top_k
        rows = jnp.where(valid, xf[tok], 0).astype(dtype)
        wr = jnp.where(valid[:, 0], w.reshape(-1)[order], 0.0)
    with jax.named_scope("moe.experts"):
        h = relu2(grouped_matmul(rows, p["w_up"], sizes,
                                 interpret=interpret))
        y = grouped_matmul(h.astype(dtype), p["w_down"], sizes,
                           interpret=interpret)
    with jax.named_scope("moe.dispatch"):
        y = jnp.where(valid, y, 0.0) * wr[:, None]
        routed = jnp.zeros((T, d), jnp.float32).at[tok].add(y)
    with jax.named_scope("moe.shared"):
        up, down = (p[k]["kernel"].astype(dtype)
                    for k in ("shared_up", "shared_down"))
        shared = forward_precise(relu2(forward_precise(xf.astype(dtype), up)),
                                 down)
    return (routed + shared.astype(jnp.float32)).reshape(B, S, d) \
        .astype(x.dtype)
