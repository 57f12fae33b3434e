"""Mamba-2 (SSD, state-space duality — arXiv:2405.21060), chunked.

Train/prefill use the chunked SSD algorithm: within-chunk "attention-like"
term via the segment-sum decay matrix, across-chunk linear recurrence via
lax.scan over chunk states (O(S·Q) compute, O(S/Q) sequential steps, state
[H, P, N] carried in fp32). Decode is the O(1) per-token recurrence over
the same state — this is what makes long_500k tractable for SSM archs.

Block layout follows the reference Mamba2 module: in_proj → (z | xBC | dt),
depthwise causal conv over xBC, SSD, gated RMSNorm, out_proj. With n_groups
G > 1, B and C are [b, S, G, N], group g serves heads [g·H/G, (g+1)·H/G),
and the gated norm normalises each group of d_inner / G channels on its own
(Mamba's `RMSNormGated(group_size=d_inner // G)`).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.models import nn


class SSMSpec(NamedTuple):
    d_model: int
    d_inner: int       # n_heads * head_dim
    n_heads: int
    head_dim: int      # P
    state: int         # N
    conv_width: int
    n_groups: int = 1  # G: B/C groups


def spec_from_cfg(cfg) -> SSMSpec:
    d_inner = cfg.ssm_heads * cfg.ssm_head_dim
    return SSMSpec(cfg.d_model, d_inner, cfg.ssm_heads, cfg.ssm_head_dim,
                   cfg.ssm_state, cfg.conv_width, cfg.ssm_groups)


# ------------------------------------------------------------------------ init
def mamba2_init(key, s: SSMSpec, *, param_dtype=jnp.float32):
    gn = s.n_groups * s.state
    conv_ch = s.d_inner + 2 * gn               # x, B, C share the conv
    d_in_proj = 2 * s.d_inner + 2 * gn + s.n_heads  # z,xBC,dt
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "in_proj": nn.linear_init(k1, s.d_model, d_in_proj, use_bias=False,
                                  param_dtype=param_dtype),
        "conv_w": nn.lecun_normal()(k2, (s.conv_width, conv_ch), param_dtype),
        "conv_b": jnp.zeros((conv_ch,), param_dtype),
        "A_log": jnp.zeros((s.n_heads,), param_dtype),         # A = -exp(A_log)
        "dt_bias": jnp.full((s.n_heads,), math.log(math.e - 1), param_dtype),
        "D": jnp.ones((s.n_heads,), param_dtype),
        "norm": nn.rmsnorm_init(s.d_inner, param_dtype=param_dtype),
        "out_proj": nn.linear_init(k4, s.d_inner, s.d_model, use_bias=False,
                                   param_dtype=param_dtype),
    }


# ------------------------------------------------------------------- SSD core
def _segsum(a: jax.Array) -> jax.Array:
    """a: [..., Q] -> lower-triangular cumulative sums L[i,j] = sum_{j<m<=i} a_m."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    d = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, d, -jnp.inf)


def ssd_chunked(xh: jax.Array, dtA: jax.Array, dtx_scale: jax.Array,
                Bm: jax.Array, Cm: jax.Array, *, chunk: int,
                initial_state: jax.Array | None = None):
    """Chunked SSD scan.

    xh:   [b, S, H, P]   head inputs
    dtA:  [b, S, H]      log-decay per step (dt * A, negative)
    dtx_scale: [b, S, H] input scale (dt)
    Bm,Cm: [b, S, N]     shared across heads (n_groups=1), or
           [b, S, G, N]  group g shared by heads [g·H/G, (g+1)·H/G)
    Returns (y [b,S,H,P], final_state [b,H,P,N]).
    """
    if Bm.ndim == 4:
        return _ssd_grouped(xh, dtA, dtx_scale, Bm, Cm, chunk=chunk,
                            initial_state=initial_state)
    b, S, H, P = xh.shape
    N = Bm.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    nc = S // chunk
    f32 = jnp.float32

    xc = (xh * dtx_scale[..., None]).astype(f32).reshape(b, nc, chunk, H, P)
    Ac = dtA.astype(f32).reshape(b, nc, chunk, H)
    Bc = Bm.astype(f32).reshape(b, nc, chunk, N)
    Cc = Cm.astype(f32).reshape(b, nc, chunk, N)

    A_cum = jnp.cumsum(Ac, axis=2)                       # [b,nc,Q,H]
    # within-chunk (diagonal) term
    L = jnp.exp(_segsum(jnp.moveaxis(Ac, -1, -2)))       # [b,nc,H,Q,Q]
    G = jnp.einsum("bcqn,bcsn->bcqs", Cc, Bc)            # [b,nc,Q,Q]
    y_diag = jnp.einsum("bcqs,bchqs,bcshp->bcqhp", G, L, xc)

    # end-of-chunk states
    decay_states = jnp.exp(A_cum[:, :, -1:, :] - A_cum)  # [b,nc,Q,H]
    states = jnp.einsum("bcsn,bcsh,bcshp->bchpn", Bc, decay_states, xc)
    chunk_decay = jnp.exp(A_cum[:, :, -1, :])            # [b,nc,H]

    # across-chunk recurrence (sequential scan over chunks)
    def step(carry, inp):
        st, dec = inp                                    # [b,H,P,N], [b,H]
        prev = carry
        new = prev * dec[..., None, None] + st
        return new, prev

    init = (jnp.zeros((b, H, P, N), f32) if initial_state is None
            else initial_state.astype(f32))
    final, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # [b,nc,H,P,N]

    # cross-chunk (off-diagonal) contribution
    state_decay_out = jnp.exp(A_cum)                     # [b,nc,Q,H]
    y_off = jnp.einsum("bcqn,bchpn,bcqh->bcqhp", Cc, prev_states,
                       state_decay_out)
    y = (y_diag + y_off).reshape(b, S, H, P)
    return y, final


def _ssd_grouped(xh, dtA, dtx_scale, Bm, Cm, *, chunk: int,
                 initial_state=None):
    """`ssd_chunked` with B, C in G groups: the same chunked algorithm with
    the heads split [G, H/G], so C·Bᵀ is formed once per group. Kept apart
    from the one-group path: run with a size-1 group axis, this form
    compiles mamba2-780m's v5e pod step to 2,926 instructions and a 6.3 MB
    larger peak, where the one-group path keeps the 2,916 it had."""
    b, S, H, P = xh.shape
    G, N = Bm.shape[-2:]
    Hg = H // G
    chunk = min(chunk, S)
    assert S % chunk == 0 and H % G == 0
    nc = S // chunk
    f32 = jnp.float32

    xc = (xh * dtx_scale[..., None]).astype(f32).reshape(b, nc, chunk, G,
                                                         Hg, P)
    Ac = dtA.astype(f32).reshape(b, nc, chunk, G, Hg)
    Bc = Bm.astype(f32).reshape(b, nc, chunk, G, N)
    Cc = Cm.astype(f32).reshape(b, nc, chunk, G, N)

    A_cum = jnp.cumsum(Ac, axis=2)                       # [b,nc,Q,G,Hg]
    L = jnp.exp(_segsum(jnp.moveaxis(Ac, 2, -1)))        # [b,nc,G,Hg,Q,Q]
    CB = jnp.einsum("bcqgn,bcsgn->bcgqs", Cc, Bc)        # [b,nc,G,Q,Q]
    y_diag = jnp.einsum("bcghqs,bcsghp->bcqghp", CB[:, :, :, None] * L, xc)

    decay_states = jnp.exp(A_cum[:, :, -1:] - A_cum)     # [b,nc,Q,G,Hg]
    states = jnp.einsum("bcsgn,bcsghp->bcghpn", Bc,
                        decay_states[..., None] * xc)
    chunk_decay = jnp.exp(A_cum[:, :, -1])               # [b,nc,G,Hg]

    def step(prev, inp):
        st, dec = inp
        return prev * dec[..., None, None] + st, prev

    init = (jnp.zeros((b, G, Hg, P, N), f32) if initial_state is None
            else initial_state.astype(f32).reshape(b, G, Hg, P, N))
    final, prev_states = jax.lax.scan(
        step, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # [b,nc,G,Hg,P,N]

    y_off = jnp.einsum("bcqgn,bcghpn->bcqghp", Cc, prev_states) \
        * jnp.exp(A_cum)[..., None]
    y = (y_diag + y_off).reshape(b, S, H, P)
    return y, final.reshape(b, H, P, N)


# ------------------------------------------------------------------ block apply
def _split_proj(s: SSMSpec, zxbcdt: jax.Array):
    z, xBC, dt = jnp.split(
        zxbcdt, [s.d_inner, 2 * s.d_inner + 2 * s.n_groups * s.state],
        axis=-1)
    return z, xBC, dt


def gated_norm(p, s: SSMSpec, y: jax.Array, z: jax.Array, *,
               eps: float = 1e-6) -> jax.Array:
    """RMSNorm of y · silu(z) over each group of d_inner / n_groups
    channels (over all of d_inner when n_groups is 1)."""
    y = y * jax.nn.silu(z.astype(jnp.float32))
    if s.n_groups == 1:
        return nn.rmsnorm_apply(p, y, eps=eps)
    g = y.reshape(y.shape[:-1] + (s.n_groups, s.d_inner // s.n_groups))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True) + eps)
    return g.reshape(y.shape) * p["scale"].astype(jnp.float32)


def mamba2_train(p, s: SSMSpec, x: jax.Array, *, chunk: int = 256,
                 dtype=jnp.bfloat16, return_state: bool = False,
                 eps: float = 1e-6):
    """x: [B, S, d_model] -> [B, S, d_model] (full-sequence train/prefill)."""
    B, S, _ = x.shape
    zxbcdt = nn.linear_apply(p["in_proj"], x, dtype=dtype)
    z, xBC, dt = _split_proj(s, zxbcdt)

    # depthwise causal conv over features of xBC
    w = p["conv_w"].astype(jnp.float32)                  # [W, conv_ch]
    xBC32 = xBC.astype(jnp.float32)
    pad = jnp.pad(xBC32, ((0, 0), (s.conv_width - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + S, :] * w[i] for i in range(s.conv_width))
    xBC = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))

    gn = s.n_groups * s.state
    xh, Bm, Cm = jnp.split(xBC, [s.d_inner, s.d_inner + gn], axis=-1)
    if s.n_groups > 1:
        Bm = Bm.reshape(B, S, s.n_groups, s.state)
        Cm = Cm.reshape(B, S, s.n_groups, s.state)
    xh = xh.reshape(B, S, s.n_heads, s.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))         # [H]
    dtA = dt * A[None, None, :]                          # [B,S,H]

    y, final = ssd_chunked(xh, dtA, dt, Bm, Cm, chunk=chunk)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, None, :, None]
    y = y.reshape(B, S, s.d_inner)
    y = gated_norm(p["norm"], s, y, z, eps=eps)
    out = nn.linear_apply(p["out_proj"], y.astype(dtype), dtype=dtype)
    if return_state:
        conv_state = xBC32[:, S - (s.conv_width - 1):, :] if S >= s.conv_width - 1 \
            else jnp.pad(xBC32, ((0, 0), (s.conv_width - 1 - S, 0), (0, 0)))
        # NOTE: conv state stores PRE-activation (pre-silu, pre-bias) inputs
        return out.astype(x.dtype), (final, conv_state)
    return out.astype(x.dtype)


def mamba2_decode(p, s: SSMSpec, x: jax.Array, state: jax.Array,
                  conv_state: jax.Array, *, dtype=jnp.bfloat16):
    """One token. x: [B, 1, d_model]; state: [B,H,P,N] fp32;
    conv_state: [B, W-1, conv_ch] fp32 (pre-activation xBC history).
    One group of B/C (n_groups 1) only."""
    assert s.n_groups == 1, "mamba2_decode: n_groups > 1 is not implemented"
    B = x.shape[0]
    zxbcdt = nn.linear_apply(p["in_proj"], x[:, 0, :], dtype=dtype)
    z, xBC_new, dt = _split_proj(s, zxbcdt)

    hist = jnp.concatenate([conv_state,
                            xBC_new.astype(jnp.float32)[:, None, :]], axis=1)
    w = p["conv_w"].astype(jnp.float32)
    conv = jnp.einsum("bwc,wc->bc", hist, w) + p["conv_b"].astype(jnp.float32)
    xBC = jax.nn.silu(conv)
    new_conv_state = hist[:, 1:, :]

    xh, Bm, Cm = jnp.split(xBC, [s.d_inner, s.d_inner + s.state], axis=-1)
    xh = xh.reshape(B, s.n_heads, s.head_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    a = jnp.exp(dt * A[None, :])                          # [B,H]
    new_state = state * a[..., None, None] + \
        jnp.einsum("bh,bhp,bn->bhpn", dt, xh.astype(jnp.float32), Bm)
    y = jnp.einsum("bn,bhpn->bhp", Cm, new_state)
    y = y + xh.astype(jnp.float32) * p["D"].astype(jnp.float32)[None, :, None]
    y = y.reshape(B, s.d_inner)
    y = nn.rmsnorm_apply(p["norm"], y * jax.nn.silu(z.astype(jnp.float32)))
    out = nn.linear_apply(p["out_proj"], y.astype(dtype), dtype=dtype)
    return out[:, None, :].astype(x.dtype), new_state, new_conv_state


# ---------------------------------------------------------------------- oracle
def ssd_reference(xh, dtA, dtx_scale, Bm, Cm, initial_state=None):
    """O(S) sequential recurrence oracle for tests (exact SSD semantics).
    B, C of [b, S, N] serve every head; of [b, S, G, N], heads in G
    contiguous groups."""
    b, S, H, P = xh.shape
    if Bm.ndim == 3:
        Bm, Cm = Bm[:, :, None], Cm[:, :, None]
    N = Bm.shape[-1]
    grp = jnp.arange(H) // (H // Bm.shape[2])
    st = jnp.zeros((b, H, P, N), jnp.float32) if initial_state is None \
        else initial_state.astype(jnp.float32)

    def step(st, t):
        a = jnp.exp(dtA[:, t, :]).astype(jnp.float32)            # [b,H]
        xt = (xh[:, t] * dtx_scale[:, t, :, None]).astype(jnp.float32)
        st = st * a[..., None, None] + jnp.einsum("bhp,bhn->bhpn", xt,
                                                  Bm[:, t][:, grp])
        y = jnp.einsum("bhn,bhpn->bhp", Cm[:, t][:, grp], st)
        return st, y

    st, ys = jax.lax.scan(step, st, jnp.arange(S))
    return jnp.moveaxis(ys, 0, 1), st                            # [b,S,H,P]
