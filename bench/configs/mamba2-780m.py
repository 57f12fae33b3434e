"""mamba2-780m: the program's model at published widths, its weights and
its plain reference.

The harness finds this module by the configuration's name and uses:
  arch(cfg)                 the program's `ArchConfig` for the run;
  init(cfg, key)            the weights, made by the benchmark from the seed
                            with Mamba-2's published initialisation, in the
                            program's parameter layout ([L, ...] stacks);
  reference_loss(cfg, params, batch, mode)
                            next-token cross-entropy of the same model in
                            plain jax.numpy, following the Mamba-2 equations
                            (arXiv:2405.21060, the reference `Mamba2`
                            module), its contractions in `bench.precision`
                            `mode` ("float32", or "int8" for the control);
  train_flops(cfg, seq)     forward + backward FLOPs of one token.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import precision


def _sizes(cfg):
    d = cfg["d_model"]
    di = cfg["expand"] * d
    n = cfg["d_state"] * cfg["ngroups"]
    h = di // cfg["headdim"]
    return d, di, n, h


def arch(cfg):
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=cfg["name"], family="ssm", n_layers=cfg["n_layer"],
        d_model=cfg["d_model"], n_heads=0, n_kv_heads=0, d_ff=0,
        vocab=cfg["padded_vocab"], mlp_type="none", ssm_state=cfg["d_state"],
        ssm_head_dim=cfg["headdim"], ssm_expand=cfg["expand"],
        conv_width=cfg["d_conv"], tie_embeddings=cfg["tie_embeddings"],
        source=cfg["source"])


def init(cfg, key):
    d, di, n, h = _sizes(cfg)
    L, W, V = cfg["n_layer"], cfg["d_conv"], cfg["padded_vocab"]
    conv_ch = di + 2 * n
    k = jax.random.split(key, 7)

    def uniform(key, shape, bound):
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)

    dt = jnp.exp(jax.random.uniform(k[5], (L, h), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    ones = lambda *s: jnp.ones(s, jnp.float32)
    return {
        "embed": {"embedding": 0.02 * jax.random.normal(k[0], (V, d))},
        "final_norm": {"scale": ones(d)},
        "layers": {
            "ssm_norm": {"scale": ones(L, d)},
            "ssm": {
                "in_proj": {"kernel": uniform(
                    k[1], (L, d, 2 * di + 2 * n + h), 1 / math.sqrt(d))},
                "conv_w": uniform(k[2], (L, W, conv_ch), 1 / math.sqrt(W)),
                "conv_b": uniform(k[3], (L, conv_ch), 1 / math.sqrt(W)),
                "A_log": jnp.log(jax.random.uniform(k[4], (L, h), jnp.float32,
                                                    1.0, 16.0)),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                "D": ones(L, h),
                "norm": {"scale": ones(L, di)},
                "out_proj": {"kernel": uniform(k[6], (L, di, d),
                                               1 / math.sqrt(di))
                             / math.sqrt(L)},
            },
        },
    }


def _rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _mixer(cfg, p, u, mode):
    """One Mamba-2 mixer over u [B, S, d]: in_proj -> (z, xBC, dt); causal
    depthwise conv + SiLU on xBC; the SSM
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t
    evaluated in its quadratic (attention-like) form over the whole
    sequence; gated RMSNorm (norm of y * silu(z)); out_proj."""
    d, di, n, h = _sizes(cfg)
    B, S, _ = u.shape
    P, W = cfg["headdim"], cfg["d_conv"]
    eps = cfg["norm_epsilon"]
    zxbcdt = precision.einsum(mode, "bsd,de->bse", u, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    xpad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(xpad[:, i:i + S] * p["conv_w"][i] for i in range(W))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x, bm, cm = jnp.split(xbc, [di, di + n], axis=-1)
    x = x.reshape(B, S, h, P)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [B,S,H]
    a = -jnp.exp(p["A_log"])                                    # [H]
    cs = jnp.cumsum(dt * a, axis=1)                             # [B,S,H]
    seg = cs[:, :, None, :] - cs[:, None, :, :]                 # [B,t,s,H]
    causal = jnp.tril(jnp.ones((S, S), bool))[None, :, :, None]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0)), 0)
    cb = precision.einsum(mode, "btn,bsn->bts", cm, bm)
    mix = cb[..., None] * decay * dt[:, None]                   # [B,t,s,H]
    y = precision.einsum(mode, "btsh,bshp->bthp", mix, x)
    y = y + x * p["D"][:, None]
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = _rmsnorm(y, p["norm"]["scale"], eps)
    return precision.einsum(mode, "bse,ed->bsd", y, p["out_proj"]["kernel"])


def reference_loss(cfg, params, batch, mode="float32"):
    """Mean next-token cross-entropy: embedding, n_layer pre-norm residual
    Mamba-2 blocks, final RMSNorm, tied LM head. Every contraction runs in
    `bench.precision` `mode`, the rest in float32."""
    p = params
    eps = cfg["norm_epsilon"]
    emb = p["embed"]["embedding"]
    x = jnp.take(emb, batch["tokens"], axis=0)

    def block(x, lp):
        return x + _mixer(cfg, lp["ssm"], _rmsnorm(x, lp["ssm_norm"]["scale"],
                                                   eps), mode), None

    x, _ = jax.lax.scan(block, x, p["layers"])
    x = _rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = precision.einsum(mode, "bsd,vd->bsv", x, emb)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None],
                                         -1))


def train_flops(cfg, seq):
    """Forward + backward (3x forward) FLOPs of one token: the in/out
    projections, the conv, the SSD in its chunked form (chunk =
    min(chunk_size, seq): C·Bᵀ, the masked mix of x, the chunk states and
    their read-out) and the tied LM head."""
    d, di, n, h = _sizes(cfg)
    P, W, V = cfg["headdim"], cfg["d_conv"], cfg["padded_vocab"]
    q = min(cfg["chunk_size"], seq)
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    conv = 2 * W * (di + 2 * n)
    ssd = 2 * q * n + 2 * q * h * P + 2 * 2 * h * P * n
    return 3 * (cfg["n_layer"] * (proj + conv + ssd) + 2 * d * V)
