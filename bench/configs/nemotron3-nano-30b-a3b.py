"""nemotron3-nano-30b-a3b: one period of Nemotron 3 Nano's one-mixer
layers (EMEMEM*: three expert-share MoE, three Mamba-2, one attention) at
published widths, its weights and its plain reference.

The harness finds this module by the configuration's name and uses:
  arch(cfg)                 the program's `ArchConfig` for the run;
  init(cfg, key)            the weights, made by the benchmark from the seed
                            (the file's `assumed.init`), in the program's
                            parameter layout (one [n, ...] stack per kind
                            of layer, an untied head);
  reference_loss(cfg, params, batch, mode)
                            next-token cross-entropy of the same model in
                            plain jax.numpy following the published NemotronH
                            modelling code, its contractions in
                            `bench.precision` `mode` ("float32", or "int8"
                            for the control);
  train_flops(cfg, seq)     forward + backward FLOPs of one token.

The chip's share (the file's `deployment`): the router scores all
`router_experts` experts and picks top-k of them; only the held experts
[held_experts[0], held_experts[1]) are computed, and the absent experts'
part of the result is left out, here as in the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench import precision

KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def _mamba_sizes(cfg):
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h * p, h, p, cfg["n_groups"], cfg["ssm_state_size"]


def arch(cfg):
    from repro.configs.base import ArchConfig
    lo, hi = cfg["held_experts"]
    assert lo == 0, "the program holds experts [0, held)"
    return ArchConfig(
        name=cfg["name"], family="hybrid",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        use_rope=False, mlp_type="none", norm_eps=cfg["norm_eps"],
        layer_pattern=cfg["hybrid_override_pattern"],
        n_experts=cfg["router_experts"],
        moe_top_k=cfg["num_experts_per_tok"], held_experts=hi - lo,
        shared_d_ff=cfg["moe_shared_expert_intermediate_size"],
        routed_scale=cfg["routed_scaling_factor"],
        ssm_state=cfg["ssm_state_size"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_n_heads=cfg["mamba_num_heads"], ssm_groups=cfg["n_groups"],
        ssm_chunk=cfg["chunk_size"], conv_width=cfg["conv_kernel"],
        tie_embeddings=cfg["tie_word_embeddings"], source=cfg["source"])


# ------------------------------------------------------------------ weights
def init(cfg, key):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    pat = cfg["hybrid_override_pattern"]
    nE, nM, nA = (pat.count(c) for c in "EM*")
    E, (lo, hi) = cfg["router_experts"], cfg["held_experts"]
    H = hi - lo
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    di, h, _, G, N = _mamba_sizes(cfg)
    W = cfg["conv_kernel"]
    conv_ch = di + 2 * G * N
    hq = cfg["num_attention_heads"] * cfg["head_dim"]
    hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
    std = 0.02
    k = iter(jax.random.split(key, 16))

    def normal(*shape):
        return std * jax.random.normal(next(k), shape, jnp.float32)

    def uniform(bound, *shape):
        return jax.random.uniform(next(k), shape, jnp.float32, -bound, bound)

    ones = lambda *s: jnp.ones(s, jnp.float32)
    dt = jnp.exp(jax.random.uniform(next(k), (nM, h), jnp.float32)
                 * (math.log(cfg["time_step_max"])
                    - math.log(cfg["time_step_min"]))
                 + math.log(cfg["time_step_min"]))
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    return {
        "embed": {"embedding": normal(V, d)},
        "head": {"kernel": normal(d, V)},
        "final_norm": {"scale": ones(d)},
        "layers": {
            "attn": {"attn_norm": {"scale": ones(nA, d)},
                     "wq": {"kernel": normal(nA, d, hq)},
                     "wk": {"kernel": normal(nA, d, hkv)},
                     "wv": {"kernel": normal(nA, d, hkv)},
                     "wo": {"kernel": normal(nA, hq, d)}},
            "mamba": {
                "ssm_norm": {"scale": ones(nM, d)},
                "ssm": {
                    "in_proj": {"kernel": normal(nM, d, 2 * di + 2 * G * N
                                                 + h)},
                    "conv_w": uniform(1 / math.sqrt(W), nM, W, conv_ch),
                    "conv_b": uniform(1 / math.sqrt(W), nM, conv_ch),
                    "A_log": jnp.broadcast_to(
                        jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                        (nM, h)),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "D": ones(nM, h),
                    "norm": {"scale": ones(nM, di)},
                    "out_proj": {"kernel": uniform(1 / math.sqrt(di), nM, di,
                                                   d)
                                 / math.sqrt(cfg["published"]
                                             ["num_hidden_layers"])},
                },
            },
            "moe": {
                "ffn_norm": {"scale": ones(nE, d)},
                "moe": {
                    "router": {"kernel": normal(nE, d, E)},
                    "router_bias": jnp.zeros((nE, E), jnp.float32),
                    "w_up": normal(nE, H, d, f),
                    "w_down": normal(nE, H, f, d),
                    "shared_up": {"kernel": normal(nE, d, fs)},
                    "shared_down": {"kernel": normal(nE, fs, d)},
                },
            },
        },
    }


# ---------------------------------------------------------------- reference
def _rmsnorm(x, scale, eps):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return y * scale


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def _moe(cfg, p, u, mode):
    """NemotronHMOE on u [B, S, d]: sigmoid scores of all router_experts
    experts (the published gate takes its linear in float32); the
    top-k of score + e_score_correction_bias, ties to the lower index as
    `lax.top_k`; their scores normalised (+1e-20) and times
    routed_scaling_factor; each held expert's relu² MLP weighted by its
    token's weight (0 where not chosen), dense over all tokens; plus the
    shared relu² expert on every token."""
    lo, hi = cfg["held_experts"]
    k = cfg["num_experts_per_tok"]
    logits = precision.einsum(mode, "bsd,de->bse", u, p["router"]["kernel"])
    scores = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(scores + p["router_bias"], k)
    w = jnp.take_along_axis(scores, sel, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    gate = jnp.sum(jnp.where(sel[..., None] == jnp.arange(lo, hi), w[..., None],
                             0.0), axis=-2)                      # [B,S,H]
    h = _relu2(precision.einsum(mode, "bsd,edf->bsef", u, p["w_up"]))
    routed = precision.einsum(mode, "bsef,efd->bsd", h * gate[..., None],
                              p["w_down"])
    hs = _relu2(precision.einsum(mode, "bsd,df->bsf", u,
                                 p["shared_up"]["kernel"]))
    return routed + precision.einsum(mode, "bsf,fd->bsd", hs,
                                     p["shared_down"]["kernel"])


def _segsum(a):
    """a [..., Q] -> [..., Q, Q]: sum of a over (s, t], -inf above the
    diagonal."""
    Q = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], a.shape + (Q,))         # [..., t, s]
    x = jnp.where(jnp.tril(jnp.ones((Q, Q), bool), -1), x, 0.0)
    seg = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg, -jnp.inf)


def _mamba(cfg, p, u, mode):
    """NemotronHMamba2Mixer on u [B, S, d]: in_proj -> (z, xBC, dt); causal
    depthwise conv (with bias) + SiLU on xBC; the SSM with B, C in n_groups
    groups (head h reads group h // (heads / n_groups))
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t
    in Mamba-2's minimal SSD form (arXiv:2405.21060, `ssd_minimal`): the
    quadratic form inside each chunk of chunk_size, and the chunk states
    passed on through the exact decays between chunks; the gated RMSNorm
    over groups of d_inner / n_groups (norm of y * silu(z)); out_proj."""
    di, h, P, G, N = _mamba_sizes(cfg)
    B, S, _ = u.shape
    W, Q, eps = cfg["conv_kernel"], cfg["chunk_size"], \
        cfg["layer_norm_epsilon"]
    Q = min(Q, S)
    c = S // Q
    zxbcdt = precision.einsum(mode, "bsd,de->bse", u, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    xpad = jnp.pad(xbc, ((0, 0), (W - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(xpad[:, i:i + S] * p["conv_w"][i] for i in range(W))
                      + p["conv_b"])
    x, bm, cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])                     # [B,S,H]
    a = dt * -jnp.exp(p["A_log"])                               # [B,S,H]
    grp = jnp.arange(h) // (h // G)
    xr = x.reshape(B, S, h, P)
    x = (xr * dt[..., None]).reshape(B, c, Q, h, P)
    a = a.reshape(B, c, Q, h).transpose(0, 3, 1, 2)             # [B,H,c,Q]
    bm = bm.reshape(B, c, Q, G, N)[:, :, :, grp]                # [B,c,Q,H,N]
    cm = cm.reshape(B, c, Q, G, N)[:, :, :, grp]
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. inside each chunk: the quadratic form
    L = jnp.exp(_segsum(a))                                     # [B,H,c,Q,Q]
    cb = precision.einsum(mode, "bclhn,bcshn->bhcls", cm, bm)
    y_diag = precision.einsum(mode, "bhcls,bcshp->bclhp", cb * L, x)
    # 2. each chunk's final state
    decay = jnp.exp(a_cum[..., -1:] - a_cum)                    # [B,H,c,Q]
    states = precision.einsum(mode, "bclhn,bclhp->bchpn",
                              bm * decay.transpose(0, 2, 3, 1)[..., None], x)
    # 3. the states passed between chunks
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0),
                                                          (1, 0)))))
    states = precision.einsum(mode, "bhzc,bchpn->bzhpn", chunk_decay,
                              states)[:, :-1]
    # 4. the states' read-out
    y_off = precision.einsum(mode, "bclhn,bchpn->bclhp", cm, states) \
        * jnp.exp(a_cum).transpose(0, 2, 3, 1)[..., None]
    y = (y_diag + y_off).reshape(B, S, h, P) + xr * p["D"][:, None]
    y = y.reshape(B, S, di) * jax.nn.silu(z)
    y = _rmsnorm(y.reshape(B, S, G, di // G), 1.0, eps).reshape(B, S, di) \
        * p["norm"]["scale"]
    return precision.einsum(mode, "bse,ed->bsd", y, p["out_proj"]["kernel"])


def _attention(cfg, p, u, mode, block=512):
    """NemotronHAttention on u [B, S, d]: q, k, v projections, no rotary
    embedding, causal softmax(q kᵀ / sqrt(head_dim)) v with the kv heads
    shared by groups of q heads, o projection. Queries in blocks of
    `block`, each recomputed in the backward pass, so the [S, S] scores
    are never whole."""
    B, S, _ = u.shape
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    q = precision.einsum(mode, "bsd,de->bse", u, p["wq"]["kernel"])
    k = precision.einsum(mode, "bsd,de->bse", u, p["wk"]["kernel"])
    v = precision.einsum(mode, "bsd,de->bse", u, p["wv"]["kernel"])
    q = q.reshape(B, S, nkv, nh // nkv, hd)
    k = k.reshape(B, S, nkv, hd)
    v = v.reshape(B, S, nkv, hd)
    block = min(block, S)

    @jax.checkpoint
    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 1)
        s = precision.einsum(mode, "bqkgd,bskd->bkgqs", qb, k) / math.sqrt(hd)
        pos = i * block + jnp.arange(block)
        s = jnp.where(pos[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return precision.einsum(mode, "bkgqs,bskd->bqkgd",
                                jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(one, jnp.arange(S // block))        # [n, B, blk, ...]
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, nh * hd)
    return precision.einsum(mode, "bse,ed->bsd", o, p["wo"]["kernel"])


MIXERS = {"moe": (_moe, "ffn_norm", "moe"), "mamba": (_mamba, "ssm_norm",
                                                       "ssm"),
          "attn": (_attention, "attn_norm", None)}


def reference_loss(cfg, params, batch, mode="float32"):
    """Mean next-token cross-entropy over the held vocabulary: embedding,
    the layers of hybrid_override_pattern in order (each x + mixer(RMSNorm
    x), recomputed in the backward pass), final RMSNorm, untied head.
    Every contraction runs in `bench.precision` `mode`, the rest in
    float32."""
    p = params
    eps = cfg["norm_eps"]
    x = jnp.take(p["embed"]["embedding"], batch["tokens"], axis=0)
    seen = dict.fromkeys(KINDS.values(), 0)
    for ch in cfg["hybrid_override_pattern"]:
        kind = KINDS[ch]
        fn, norm, sub = MIXERS[kind]
        lp = jax.tree.map(lambda a, i=seen[kind]: a[i], p["layers"][kind])
        seen[kind] += 1

        @jax.checkpoint
        def layer(x, lp, fn=fn, norm=norm, sub=sub):
            return x + fn(cfg, lp[sub] if sub else lp,
                          _rmsnorm(x, lp[norm]["scale"], eps), mode)

        x = layer(x, lp)
    x = _rmsnorm(x, p["final_norm"]["scale"], eps)
    logits = precision.einsum(mode, "bsd,dv->bsv", x, p["head"]["kernel"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None],
                                         -1))


# -------------------------------------------------------------------- FLOPs
def train_flops(cfg, seq):
    """Forward + backward (3x forward) FLOPs of one token: per MoE layer
    the router, the held experts at their expected share of the top-k
    slots (top_k * held / router_experts experts a token) and the shared
    expert; per Mamba-2 layer the projections, the conv and the SSD in its
    chunked form (chunk = min(chunk_size, seq)); per attention layer the
    projections and causal attention (seq / 2 keys a query on average);
    the head."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    pat = cfg["hybrid_override_pattern"]
    E, (lo, hi) = cfg["router_experts"], cfg["held_experts"]
    f, fs = cfg["moe_intermediate_size"], \
        cfg["moe_shared_expert_intermediate_size"]
    k = cfg["num_experts_per_tok"]
    moe = 2 * d * E + k * (hi - lo) / E * 4 * d * f + 4 * d * fs
    di, h, P, G, N = _mamba_sizes(cfg)
    q = min(cfg["chunk_size"], seq)
    mamba = (2 * d * (2 * di + 2 * G * N + h) + 2 * di * d
             + 2 * cfg["conv_kernel"] * (di + 2 * G * N)
             + 2 * q * G * N + 2 * q * h * P + 2 * 2 * h * P * N)
    nh, nkv, hd = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    attn = 2 * d * hd * (2 * nh + 2 * nkv) + 2 * 2 * nh * hd * seq / 2
    return 3 * (pat.count("E") * moe + pat.count("M") * mamba
                + pat.count("*") * attn + 2 * d * V)
