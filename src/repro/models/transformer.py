"""Unified decoder/encoder stack covering all assigned families.

One scanned block structure per family (uniform pytree across layers →
jax.lax.scan over stacked [L, ...] params keeps the HLO O(1) in depth):

  dense  : attn + (gated|gelu) MLP            (gemma3 / starcoder2 / stablelm …)
  moe    : attn + MoE                          (grok-1, qwen3-moe)
  ssm    : mamba2 block only                   (mamba2-780m)
  hybrid : parallel attn+SSM heads, then MLP   (hymba)
  audio  : non-causal attn + MLP encoder       (hubert)
  vlm    : prefix-LM decoder over [patches; text]  (paligemma)

A config with a `layer_pattern` (Nemotron-H: "M" Mamba-2, "E" expert
share, "*" attention) instead holds one stack per kind of one-mixer layer,
[n_kind, ...] each, and scans over whole periods of the pattern, running
one period's layers in their published order inside the scan body.

Mixed local/global attention (gemma3's 5:1, hymba's 3 full layers) is
handled INSIDE the scan with a per-layer dynamic window scalar — sliding-
window layers get w, full layers get S+1 — so the layer pytree stays
uniform. Blocks are wrapped in jax.checkpoint (remat) for training.

Losses use a sequence-chunked cross-entropy so the [B, S, vocab] logits
tensor is never materialized (vocab up to 262k).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import mamba2 as m2
from repro.models import moe as moe_lib
from repro.models import nn
from repro.models.attention import decode_attention, flash_attention, rope

FULL_WINDOW = 1 << 30


def explicit_gather(x, spec):
    """All-gather a sharded leaf to full size via an EXPLICIT collective in
    a shard_map manual region. Unlike with_sharding_constraint, SPMD cannot
    hoist/commute this out of a layer scan (it satisfies a replication
    constraint by replicating the whole [L, ...] stack instead — observed
    +15 GiB). The transpose is a reduce-scatter, so grads land back on the
    FSDP shards automatically."""
    from jax.sharding import PartitionSpec as P
    entries = [(d, e) for d, e in enumerate(spec) if e is not None]
    if not entries:
        return x
    axes = []
    for _, e in entries:
        axes += list(e) if isinstance(e, (tuple, list)) else [e]

    def fn(loc):
        for dim, e in entries:
            for ax in (e if isinstance(e, (tuple, list)) else (e,)):
                loc = jax.lax.all_gather(loc, ax, axis=dim, tiled=True)
        return loc

    return jax.shard_map(fn, in_specs=(spec,),
                         out_specs=P(*[None] * x.ndim),
                         axis_names=set(axes), check_vma=False)(x)


def _norm_init(cfg, d):
    return nn.rmsnorm_init(d) if cfg.norm_type == "rms" else nn.layernorm_init(d)


def _norm_apply(cfg, p, x):
    return nn.rmsnorm_apply(p, x, eps=cfg.norm_eps) \
        if cfg.norm_type == "rms" else nn.layernorm_apply(p, x)


# ------------------------------------------------------------------ block init
def block_init(key, cfg: ArchConfig, *, param_dtype=jnp.float32):
    d = cfg.d_model
    hd = cfg.head_dim_
    ks = list(jax.random.split(key, 12))
    p: dict[str, Any] = {}
    if cfg.has_attention:
        p["attn_norm"] = _norm_init(cfg, d)
        p["wq"] = nn.linear_init(ks[0], d, cfg.n_heads * hd, use_bias=False,
                                 param_dtype=param_dtype)
        p["wk"] = nn.linear_init(ks[1], d, cfg.n_kv_heads * hd, use_bias=False,
                                 param_dtype=param_dtype)
        p["wv"] = nn.linear_init(ks[2], d, cfg.n_kv_heads * hd, use_bias=False,
                                 param_dtype=param_dtype)
        p["wo"] = nn.linear_init(ks[3], cfg.n_heads * hd, d, use_bias=False,
                                 param_dtype=param_dtype)
    if cfg.has_ssm:
        p["ssm_norm"] = _norm_init(cfg, d)
        p["ssm"] = m2.mamba2_init(ks[4], m2.spec_from_cfg(cfg),
                                  param_dtype=param_dtype)
    if cfg.n_experts:
        p["ffn_norm"] = _norm_init(cfg, d)
        p["moe"] = moe_lib.moe_init(ks[5], d, cfg.d_ff, cfg.n_experts,
                                    param_dtype=param_dtype)
    elif cfg.mlp_type == "gated":
        p["ffn_norm"] = _norm_init(cfg, d)
        p["w_gate"] = nn.linear_init(ks[6], d, cfg.d_ff, use_bias=False,
                                     param_dtype=param_dtype)
        p["w_up"] = nn.linear_init(ks[7], d, cfg.d_ff, use_bias=False,
                                   param_dtype=param_dtype)
        p["w_down"] = nn.linear_init(ks[8], cfg.d_ff, d, use_bias=False,
                                     param_dtype=param_dtype)
    elif cfg.mlp_type == "gelu":
        p["ffn_norm"] = _norm_init(cfg, d)
        p["fc1"] = nn.linear_init(ks[6], d, cfg.d_ff, param_dtype=param_dtype)
        p["fc2"] = nn.linear_init(ks[7], cfg.d_ff, d, param_dtype=param_dtype)
    return p


# ------------------------------------------------------------- block sub-parts
def _attn_full(cfg, p, x, window, *, positions, dtype, prefix_len=0):
    """Full-sequence attention (train/prefill). Returns (out, (k, v))."""
    B, S, _ = x.shape
    hd = cfg.head_dim_
    h = _norm_apply(cfg, p["attn_norm"], x)
    with jax.named_scope("attn"):
        q = nn.linear_apply(p["wq"], h, dtype=dtype).reshape(B, S, cfg.n_heads,
                                                             hd)
        k = nn.linear_apply(p["wk"], h, dtype=dtype).reshape(B, S,
                                                             cfg.n_kv_heads, hd)
        v = nn.linear_apply(p["wv"], h, dtype=dtype).reshape(B, S,
                                                             cfg.n_kv_heads, hd)
        if cfg.use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
        o = flash_attention(q, k, v, causal=cfg.causal, window=window,
                            prefix_len=prefix_len)
        out = nn.linear_apply(p["wo"], o.reshape(B, S, -1), dtype=dtype)
    return out, (k, v)


def _quantize_kv(x):
    """Per-(position, kv-head) symmetric int8: x [B, S, KV, hd] →
    (int8 codes, fp32 scales [B, S, KV])."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    codes = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                     -127, 127).astype(jnp.int8)
    return codes, scale


def _attn_decode(cfg, p, x, cache, cur_index, window, *, dtype):
    """One-token attention against the cache. Returns (out, new_cache_kv).
    Supports bf16 caches and int8 caches (with per-position scales — the
    dequant folds into the logits/PV einsums, so the HBM stream stays
    int8: halves the decode's memory-bandwidth roofline term)."""
    B = x.shape[0]
    hd = cfg.head_dim_
    int8_cache = "k_scale" in cache
    h = _norm_apply(cfg, p["attn_norm"], x)
    q = nn.linear_apply(p["wq"], h, dtype=dtype).reshape(B, 1, cfg.n_heads, hd)
    k = nn.linear_apply(p["wk"], h, dtype=dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    v = nn.linear_apply(p["wv"], h, dtype=dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    pos = cur_index[None]                                  # [1]
    q = rope(q, pos, cfg.rope_theta)
    k = rope(k, pos, cfg.rope_theta)
    kc, vc = cache["k"], cache["v"]
    new_cache = {}
    if int8_cache:
        k8, ks = _quantize_kv(k)
        v8, vs = _quantize_kv(v)
        upd = lambda c, u: jax.lax.dynamic_update_slice_in_dim(
            c, u.astype(c.dtype), cur_index, 1)
        kc, vc = upd(kc, k8), upd(vc, v8)
        kss = upd(cache["k_scale"], ks)
        vss = upd(cache["v_scale"], vs)
        new_cache.update(k_scale=kss, v_scale=vss)
        o = decode_attention(q, kc, vc, cur_index, window=window,
                             k_scale=kss, v_scale=vss)
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype),
                                                 cur_index, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype),
                                                 cur_index, 1)
        o = decode_attention(q, kc, vc, cur_index, window=window)
    out = nn.linear_apply(p["wo"], o.reshape(B, 1, -1), dtype=dtype)
    new_cache.update(k=kc, v=vc)
    return out, new_cache


def _ffn(cfg, p, x, *, dtype, moe_axes=None):
    if cfg.n_experts:
        h = _norm_apply(cfg, p["ffn_norm"], x)
        return moe_lib.moe_apply(p["moe"], h, n_experts=cfg.n_experts,
                                 top_k=cfg.moe_top_k,
                                 capacity_factor=cfg.capacity_factor,
                                 dtype=dtype, shard_tokens_axes=moe_axes)
    if cfg.mlp_type == "gated":
        h = _norm_apply(cfg, p["ffn_norm"], x)
        g = jax.nn.silu(nn.linear_apply(p["w_gate"], h, dtype=dtype))
        u = nn.linear_apply(p["w_up"], h, dtype=dtype)
        return nn.linear_apply(p["w_down"], g * u, dtype=dtype)
    if cfg.mlp_type == "gelu":
        h = _norm_apply(cfg, p["ffn_norm"], x)
        h = nn.gelu(nn.linear_apply(p["fc1"], h, dtype=dtype))
        return nn.linear_apply(p["fc2"], h, dtype=dtype)
    return None


# ----------------------------------------------------------------- block apply
def block_train(cfg: ArchConfig, p, x, window, *, positions, dtype,
                prefix_len=0, collect_cache: bool = False, moe_axes=None):
    """Full-sequence block. Returns (x, cache_layer|None)."""
    cache = {}
    if cfg.parallel_ssm:                      # hymba: attn ‖ ssm on same input
        a_out, kv = _attn_full(cfg, p, x, window, positions=positions,
                               dtype=dtype, prefix_len=prefix_len)
        s_in = _norm_apply(cfg, p["ssm_norm"], x)
        if collect_cache:
            s_out, (st, cv) = m2.mamba2_train(p["ssm"], m2.spec_from_cfg(cfg),
                                              s_in, dtype=dtype,
                                              return_state=True)
            cache.update(k=kv[0], v=kv[1], ssm=st, conv=cv)
        else:
            s_out = m2.mamba2_train(p["ssm"], m2.spec_from_cfg(cfg), s_in,
                                    dtype=dtype)
        x = x + 0.5 * (a_out + s_out)
        if collect_cache and cfg.has_attention:
            pass
    elif cfg.has_ssm:                         # mamba2: SSM is the mixer
        s_in = _norm_apply(cfg, p["ssm_norm"], x)
        if collect_cache:
            s_out, (st, cv) = m2.mamba2_train(p["ssm"], m2.spec_from_cfg(cfg),
                                              s_in, dtype=dtype,
                                              return_state=True)
            cache.update(ssm=st, conv=cv)
        else:
            s_out = m2.mamba2_train(p["ssm"], m2.spec_from_cfg(cfg), s_in,
                                    chunk=cfg.ssm_chunk, dtype=dtype,
                                    eps=cfg.norm_eps)
        x = x + s_out
    else:
        a_out, kv = _attn_full(cfg, p, x, window, positions=positions,
                               dtype=dtype, prefix_len=prefix_len)
        x = x + a_out
        if collect_cache:
            cache.update(k=kv[0], v=kv[1])

    f = _ffn(cfg, p, x, dtype=dtype, moe_axes=moe_axes)
    if f is not None:
        x = x + f
    return x, (cache if collect_cache else None)


def block_decode(cfg: ArchConfig, p, x, cache, cur_index, window, *, dtype,
                 moe_axes=None):
    """One-token block vs cache. Returns (x, new_cache)."""
    new_cache = dict(cache)
    if cfg.parallel_ssm:
        a_out, kv_cache = _attn_decode(cfg, p, x, cache, cur_index, window,
                                       dtype=dtype)
        s_in = _norm_apply(cfg, p["ssm_norm"], x)
        s_out, st, cv = m2.mamba2_decode(p["ssm"], m2.spec_from_cfg(cfg),
                                         s_in, cache["ssm"], cache["conv"],
                                         dtype=dtype)
        x = x + 0.5 * (a_out + s_out)
        new_cache.update(ssm=st, conv=cv, **kv_cache)
    elif cfg.has_ssm:
        s_in = _norm_apply(cfg, p["ssm_norm"], x)
        s_out, st, cv = m2.mamba2_decode(p["ssm"], m2.spec_from_cfg(cfg),
                                         s_in, cache["ssm"], cache["conv"],
                                         dtype=dtype)
        x = x + s_out
        new_cache.update(ssm=st, conv=cv)
    else:
        a_out, kv_cache = _attn_decode(cfg, p, x, cache, cur_index, window,
                                       dtype=dtype)
        x = x + a_out
        new_cache.update(**kv_cache)
    f = _ffn(cfg, p, x, dtype=dtype, moe_axes=moe_axes)
    if f is not None:
        x = x + f
    return x, new_cache


# ------------------------------------------------------- one-mixer layer stacks
KINDS = {"M": "mamba", "E": "moe", "*": "attn"}


def pattern_period(pattern: str) -> str:
    """The shortest string whose repeats make up `pattern`."""
    n = len(pattern)
    for p in range(1, n + 1):
        if n % p == 0 and pattern[:p] * (n // p) == pattern:
            return pattern[:p]
    raise ValueError("empty layer pattern")


def mixer_init(key, cfg: ArchConfig, kind: str, *, param_dtype=jnp.float32):
    """One layer of a `layer_pattern` model: a pre-norm and one mixer."""
    d, hd = cfg.d_model, cfg.head_dim_
    if kind == "mamba":
        return {"ssm_norm": _norm_init(cfg, d),
                "ssm": m2.mamba2_init(key, m2.spec_from_cfg(cfg),
                                      param_dtype=param_dtype)}
    if kind == "moe":
        return {"ffn_norm": _norm_init(cfg, d),
                "moe": moe_lib.share_init(
                    key, d, cfg.d_ff, cfg.shared_d_ff, cfg.n_experts,
                    cfg.held_experts or cfg.n_experts,
                    param_dtype=param_dtype)}
    ks = jax.random.split(key, 4)
    lin = functools.partial(nn.linear_init, use_bias=False,
                            param_dtype=param_dtype)
    return {"attn_norm": _norm_init(cfg, d),
            "wq": lin(ks[0], d, cfg.n_heads * hd),
            "wk": lin(ks[1], d, cfg.n_kv_heads * hd),
            "wv": lin(ks[2], d, cfg.n_kv_heads * hd),
            "wo": lin(ks[3], cfg.n_heads * hd, d)}


def mixer_apply(cfg: ArchConfig, kind: str, p, x, *, positions, dtype):
    """x + mixer(norm(x)) for one layer of a `layer_pattern` model."""
    if kind == "mamba":
        return x + m2.mamba2_train(
            p["ssm"], m2.spec_from_cfg(cfg),
            _norm_apply(cfg, p["ssm_norm"], x), chunk=cfg.ssm_chunk,
            dtype=dtype, eps=cfg.norm_eps)
    if kind == "moe":
        return x + moe_lib.share_apply(
            p["moe"], _norm_apply(cfg, p["ffn_norm"], x),
            top_k=cfg.moe_top_k, scale=cfg.routed_scale, dtype=dtype)
    return x + _attn_full(cfg, p, x, None, positions=positions,
                          dtype=dtype)[0]


# -------------------------------------------------------------------- LM model
@dataclasses.dataclass(frozen=True)
class LM:
    """Facade: init / loss / prefill / decode for one ArchConfig."""
    cfg: ArchConfig
    dtype: Any = jnp.bfloat16       # compute dtype
    param_dtype: Any = jnp.float32  # storage dtype (bf16 for the full archs)
    remat: bool = True
    remat_policy: str = "nothing"   # nothing | dots
    use_scan: bool = True           # scan over layers (False: unrolled —
                                    # used by the dry-run cost extrapolation)
    batch_axes: tuple | None = None  # mesh axes for the activation batch dim;
                                     # set by the launcher (e.g. ("data",) or
                                     # ("pod","data")) to pin the residual-
                                     # stream layout under GSPMD. None = no
                                     # constraint (single-device tests).
    moe_dispatch_axes: tuple | None = None  # shard-local MoE dispatch over
                                     # these (token/batch) mesh axes.
    zero3_layer: bool = False        # streamed ZeRO-3: fully gather each
                                     # layer's weights INSIDE the scan body
                                     # (one layer in flight), for the pure-DP
                                     # layout where batch covers the mesh.
    layer_param_specs: Any = None    # pytree of PartitionSpec for ONE layer
                                     # (stack spec minus the L dim); required
                                     # when zero3_layer is set.
    kv_dtype: str = "compute"        # "compute" (bf16/f32) | "int8" — int8
                                     # stores per-(position, kv-head) scales
                                     # alongside and halves the decode HBM
                                     # roofline term (§Perf bonus cell).
    act_seq_axis: str | None = None  # Megatron-style sequence parallelism:
                                     # shard the residual stream's S dim over
                                     # this mesh axis (attention gathers K/V
                                     # around it). None = S replicated.

    def _constrain(self, x):
        """Residual stream: [B(batch_axes), S(act_seq_axis), d]. Without this
        GSPMD may drop the batch sharding and emit full-batch partial-sum
        all-reduces (observed: 3.4 GiB fp32 ARs on stablelm train_4k)."""
        if self.batch_axes is None:
            return x
        from jax.sharding import PartitionSpec as P
        rest = [None] * (x.ndim - 1)
        if self.act_seq_axis is not None and x.ndim >= 3 and x.shape[1] > 1:
            rest[0] = self.act_seq_axis
        spec = P(tuple(self.batch_axes), *rest)
        return jax.lax.with_sharding_constraint(x, spec)

    # ------------------------------------------------------------------ init
    def init(self, key) -> dict:
        cfg = self.cfg
        pd = self.param_dtype
        k_emb, k_layers, k_head, k_fe = jax.random.split(key, 4)
        params: dict[str, Any] = {}
        if cfg.frontend == "tokens" or cfg.frontend == "patches":
            params["embed"] = nn.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                                param_dtype=pd)
        if cfg.frontend == "frames":
            params["frontend"] = nn.linear_init(k_fe, cfg.frame_dim,
                                                cfg.d_model, param_dtype=pd)
            params["head"] = nn.linear_init(k_head, cfg.d_model, cfg.vocab,
                                            param_dtype=pd)
        if cfg.frontend == "patches":
            params["patch_proj"] = nn.linear_init(k_fe, cfg.patch_dim,
                                                  cfg.d_model, param_dtype=pd)
        if cfg.frontend == "tokens" and not cfg.tie_embeddings:
            params["head"] = nn.linear_init(k_head, cfg.d_model, cfg.vocab,
                                            use_bias=False, param_dtype=pd)
        if cfg.layer_pattern:
            params["layers"] = {}
            for i, (ch, kind) in enumerate(KINDS.items()):
                n = cfg.layer_pattern.count(ch)
                if n:
                    keys = jax.random.split(jax.random.fold_in(k_layers, i), n)
                    params["layers"][kind] = jax.vmap(
                        lambda k, kind=kind: mixer_init(
                            k, cfg, kind, param_dtype=pd))(keys)
            params["final_norm"] = _norm_init(cfg, cfg.d_model)
            return params
        layer_keys = jax.random.split(k_layers, cfg.n_layers)
        params["layers"] = jax.vmap(
            lambda k: block_init(k, cfg, param_dtype=pd))(layer_keys)
        params["final_norm"] = _norm_init(cfg, cfg.d_model)
        return params

    # ------------------------------------------------------------- internals
    def _windows(self, S: int) -> jax.Array:
        cfg = self.cfg
        return jnp.asarray([cfg.window if k == "sw" else FULL_WINDOW
                            for k in cfg.layer_kinds()], jnp.int32)

    def _pattern_stack(self, params, x, *, positions):
        """Scan over whole periods of `layer_pattern`; inside the body the
        period's layers run in order, each rematerialised on its own."""
        cfg = self.cfg
        period = pattern_period(cfg.layer_pattern)
        reps = len(cfg.layer_pattern) // len(period)
        stacks = {kind: jax.tree.map(
            lambda a: a.reshape((reps, a.shape[0] // reps) + a.shape[1:]), st)
            for kind, st in params["layers"].items()}

        def layer(kind, lp, h):
            h = self._constrain(h)
            return mixer_apply(cfg, kind, lp, h, positions=positions,
                               dtype=self.dtype)

        if self.remat:
            layer = jax.checkpoint(
                layer, static_argnums=(0,),
                policy=jax.checkpoint_policies.nothing_saveable)

        def body(h, lps):
            seen = dict.fromkeys(lps, 0)
            for ch in period:
                kind = KINDS[ch]
                lp = jax.tree.map(lambda a, i=seen[kind]: a[i], lps[kind])
                seen[kind] += 1
                h = layer(kind, lp, h)
            return self._constrain(h), None

        x, _ = jax.lax.scan(body, x, stacks)
        return _norm_apply(cfg, params["final_norm"], x), None

    def _stack(self, params, x, *, positions, prefix_len=0,
               collect_cache=False):
        cfg = self.cfg
        if cfg.layer_pattern:
            if collect_cache or prefix_len:
                raise NotImplementedError(
                    "layer_pattern models run training steps only")
            return self._pattern_stack(params, x, positions=positions)
        windows = self._windows(x.shape[1])

        def body(h, xs):
            lp, w = xs
            if self.zero3_layer:
                from jax.sharding import PartitionSpec as P
                lp = jax.tree.map(
                    explicit_gather, lp, self.layer_param_specs,
                    is_leaf=lambda s: isinstance(s, P))
            h = self._constrain(h)
            out, cache = block_train(cfg, lp, h, w, positions=positions,
                                     dtype=self.dtype, prefix_len=prefix_len,
                                     collect_cache=collect_cache,
                                     moe_axes=self.moe_dispatch_axes)
            return self._constrain(out), cache

        f = body
        if self.remat:
            policy = (jax.checkpoint_policies.nothing_saveable
                      if self.remat_policy == "nothing"
                      else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
            f = jax.checkpoint(body, policy=policy)
        if self.use_scan:
            x, caches = jax.lax.scan(f, x, (params["layers"], windows))
        else:  # unrolled (dry-run per-layer cost extrapolation)
            cache_list = []
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                x, c = f(x, (lp, windows[i]))
                cache_list.append(c)
            caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *cache_list)
                      if collect_cache else None)
        x = _norm_apply(cfg, params["final_norm"], x)
        return x, caches

    def _embed_inputs(self, params, batch):
        """Returns (x [B,S,d], positions [S], prefix_len, label_offset)."""
        cfg = self.cfg
        if cfg.frontend == "frames":
            x = nn.linear_apply(params["frontend"], batch["frames"],
                                dtype=self.dtype)
            S = x.shape[1]
            return self._constrain(x), jnp.arange(S), 0
        if cfg.frontend == "patches":
            pe = nn.linear_apply(params["patch_proj"], batch["patches"],
                                 dtype=self.dtype)
            te = nn.embedding_apply(params["embed"], batch["tokens"],
                                    dtype=self.dtype)
            x = jnp.concatenate([pe, te], axis=1)
            S = x.shape[1]
            return self._constrain(x), jnp.arange(S), cfg.n_patches
        x = nn.embedding_apply(params["embed"], batch["tokens"],
                               dtype=self.dtype)
        return self._constrain(x), jnp.arange(x.shape[1]), 0

    # ------------------------------------------------------------------ loss
    def loss(self, params, batch) -> jax.Array:
        cfg = self.cfg
        x, positions, prefix = self._embed_inputs(params, batch)
        h, _ = self._stack(params, x, positions=positions, prefix_len=prefix)
        labels = batch["labels"]
        if cfg.frontend == "frames":       # per-frame classification (stub)
            logits = nn.linear_apply(params["head"], h, dtype=jnp.float32)
            return _ce(logits, labels)
        if cfg.frontend == "patches":      # loss on text positions only
            h = h[:, cfg.n_patches:, :]
        # next-token LM loss, chunked over sequence
        head = (params["head"]["kernel"].T if "head" in params
                else params["embed"]["embedding"])
        return chunked_ce_loss(h, head, labels)

    # --------------------------------------------------------------- prefill
    def prefill(self, params, batch):
        cfg = self.cfg
        x, positions, prefix = self._embed_inputs(params, batch)
        h, caches = self._stack(params, x, positions=positions,
                                prefix_len=prefix, collect_cache=True)
        last = h[:, -1, :]
        logits = self._head(params, last[:, None, :])
        return logits, caches

    def _head(self, params, h):
        cfg = self.cfg
        if cfg.frontend == "frames":
            return nn.linear_apply(params["head"], h, dtype=jnp.float32)
        emb = params["embed"]["embedding"].astype(self.dtype)
        return (h.astype(self.dtype) @ emb.T).astype(jnp.float32)

    # ---------------------------------------------------------------- decode
    def decode_step(self, params, cache, token, cur_index):
        """token: [B, 1] int32; cur_index: scalar int32 (position to write).
        Returns (logits [B, 1, vocab], new_cache)."""
        cfg = self.cfg
        x = self._constrain(
            nn.embedding_apply(params["embed"], token, dtype=self.dtype))
        windows = self._windows(1)

        def body(h, xs):
            lp, cl, w = xs
            out, new_cl = block_decode(cfg, lp, h, cl, cur_index, w,
                                       dtype=self.dtype,
                                       moe_axes=self.moe_dispatch_axes)
            return self._constrain(out), new_cl

        if self.use_scan:
            x, new_cache = jax.lax.scan(body, x,
                                        (params["layers"], cache, windows))
        else:
            cls = []
            for i in range(cfg.n_layers):
                lp = jax.tree.map(lambda a: a[i], params["layers"])
                cl = jax.tree.map(lambda a: a[i], cache)
                x, ncl = body(x, (lp, cl, windows[i]))
                cls.append(ncl)
            new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *cls)
        x = _norm_apply(cfg, params["final_norm"], x)
        return self._head(params, x), new_cache

    # ------------------------------------------------------------- cache init
    def init_cache(self, B: int, S: int, *, dtype=None):
        """Zeroed cache pytree with leading layer dim [L, ...]."""
        cfg = self.cfg
        dt = dtype or self.dtype
        L = cfg.n_layers
        c: dict[str, Any] = {}
        if cfg.has_attention:
            hd = cfg.head_dim_
            if self.kv_dtype == "int8":
                c["k"] = jnp.zeros((L, B, S, cfg.n_kv_heads, hd), jnp.int8)
                c["v"] = jnp.zeros((L, B, S, cfg.n_kv_heads, hd), jnp.int8)
                c["k_scale"] = jnp.zeros((L, B, S, cfg.n_kv_heads),
                                         jnp.float32)
                c["v_scale"] = jnp.zeros((L, B, S, cfg.n_kv_heads),
                                         jnp.float32)
            else:
                c["k"] = jnp.zeros((L, B, S, cfg.n_kv_heads, hd), dt)
                c["v"] = jnp.zeros((L, B, S, cfg.n_kv_heads, hd), dt)
        if cfg.has_ssm:
            s = m2.spec_from_cfg(cfg)
            c["ssm"] = jnp.zeros((L, B, s.n_heads, s.head_dim, s.state),
                                 jnp.float32)
            c["conv"] = jnp.zeros((L, B, s.conv_width - 1,
                                   s.d_inner + 2 * s.state), jnp.float32)
        return c

    def cache_specs(self, B: int, S: int):
        return jax.eval_shape(lambda: self.init_cache(B, S))


# ----------------------------------------------------------------------- losses
def _ce(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def chunked_ce_loss(h: jax.Array, embedding: jax.Array, labels: jax.Array,
                    *, chunk: int = 512) -> jax.Array:
    """CE(h @ E^T, labels) without materializing [B, S, V]."""
    B, S, d = h.shape
    chunk = min(chunk, S)
    while S % chunk:        # e.g. vlm text length 3840 → chunk 256
        chunk //= 2
    nc = S // chunk
    hc = h.reshape(B, nc, chunk, d)
    lc = labels.reshape(B, nc, chunk)
    emb = embedding.astype(jnp.bfloat16)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def one(ci):
        logits = (hc[:, ci].astype(jnp.bfloat16) @ emb.T).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, lc[:, ci][..., None], axis=-1)[..., 0]
        return -jnp.sum(ll)

    total = jax.lax.map(one, jnp.arange(nc))
    return jnp.sum(total) / (B * S)
