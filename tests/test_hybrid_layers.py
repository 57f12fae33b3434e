"""The one-mixer hybrid's layers against plain references on the CPU:
grouped SSD and its gated norm, the expert-share MoE layer (its shares add
up to the uncut layer; dispatch drops no slot, with an empty held expert
and group sizes off any tile boundary), the layer pattern's period and
the config's RMSNorm eps."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.models import mamba2 as m2
from repro.models import moe
from repro.models import nn
from repro.models.transformer import LM, pattern_period

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ SSD
@pytest.mark.parametrize("groups", [2, 8])
@pytest.mark.parametrize("with_state", [False, True])
def test_grouped_ssd_matches_the_sequential_recurrence(groups, with_state):
    b, S, H, P, N = 2, 48, 16, 4, 8
    k = jax.random.split(jax.random.PRNGKey(groups), 7)
    xh = jax.random.normal(k[0], (b, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, S, H)))
    A = -jnp.exp(jax.random.normal(k[2], (H,)))
    Bm = jax.random.normal(k[3], (b, S, groups, N))
    Cm = jax.random.normal(k[4], (b, S, groups, N))
    st0 = jax.random.normal(k[5], (b, H, P, N)) if with_state else None
    with jax.default_matmul_precision("highest"):
        y, st = m2.ssd_chunked(xh, dt * A, dt, Bm, Cm, chunk=16,
                               initial_state=st0)
        y_ref, st_ref = m2.ssd_reference(xh, dt * A, dt, Bm, Cm,
                                         initial_state=st0)
    np.testing.assert_allclose(y, y_ref, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(st, st_ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_gated_norm_normalises_each_group(groups):
    s = m2.SSMSpec(d_model=8, d_inner=64, n_heads=16, head_dim=4, state=8,
                   conv_width=4, n_groups=groups)
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    y = jax.random.normal(k[0], (2, 5, 64))
    z = jax.random.normal(k[1], (2, 5, 64))
    p = {"scale": jax.random.normal(k[2], (64,))}
    got = m2.gated_norm(p, s, y, z, eps=1e-5)
    g = (y * jax.nn.silu(z)).reshape(2, 5, groups, 64 // groups)
    want = g / jnp.sqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(got, want.reshape(2, 5, 64) * p["scale"],
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------------ MoE
D, E, TOP, F, FS = 32, 16, 4, 16, 24


def _layer(held=E, key=0):
    return moe.share_init(jax.random.PRNGKey(key), D, F, FS, E, held)


def _share(p, first, held):
    q = dict(p)
    q["w_up"] = p["w_up"][first:first + held]
    q["w_down"] = p["w_down"][first:first + held]
    return q


def _shared_only(p, x):
    h = moe.relu2(jnp.matmul(x, p["shared_up"]["kernel"], precision=HI))
    return jnp.matmul(h, p["shared_down"]["kernel"], precision=HI)


def _dense(p, x, *, first, held, scale=2.5):
    """Every token's top-k slots one by one: sum over its choices that fall
    in [first, first + held) of weight · relu²(x W_up) W_down, plus the
    shared expert."""
    xf = x.reshape(-1, D)
    logits = jnp.matmul(xf, p["router"]["kernel"], precision=HI)
    scores = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(scores + p["router_bias"], TOP)
    w = jnp.take_along_axis(scores, sel, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    out = []
    for t in range(xf.shape[0]):
        y = _shared_only(p, xf[t])
        for i in range(TOP):
            e = int(sel[t, i]) - first
            if 0 <= e < held:
                h = moe.relu2(jnp.matmul(xf[t], p["w_up"][e], precision=HI))
                y = y + w[t, i] * jnp.matmul(h, p["w_down"][e], precision=HI)
        out.append(y)
    return jnp.stack(out).reshape(x.shape)


def test_expert_shares_add_up_to_the_uncut_layer():
    """model-configs §4: the held shares' outputs over a partition of the
    experts, with the shared expert counted once, equal the layer that
    holds every expert."""
    p = _layer()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, D))
    with jax.default_matmul_precision("highest"):
        whole = moe.share_apply(p, x, top_k=TOP, scale=2.5,
                                dtype=jnp.float32)
        shared = _shared_only(p, x)
        parts = sum(moe.share_apply(_share(p, first, 4), x, top_k=TOP,
                                    scale=2.5, first=first,
                                    dtype=jnp.float32) - shared
                    for first in range(0, E, 4))
    np.testing.assert_allclose(parts + shared, whole, rtol=1e-5, atol=1e-5)


def _biased(p, bias):
    q = dict(p)
    q["router_bias"] = bias
    return q


@pytest.mark.parametrize("case", ["one_held_expert_empty", "unaligned_groups",
                                  "every_choice_held", "groups_span_tiles"])
def test_dropless_dispatch_computes_every_slot(case):
    """share_apply against the slot-by-slot sum, forward and gradient: a
    step in which held expert 1 gets no token (its selection bias is -inf
    in effect), one in which the 37 tokens end groups off any 8-row tile
    boundary, one in which every token's every choice is a held expert, so the
    T · min(top_k, held) rows are all used, and one whose four full groups
    of 70 rows cross the grouped matmul's 128-row tiles, beside two empty
    ones."""
    first, held, T = 4, 6, 37
    if case == "groups_span_tiles":
        T = 70
    p = _share(_layer(), first, held)
    bias = jnp.zeros((E,))
    if case == "one_held_expert_empty":
        bias = bias.at[first + 1].set(-1e4)
    if case == "every_choice_held":
        bias = bias.at[first:first + held].set(1e4)
    if case == "groups_span_tiles":
        bias = bias.at[jnp.array([4, 6, 7, 9])].set(1e4)
    p = _biased(p, bias)
    x = jax.random.normal(jax.random.PRNGKey(2), (1, T, D))

    xf = x.reshape(-1, D)
    _, sel = jax.lax.top_k(jax.nn.sigmoid(xf @ p["router"]["kernel"])
                           + bias, TOP)
    counts = np.array([(np.asarray(sel) == first + e).sum()
                       for e in range(held)])
    if case == "one_held_expert_empty":
        assert counts[1] == 0 and counts.sum() > 0
    if case == "unaligned_groups":
        assert any(end % 8 for end in np.cumsum(counts))
    if case == "every_choice_held":
        assert counts.sum() == T * TOP
    if case == "groups_span_tiles":
        ends = np.cumsum(counts)
        assert list(counts) == [70, 0, 70, 70, 0, 70]
        assert ends[-1] > moe.TILE_ROWS and any(e % moe.TILE_ROWS
                                                for e in ends)

    def f(p, x):
        return moe.share_apply(p, x, top_k=TOP, scale=2.5, first=first,
                               dtype=jnp.float32)

    def g(p, x):
        return _dense(p, x, first=first, held=held)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(f(p, x), g(p, x), rtol=1e-5, atol=1e-5)
        ct = jax.random.normal(jax.random.PRNGKey(3), x.shape)
        gf = jax.grad(lambda p, x: jnp.sum(f(p, x) * ct), (0, 1))(p, x)
        gg = jax.grad(lambda p, x: jnp.sum(g(p, x) * ct), (0, 1))(p, x)
    for a, b in zip(jax.tree.leaves(gf), jax.tree.leaves(gg)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_dispatch_under_vmap_runs_pod_by_pod():
    """The pod round vmaps the local round over pods; the grouped matmul
    then runs one pod at a time and gives each pod its own result and
    gradient."""
    p = _share(_layer(), 0, 8)
    xs = jax.random.normal(jax.random.PRNGKey(4), (3, 1, 20, D))

    def loss(p, x):
        return jnp.sum(jnp.square(moe.share_apply(
            p, x, top_k=TOP, scale=2.5, dtype=jnp.float32)))

    with jax.default_matmul_precision("highest"):
        vm = jax.vmap(jax.value_and_grad(loss), (None, 0))(p, xs)
        one = [jax.value_and_grad(loss)(p, x) for x in xs]
    for i, (l, g) in enumerate(one):
        np.testing.assert_allclose(vm[0][i], l, rtol=1e-5)
        for a, b in zip(jax.tree.leaves(vm[1]), jax.tree.leaves(g)):
            np.testing.assert_allclose(a[i], b, rtol=1e-4, atol=1e-5)


# ------------------------------------------------------------ the stack
@pytest.mark.parametrize("pattern,period", [("EMEMEM*", "EMEMEM*"),
                                            ("ME*ME*", "ME*"),
                                            ("MMMM", "M")])
def test_pattern_period(pattern, period):
    assert pattern_period(pattern) == period


def test_rmsnorm_eps_is_a_config_value():
    base = ArchConfig(name="t", family="dense", n_layers=1, d_model=16,
                      n_heads=2, n_kv_heads=1, d_ff=32, vocab=64)
    assert base.norm_eps == 1e-6
    x = 1e-3 * jax.random.normal(jax.random.PRNGKey(0), (3, 16))
    p = nn.rmsnorm_init(16)
    from repro.models.transformer import _norm_apply
    for eps in (1e-6, 1e-5):
        cfg = dataclasses.replace(base, norm_eps=eps)
        np.testing.assert_allclose(_norm_apply(cfg, p, x),
                                   nn.rmsnorm_apply(p, x, eps=eps), rtol=1e-6)
    assert not np.allclose(_norm_apply(base, p, x),
                           _norm_apply(dataclasses.replace(base,
                                                           norm_eps=1e-5),
                                       p, x), rtol=1e-3)


def test_pattern_model_holds_one_stack_per_kind_and_trains():
    cfg = ArchConfig(name="t", family="hybrid", n_layers=6, d_model=32,
                     n_heads=4, n_kv_heads=2, head_dim=8, d_ff=16, vocab=64,
                     layer_pattern="ME*ME*", n_experts=E, moe_top_k=TOP,
                     held_experts=4, shared_d_ff=FS, routed_scale=2.5,
                     ssm_state=8, ssm_head_dim=8, ssm_n_heads=8,
                     ssm_groups=2, ssm_chunk=8, use_rope=False,
                     mlp_type="none", tie_embeddings=False)
    lm = LM(cfg, dtype=jnp.float32)
    params = lm.init(jax.random.PRNGKey(0))
    assert sorted(params["layers"]) == ["attn", "mamba", "moe"]
    assert params["layers"]["moe"]["moe"]["w_up"].shape == (2, 4, 32, 16)
    assert params["head"]["kernel"].shape == (32, 64)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    loss, g = jax.jit(jax.value_and_grad(lm.loss))(
        params, {"tokens": tok, "labels": tok})
    assert np.isfinite(loss)
    assert all(np.isfinite(x).all() for x in jax.tree.leaves(g))
    with pytest.raises(NotImplementedError):
        lm.prefill(params, {"tokens": tok})
