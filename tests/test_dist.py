"""Distribution tests on 8 placeholder devices.

These run in a SUBPROCESS with XLA_FLAGS set so the main pytest process
keeps its single CPU device (per the dry-run spec)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import PartitionSpec as P, NamedSharding
    """) + textwrap.dedent(body)
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-4000:]}"
    return r.stdout


class TestShardingRules:
    def test_param_specs_follow_rules_and_divisibility(self):
        _run("""
        from repro.configs import get_config
        from repro.dist import sharding as shl
        from repro.models.transformer import LM
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = get_config("gemma3-4b").smoke()
        lm = LM(cfg, dtype=jnp.float32, remat=False)
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        specs = shl.param_specs(shapes, mesh)
        # embedding [V, d]: vocab on model, d on data
        assert specs["embed"]["embedding"] == P("model", "data"), specs["embed"]
        # wq [L, d, H*hd]: fsdp in, tp out
        assert specs["layers"]["wq"]["kernel"] == P(None, "data", "model")
        # wo transpose layout
        assert specs["layers"]["wo"]["kernel"] == P(None, "model", "data")
        # every spec divides its dim
        flat_s = jax.tree_util.tree_leaves_with_path(shapes)
        flat_p = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        for (path, sh), spec in zip(flat_s, flat_p):
            for dim, ax in enumerate(spec):
                if ax is not None:
                    assert sh.shape[dim] % mesh.shape[ax] == 0, (path, spec)
        print("OK")
        """)

    def test_moe_tp_in_expert_layout(self):
        _run("""
        from repro.configs import get_config
        from repro.dist import sharding as shl
        from repro.models.transformer import LM
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        cfg = get_config("qwen3-moe-30b-a3b").smoke()
        lm = LM(cfg, dtype=jnp.float32, remat=False)
        shapes = jax.eval_shape(lm.init, jax.random.key(0))
        specs = shl.param_specs(shapes, mesh)
        # TP-in-expert: [L, E, d(fsdp), f(model)] / w_down [L, E, f(model), d]
        assert specs["layers"]["moe"]["w_gate"] == P(None, None, "data",
                                                     "model")
        assert specs["layers"]["moe"]["w_down"] == P(None, None, "model",
                                                     "data")
        # router replicated (the sharded dispatch broadcasts it)
        assert all(e is None
                   for e in specs["layers"]["moe"]["router"]["kernel"])
        print("OK")
        """)


class TestShardedTraining:
    def test_sharded_train_step_matches_single_device(self):
        """The pjit'd train step on a 2×4 mesh computes THE SAME numbers as
        the unsharded step (GSPMD is semantics-preserving)."""
        _run("""
        from repro.configs import get_config
        from repro.dist import sharding as shl
        from repro.dist.steps import make_train_step
        from repro.models.transformer import LM
        from repro.optim import momentum_sgd

        cfg = dataclasses.replace(get_config("stablelm-3b").smoke(),
                                  vocab=256, n_layers=2)
        lm = LM(cfg, dtype=jnp.float32, remat=True, batch_axes=("data",))
        opt = momentum_sgd(0.01)
        params = lm.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 256, (8, 64)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, 256, (8, 64)),
                                       jnp.int32)}
        step = make_train_step(lm, opt)
        # single device reference
        lm_ref = LM(cfg, dtype=jnp.float32, remat=True)
        _, _, loss_ref = jax.jit(make_train_step(lm_ref, opt))(
            params, opt_state, batch)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        pspec = shl.param_specs(params, mesh)
        ospec = shl.opt_state_specs(jax.eval_shape(lambda: opt_state),
                                    pspec, mesh)
        bspec = shl.batch_specs(batch, mesh, batch_axes=("data",))
        ns = lambda t: shl.named(t, mesh)
        with mesh:
            new_p, _, loss = jax.jit(
                step, in_shardings=(ns(pspec), ns(ospec), ns(bspec)),
                out_shardings=(ns(pspec), ns(ospec),
                               NamedSharding(mesh, P())))(
                params, opt_state, batch)
        assert np.isfinite(float(loss))
        np.testing.assert_allclose(float(loss), float(loss_ref), rtol=2e-4)
        print("OK", float(loss), float(loss_ref))
        """)

    def test_pod_sync_collective(self):
        """FedLuck Eq. 6 over a (pod, data, model) mesh: sync_step averages
        compressed deltas across pods exactly (δ-adaptive path)."""
        _run("""
        from repro.dist.collectives import block_budget, make_pod_sync
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        nb, blk = 8, 64
        dim = nb * blk
        rng = np.random.RandomState(0)
        params = jnp.zeros((nb, blk), jnp.float32)
        deltas = jnp.asarray(rng.randn(2, nb, blk).astype(np.float32))
        residuals = jnp.zeros((2, nb, blk), jnp.float32)
        for rate in (0.6, 0.05):        # dense ring, then compact gather
            sync = make_pod_sync(mesh, dim, rate=rate, eta_g=1.0,
                                 n_blocks=nb)
            with mesh:
                new_p, new_r = jax.jit(sync)(params, deltas, residuals)
            # EF conservation per pod: kept + residual' == delta
            kept = np.asarray(deltas) - np.asarray(new_r)
            # Eq. 6: params' = -mean(kept) over pods
            np.testing.assert_allclose(np.asarray(new_p),
                                       -(kept[0] + kept[1]) / 2,
                                       rtol=1e-4, atol=1e-5)
            nnz = (np.abs(kept) > 0).sum(axis=(1, 2))
            k = round(rate * dim)
            if rate >= 0.5:
                # dense path: exact global threshold → density ≈ rate and
                # kept values are the largest magnitudes
                assert sync.path == "dense"
                assert (nnz <= 1.25 * k + nb).all() and \
                       (nnz >= 0.75 * k - 1).all(), (nnz, k)
                for i in range(2):
                    kmags = np.abs(kept[i])[np.abs(kept[i]) > 0]
                    dmags = np.abs(np.asarray(deltas[i]))[
                        np.abs(kept[i]) == 0]
                    assert kmags.min() >= dmags.max() - 0.05
            else:
                # compact path: per-shard threshold + fixed per-block
                # budget. Capacity-bounded (over-budget entries defer to
                # the next round via EF) and never emptier than half the
                # target; each block respects its slot budget; everything
                # shipped sits far above the bulk of the magnitudes.
                assert sync.path == "compact"
                budget = block_budget(blk, rate)
                assert budget == sync.wire.budget
                assert (nnz <= nb * budget).all() and \
                       (nnz >= 0.5 * k).all(), (nnz, k, nb * budget)
                per_block = (np.abs(kept) > 0).sum(axis=2)
                assert (per_block <= budget).all()
                for i in range(2):
                    kmags = np.abs(kept[i])[np.abs(kept[i]) > 0]
                    assert kmags.min() >= \
                        np.median(np.abs(np.asarray(deltas[i])))
        print("OK")
        """)

    def test_pod_sync_compact_matches_reference_across_crossover(self):
        """Compact (values, indices, count) gather vs the dense-carrier
        reference of the same selection semantics: identical params (fp32)
        and bitwise-identical EF residuals, carried over 3 rounds, for δ on
        both sides of density_crossover."""
        _run("""
        from repro.dist import collectives as col
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        nb, blk = 8, 64
        dim = nb * blk
        crossover = col.density_crossover(2)
        rng = np.random.RandomState(1)
        params = jnp.asarray(rng.randn(nb, blk).astype(np.float32))
        zeros = jnp.zeros((2, nb, blk), jnp.float32)
        for rate in (0.05, 0.6):
            assert (rate < crossover) == (rate == 0.05)
            jc = jax.jit(col.make_pod_sync(mesh, dim, rate=rate,
                                           n_blocks=nb, wire="compact"))
            jr = jax.jit(col.make_pod_sync(mesh, dim, rate=rate,
                                           n_blocks=nb, wire="reference"))
            pc, rc = params, zeros
            pr, rr = params, zeros
            for rnd in range(3):
                d = jnp.asarray(rng.randn(2, nb, blk).astype(np.float32))
                with mesh:
                    pc, rc = jc(pc, d, rc)
                    pr, rr = jr(pr, d, rr)
                assert np.allclose(np.asarray(pc), np.asarray(pr),
                                   rtol=1e-5, atol=1e-6), (rate, rnd)
                assert np.array_equal(np.asarray(rc), np.asarray(rr)), \\
                    (rate, rnd)
            # residuals actually carry: round-2 EF state is nonzero
            assert float(np.abs(np.asarray(rc)).max()) > 0
        # wire-cost model matches the payload the compact sync ships
        sync = col.make_pod_sync(mesh, dim, rate=0.05, n_blocks=nb,
                                 wire="compact")
        per_shard = sync.wire
        assert sync.bytes_per_device == \\
            col.all_gather_bytes(per_shard.dim, 2, 0.05,
                                 n_blocks=per_shard.n_blocks)
        print("OK")
        """)

    def test_pod_sync_dense_shards_match_whole_pod_pipeline(self):
        """The dense path splits each pod's vector over its 4 in-pod chips
        but selects against the whole pod's threshold: bitwise the EF
        residuals of `topk_compress` on each pod's whole vector, over 3
        rounds; and its wire model charges each chip's shard only."""
        _run("""
        from repro.dist import collectives as col
        from repro.kernels import ops
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        nb, blk = 8, 128
        dim = nb * blk
        rng = np.random.RandomState(2)
        params = jnp.asarray(rng.randn(nb, blk).astype(np.float32))
        for rate in (0.6, 0.1):
            sync = col.make_pod_sync(mesh, dim, rate=rate, n_blocks=nb,
                                     wire="dense")
            assert sync.path == "dense"
            assert sync.bytes_per_device == 2.0 * (2 - 1) / 2 * dim / 4 * 4
            jd = jax.jit(sync)
            p, r = params, jnp.zeros((2, nb, blk), jnp.float32)
            p_ref, r_ref = np.asarray(params), np.zeros((2, dim), np.float32)
            for rnd in range(3):
                d = rng.randn(2, nb, blk).astype(np.float32)
                with mesh:
                    p, r = jd(p, jnp.asarray(d), r)
                kept = []
                for i in range(2):
                    out, res, _, _ = ops.topk_compress(
                        jnp.asarray(d[i].reshape(dim)),
                        jnp.asarray(r_ref[i]), rate=rate)
                    kept.append(np.asarray(out))
                    r_ref[i] = np.asarray(res)
                p_ref = p_ref - (kept[0] + kept[1]).reshape(nb, blk) / 2
                assert np.array_equal(np.asarray(r).reshape(2, dim),
                                      r_ref), (rate, rnd)
                assert np.allclose(np.asarray(p), p_ref,
                                   rtol=1e-5, atol=1e-6), (rate, rnd)
            assert float(np.abs(r_ref).max()) > 0
        print("OK")
        """)

    def test_pod_round_step_composes_local_rounds_and_sync(self):
        """make_pod_round_step == (vmapped local rounds) ∘ make_pod_sync,
        and its static wire-bit charge is the sync's compact payload."""
        _run("""
        from repro.configs import get_config
        from repro.core import compression as C
        from repro.dist import collectives as col
        from repro.dist.steps import make_local_round_step, \\
            make_pod_round_step
        from repro.models.transformer import LM
        from repro.optim import momentum_sgd

        cfg = dataclasses.replace(get_config("stablelm-3b").smoke(),
                                  vocab=256, n_layers=1)
        lm = LM(cfg, dtype=jnp.float32, remat=False)
        opt = momentum_sgd(0.01)
        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*3)
        params = lm.init(jax.random.PRNGKey(0))
        flat, spec = C.flatten_pytree(params)
        dim = int(flat.shape[0])
        blk = 256
        nb = -(-dim // blk)
        while nb % 4:       # shard nb over the 4 in-pod chips
            nb += 1
        dim_pad = nb * blk
        rng = np.random.RandomState(0)
        k, B, P_pods = 2, 4, 2
        batches = {
          "tokens": jnp.asarray(rng.randint(0, 256, (P_pods, k, B, 32)),
                                jnp.int32),
          "labels": jnp.asarray(rng.randint(0, 256, (P_pods, k, B, 32)),
                                jnp.int32)}
        pb = jnp.concatenate([flat, jnp.zeros((dim_pad - dim,),
                                              jnp.float32)]).reshape(nb, blk)
        residuals = jnp.zeros((P_pods, nb, blk), jnp.float32)
        opt_states = jax.tree.map(
            lambda x: jnp.stack([x] * P_pods), opt.init(params))

        sync = col.make_pod_sync(mesh, dim_pad, rate=0.05, n_blocks=nb)
        step = make_pod_round_step(lm, opt, k, sync, spec=spec, dim=dim,
                                   n_blocks=nb)
        assert step.wire_bits_per_pod == 4 * sync.wire.payload_bits()
        with mesh:
            new_pb, new_states, new_res, loss = jax.jit(step)(
                pb, opt_states, batches, residuals)
        assert np.isfinite(float(loss))

        # reference: run the local rounds and the sync separately
        local = make_local_round_step(lm, opt, k)
        deltas = []
        for p in range(P_pods):
            ob = jax.tree.map(lambda x: x[p], opt_states)
            bb = jax.tree.map(lambda x: x[p], batches)
            _, _, delta, _ = jax.jit(local)(params, ob, bb)
            fd, _ = C.flatten_pytree(delta)
            deltas.append(np.pad(np.asarray(fd), (0, dim_pad - dim)))
        deltas = jnp.asarray(np.stack(deltas)).reshape(P_pods, nb, blk)
        with mesh:
            ref_pb, ref_res = jax.jit(sync)(pb, deltas, residuals)
        # the composed program and the split reference compile with
        # different layouts/fusions (GSPMD reduce order), so the deltas
        # themselves carry ~1e-3 float noise — loose tolerance here; the
        # bitwise sync-equivalence guarantees live in the test above
        assert np.allclose(np.asarray(new_pb), np.asarray(ref_pb),
                           rtol=1e-3, atol=2e-3)
        assert np.allclose(np.asarray(new_res), np.asarray(ref_res),
                           rtol=1e-3, atol=2e-3)
        print("OK")
        """)

    def test_decode_step_with_sequence_sharded_cache(self):
        """Flash-decoding: KV cache sequence dim sharded over `model`;
        decode result matches the unsharded reference."""
        _run("""
        from repro.configs import get_config
        from repro.dist import sharding as shl
        from repro.models.transformer import LM

        cfg = dataclasses.replace(get_config("gemma3-4b").smoke(),
                                  vocab=128, n_layers=2)
        lm = LM(cfg, dtype=jnp.float32, remat=False)
        params = lm.init(jax.random.PRNGKey(0))
        B, S = 4, 64
        cache = lm.init_cache(B, S)
        rng = np.random.RandomState(1)
        cache = {k: (jnp.asarray(rng.randn(*v.shape).astype(np.float32))
                     if k in ("k", "v") else v) for k, v in cache.items()}
        tok = jnp.asarray(rng.randint(0, 128, (B, 1)), jnp.int32)
        idx = jnp.int32(40)
        ref_logits, _ = jax.jit(lm.decode_step)(params, cache, tok, idx)

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        pspec = shl.param_specs(params, mesh)
        cspec = shl.cache_specs(cache, mesh, batch_axes=("data",))
        # assert the cache S dim really is sharded
        assert cspec["k"][2] == "model", cspec["k"]
        ns = lambda t: shl.named(t, mesh)
        with mesh:
            logits, _ = jax.jit(
                lm.decode_step,
                in_shardings=(ns(pspec), ns(cspec),
                              NamedSharding(mesh, P("data")),
                              NamedSharding(mesh, P())),
                out_shardings=(NamedSharding(mesh, P()), ns(cspec)))(
                params, cache, tok, idx)
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(ref_logits), rtol=2e-4,
                                   atol=2e-4)
        print("OK")
        """)


class TestMicrobatching:
    def test_grad_accumulation_matches_full_batch(self):
        """make_train_step(microbatches=n) computes the same update as the
        full-batch step (fault-free math under activation-memory savings)."""
        _run("""
        from repro.configs import get_config
        from repro.dist.steps import make_train_step
        from repro.models.transformer import LM
        from repro.optim import momentum_sgd

        cfg = dataclasses.replace(get_config("stablelm-3b").smoke(),
                                  vocab=128, n_layers=2)
        lm = LM(cfg, dtype=jnp.float32, remat=True)
        opt = momentum_sgd(0.01)
        params = lm.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        rng = np.random.RandomState(0)
        batch = {"tokens": jnp.asarray(rng.randint(0, 128, (8, 32)),
                                       jnp.int32),
                 "labels": jnp.asarray(rng.randint(0, 128, (8, 32)),
                                       jnp.int32)}
        full = jax.jit(make_train_step(lm, opt))
        accum = jax.jit(make_train_step(lm, opt, microbatches=4))
        p1, _, l1 = full(params, opt_state, batch)
        p2, _, l2 = accum(params, opt_state, batch)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-6)
        print("OK")
        """)
