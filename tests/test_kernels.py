"""Per-kernel shape/dtype sweeps, assert_allclose against the ref.py oracles.
Kernels run in the Pallas interpreter here; tests/test_tpu_compile.py
compiles the same code for a TPU v5e and chip_smoke.py runs it on one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ef_topk import ef_topk
from repro.kernels.fused_momentum import fused_momentum
from repro.kernels.magnitude_hist import magnitude_hist

SHAPES = [127, 1024, 8192, 40_000]
DTYPES = [jnp.float32, jnp.bfloat16]


def _g(d, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(d).astype(np.float32)
                       * np.exp(rng.randn(d))).astype(dtype)


class TestMagnitudeHist:
    @pytest.mark.parametrize("d", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_oracle(self, d, dtype):
        g = _g(d, d, dtype)
        gmax = jnp.max(jnp.abs(g.astype(jnp.float32))) + 1e-30
        edges = gmax * 2.0 ** (-jnp.arange(33, dtype=jnp.float32))
        got = magnitude_hist(g, edges, block=2048, interpret=True)
        want = ref.ref_magnitude_hist(g, edges)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_padding_does_not_count(self):
        g = _g(100, 1)   # padded to one 2048 block internally
        edges = jnp.asarray([1e-20], jnp.float32)  # everything >= this
        got = magnitude_hist(g, edges, block=2048, interpret=True)
        assert float(got[0]) == 100.0  # zeros from padding excluded


class TestEfTopk:
    @pytest.mark.parametrize("d", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_oracle(self, d, dtype):
        g, r = _g(d, d, dtype), _g(d, d + 1, dtype) * 0.1
        t = jnp.float32(0.5)
        out_k, res_k, nnz_k = ef_topk(g, r, t, block=2048, interpret=True)
        out_r, res_r, nnz_r = ref.ref_ef_topk(g, r, t)
        np.testing.assert_allclose(np.asarray(out_k, np.float32),
                                   np.asarray(out_r, np.float32),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(res_k, np.float32),
                                   np.asarray(res_r, np.float32),
                                   rtol=1e-5, atol=1e-6)
        assert float(nnz_k) == float(nnz_r)

    def test_conservation(self):
        """out + residual' == g + residual exactly (fp32)."""
        g, r = _g(5000, 2), _g(5000, 3) * 0.2
        out, res, _ = ef_topk(g, r, jnp.float32(1.0), interpret=True)
        np.testing.assert_allclose(np.asarray(out + res),
                                   np.asarray(g + r), rtol=1e-6)


class TestTopkCompressPipeline:
    @pytest.mark.parametrize("rate", [0.001, 0.01, 0.1])
    @pytest.mark.parametrize("d", [10_000, 100_000])
    def test_density_and_selection(self, rate, d):
        g = _g(d, d)
        res = jnp.zeros(d)
        out, new_res, nnz, t = ops.topk_compress(g, res, rate=rate,
                                                 interpret=True)
        k = max(1, round(rate * d))
        assert float(nnz) <= k + 1
        assert float(nnz) >= 0.9 * k - 1
        # EF decomposition holds for the full pipeline too
        np.testing.assert_allclose(np.asarray(out + new_res),
                                   np.asarray(g), rtol=1e-5, atol=1e-6)
        # every kept value beats every dropped value in magnitude (threshold)
        o = np.asarray(out)
        kept = np.abs(o[o != 0])
        dropped = np.abs(np.asarray(g))[o == 0]
        if len(kept) and len(dropped):
            assert kept.min() >= dropped.max() - 1e-5 or \
                kept.min() >= float(t) - 1e-7

    @pytest.mark.parametrize("nnz", [0, 7, 64])
    def test_compact_topk_round_trip(self, nnz):
        """scatter(values, indices) reconstructs the dense masked vector
        exactly whenever the capacity covers the support."""
        d, cap = 5000, 64
        rng = np.random.RandomState(nnz)
        dense = np.zeros(d, np.float32)
        support = rng.choice(d, size=nnz, replace=False)
        dense[support] = rng.randn(nnz).astype(np.float32)
        vals, idx = ops.compact_topk(jnp.asarray(dense), cap)
        assert vals.shape == idx.shape == (cap,)
        rebuilt = np.zeros(d, np.float32)
        np.add.at(rebuilt, np.asarray(idx), np.asarray(vals))
        np.testing.assert_array_equal(rebuilt, dense)

    def test_compact_topk_sparse_pipeline_round_trip(self):
        """topk_compress_sparse wire pair rebuilds the dense pipeline
        output bit-for-bit at the tested rate."""
        d = 40_000
        g, res = _g(d, 21), _g(d, 22) * 0.1
        dense, _, _, _ = ops.topk_compress(g, res, rate=0.01, interpret=True)
        vals, idx, _, nnz, _ = ops.topk_compress_sparse(g, res, rate=0.01,
                                                        interpret=True)
        assert float(nnz) <= vals.shape[0]
        rebuilt = np.zeros(d, np.float32)
        np.add.at(rebuilt, np.asarray(idx), np.asarray(vals))
        np.testing.assert_array_equal(rebuilt, np.asarray(dense))

    def test_statistics_use_ef_accumulator(self):
        """Threshold must be computed on g+residual, not g alone."""
        d = 10_000
        g = jnp.zeros(d)
        res = _g(d, 11)  # all signal lives in the residual
        out, _, nnz, _ = ops.topk_compress(g, res, rate=0.01, interpret=True)
        assert float(nnz) > 0


class TestCompactBlocks:
    """compact_topk.compact_blocks — the pod-sync wire-format kernel."""

    def _acc(self, nb, blk, seed=0):
        rng = np.random.RandomState(seed)
        return jnp.asarray(rng.randn(nb, blk).astype(np.float32)
                           * np.exp(rng.randn(nb, blk).astype(np.float32)))

    @pytest.mark.parametrize("nb,blk", [(1, 128), (8, 64), (12, 256)])
    @pytest.mark.parametrize("budget", [1, 5, 32])
    def test_vs_oracle_bitwise(self, nb, blk, budget):
        from repro.kernels.compact_topk import compact_blocks
        acc = self._acc(nb, blk, nb * blk + budget)
        t = jnp.float32(np.median(np.abs(np.asarray(acc))) * 2)
        got = compact_blocks(acc, t, budget=budget, interpret=True)
        want = ref.ref_compact_blocks(acc, t, budget)
        for g_, w_, name in zip(got, want, ("vals", "idx", "cnt", "res")):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_),
                                          err_msg=name)

    @pytest.mark.parametrize("threshold,expect", [(0.0, "all"),
                                                  (np.inf, "none")])
    def test_degenerate_thresholds(self, threshold, expect):
        from repro.kernels.compact_topk import compact_blocks
        nb, blk, budget = 4, 64, 8
        acc = self._acc(nb, blk, 3)
        vals, idx, cnt, res = compact_blocks(acc, jnp.float32(threshold),
                                             budget=budget, interpret=True)
        if expect == "none":   # t=inf: nothing ships, residual == acc
            assert (np.asarray(cnt) == 0).all()
            np.testing.assert_array_equal(np.asarray(res), np.asarray(acc))
            assert not np.asarray(vals).any() and not np.asarray(idx).any()
        else:                  # t=0: every block overflows to exactly budget
            assert (np.asarray(cnt) == budget).all()
            # kept entries are the FIRST `budget` coords of each block
            # (front-packed in index order), rest defer via residual
            np.testing.assert_array_equal(
                np.asarray(vals), np.asarray(acc)[:, :budget])

    def test_scatter_reconstructs_shipped_selection(self):
        """zeros.at[idx].add(vals) == acc − residual (padding slots are
        (0.0, 0) no-ops) — the property the compact pod-sync relies on."""
        from repro.kernels.compact_topk import compact_blocks
        nb, blk, budget = 8, 128, 6
        acc = self._acc(nb, blk, 17)
        t = jnp.float32(np.quantile(np.abs(np.asarray(acc)), 0.95))
        vals, idx, cnt, res = compact_blocks(acc, t, budget=budget,
                                             interpret=True)
        rebuilt = np.zeros(nb * blk, np.float32)
        np.add.at(rebuilt, np.asarray(idx).ravel(), np.asarray(vals).ravel())
        np.testing.assert_array_equal(rebuilt.reshape(nb, blk),
                                      np.asarray(acc - res))
        # indices are shard-flat (block i owns [i·blk, (i+1)·blk))
        live = np.arange(budget)[None, :] < np.asarray(cnt)[:, None]
        blocks = np.asarray(idx) // blk
        assert (blocks[live] == np.nonzero(live)[0]).all()

    def test_shard_pipeline_matches_threshold_solve(self):
        """compact_shard_topk == solve_threshold + compact_blocks, and the
        shard threshold equals topk_compress's on the same flat vector."""
        nb, blk, rate = 8, 256, 0.0625   # rate·blk integral, so the shard
        budget = max(1, min(blk, round(rate * blk)))   # target nb·budget
        assert nb * budget == round(rate * nb * blk)   # == pipeline k
        acc = self._acc(nb, blk, 29)
        vals, idx, cnt, res = ops.compact_shard_topk(acc, budget=budget,
                                                     interpret=True)
        t = ops.solve_threshold(acc.reshape(-1), nb * budget, interpret=True)
        want = ref.ref_compact_blocks(acc, t, budget)
        for g_, w_, name in zip((vals, idx, cnt, res), want,
                                ("vals", "idx", "cnt", "res")):
            np.testing.assert_array_equal(np.asarray(g_), np.asarray(w_),
                                          err_msg=name)
        # solve_threshold is the extracted topk_compress solver: same t
        _, _, _, t_pipe = ops.topk_compress(
            acc.reshape(-1), jnp.zeros(nb * blk), rate=rate, interpret=True)
        assert float(t) == float(t_pipe)


class TestFusedMomentum:
    @pytest.mark.parametrize("d", SHAPES)
    @pytest.mark.parametrize("dtype", DTYPES)
    def test_vs_oracle(self, d, dtype):
        w, mu, g = _g(d, 5, dtype), _g(d, 6), _g(d, 7, dtype)
        w2, mu2 = fused_momentum(w, mu, g, lr=0.1, momentum=0.9,
                                 block=2048, interpret=True)
        rw, rmu = ref.ref_fused_momentum(w, mu, g, lr=0.1, momentum=0.9)
        np.testing.assert_allclose(np.asarray(w2, np.float32),
                                   np.asarray(rw, np.float32),
                                   rtol=2e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(mu2, np.float32),
                                   np.asarray(rmu, np.float32),
                                   rtol=2e-5, atol=1e-6)

    def test_matches_optimizer_semantics(self):
        """Kernel == repro.optim.momentum_sgd on a flat vector."""
        from repro.optim import momentum_sgd
        d = 2000
        w, g = _g(d, 8), _g(d, 9)
        opt = momentum_sgd(0.05, momentum=0.9)
        st = opt.init(w)
        w_ref, _ = opt.update(g, st, w)
        w_k, _ = fused_momentum(w, jnp.zeros(d), g, lr=0.05, momentum=0.9,
                                interpret=True)
        np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_ref),
                                   rtol=2e-5, atol=1e-6)
