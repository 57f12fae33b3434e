"""The `pod` path: the DiLoCo-style pod-round step
(`dist.steps.make_pod_round_step` with `dist.collectives.make_pod_sync`)
on a `pod` mesh of one pod per chip, and its plain reference.

Set-up builds the compiled step and its state (weights from the seed, on
the device, in one jitted call), then drives it through the first
`check_rounds` rounds of the token stream: those are the checked steps.
The window continues the same state on the following rounds.

`FAULTS` plants a fault in the program under test, to show that the
correctness check catches it (`bench/readings.py` on the chip, the CPU
tests); no benchmark run uses it:
  state_unchanged   the step returns its state as it got it;
  half_batch        every local step sees the first half of its batch and
                    takes the mean over that half.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import time

import numpy as np

from bench import traffic as T
from bench.check import leaf_layout, leaf_norms_fn

ROWS = 8   # compact_blocks packs 8 blocks per grid step


class Cell:
    def __init__(self, cfg: dict, model, mix: dict, chips: int, seed: int,
                 trace: bool = False):
        self.cfg, self.model, self.mix, self.seed = cfg, model, mix, seed
        self.pods = chips
        # [0] unused, [1] token stream (traffic.token_stream), [2] weights
        self.seeds = T.sub_seeds(seed, 3)

    # ------------------------------------------------------------ layout
    def build(self) -> None:
        """Model, optimizer, mesh, shardings and the compiled step (the
        shape and sharding logic of the repository's chip smoke test)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.dist.collectives import make_pod_sync
        from repro.dist.steps import make_pod_round_step
        from repro.models.transformer import LM
        from repro.optim import momentum_sgd

        cfg, mix = self.cfg, self.mix
        self.lm = LM(self.model.arch(cfg), dtype=jnp.float32,
                     remat=bool(cfg["remat"]))
        self.opt = momentum_sgd(float(mix["lr"]), momentum=float(mix["momentum"]))
        self.mesh = jax.make_mesh((self.pods,), ("pod",),
                                  axis_types=(jax.sharding.AxisType.Auto,),
                                  devices=jax.devices()[:self.pods])
        shapes = jax.eval_shape(lambda k: self.model.init(cfg, k),
                                jax.random.PRNGKey(0))
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        _, self.spans = leaf_layout(shapes)
        self.dim = self.spans[-1][1]
        blk = int(mix["blk"])
        self.blk = blk
        blocks = -(-self.dim // blk)
        self.n_blocks = -(-blocks // ROWS) * ROWS   # whole grid steps
        spec = (treedef, [x.shape for x in leaves], [x.dtype for x in leaves])
        self.sync = make_pod_sync(self.mesh, self.n_blocks * blk,
                                  rate=float(mix["rate"]),
                                  eta_g=float(mix["eta_g"]),
                                  n_blocks=self.n_blocks, wire=mix["wire"])
        fn = make_pod_round_step(self.lm, self.opt, int(mix["k"]), self.sync,
                                 spec=spec, dim=self.dim,
                                 n_blocks=self.n_blocks)
        rep = NamedSharding(self.mesh, P())
        pod = NamedSharding(self.mesh, P("pod"))
        self.opt_shapes = jax.eval_shape(self.opt.init, shapes)
        opt_sh = jax.tree.map(lambda _: pod, self.opt_shapes)
        self.shardings = (rep, opt_sh, {"tokens": pod, "labels": pod}, pod)
        S = jax.ShapeDtypeStruct
        Pn, nb = self.pods, self.n_blocks
        tok = S((Pn, int(mix["k"]), int(mix["batch"]), int(mix["seq"])),
                jnp.int32, sharding=pod)
        args = (S((nb, blk), jnp.float32, sharding=rep),
                jax.tree.map(lambda x, s: S((Pn,) + x.shape, x.dtype,
                                            sharding=s),
                             self.opt_shapes, opt_sh),
                {"tokens": tok, "labels": tok},
                S((Pn, nb, blk), jnp.float32, sharding=pod))
        self.step = jax.jit(
            fn, in_shardings=self.shardings,
            out_shardings=(rep, opt_sh, pod, rep),
            donate_argnums=(0, 1, 3)).lower(*args).compile()
        # the allocator's peak leaves out the program's temporaries
        self.program_peak_bytes = getattr(self.step.memory_analysis(),
                                          "peak_memory_in_bytes", None)

        def init(key):
            params = self.model.init(cfg, key)
            flat = jnp.concatenate([x.reshape(-1) for x in
                                    jax.tree_util.tree_leaves(params)])
            pb = jnp.pad(flat, (0, nb * blk - self.dim)).reshape(nb, blk)
            opt = jax.tree.map(lambda x: jnp.broadcast_to(x, (Pn,) + x.shape),
                               self.opt.init(params))
            return pb, opt, jnp.zeros((Pn, nb, blk), jnp.float32)

        self.init = jax.jit(init, out_shardings=(rep, opt_sh, pod))
        norms = leaf_norms_fn(self.spans)
        self.mu_norms = jax.jit(lambda opt: norms(jnp.concatenate(
            [x[0].reshape(-1) for x in jax.tree_util.tree_leaves(opt["mu"])])))

        def change(pb, key):
            p0 = self.model.init(cfg, key)
            flat0 = jnp.concatenate([x.reshape(-1) for x in
                                     jax.tree_util.tree_leaves(p0)])
            return norms(pb.reshape(-1)[:self.dim] - flat0)

        self.change_norms = jax.jit(change)
        self.flops_per_round = (self.model.train_flops(cfg, int(mix["seq"]))
                                * Pn * int(mix["k"]) * int(mix["batch"])
                                * int(mix["seq"]))

    # ------------------------------------------------------------ set-up
    def setup(self) -> None:
        import jax

        self.build()
        mix = self.mix
        stream = T.token_stream(mix, self.seed, self.cfg["vocab_size"],
                                self.pods)
        self.stream = stream
        pod = self.shardings[3]
        self.batches = [{"tokens": jax.device_put(s[..., :-1], pod),
                         "labels": jax.device_put(s[..., 1:], pod)}
                        for s in stream]
        key = jax.random.PRNGKey(self.seeds[2])
        self.state = self.init(key)
        self.rounds = 0
        losses = []
        for r in range(int(mix["check_rounds"])):
            loss = self.round()
            losses.append(float(loss))
            if r == 0:
                update = np.asarray(self.mu_norms(self.state[1]))
        change = np.asarray(self.change_norms(self.state[0], key))
        self.readings = {"loss": losses, "update": update, "change": change}

    def hlo_text(self) -> str:
        """The compiled step's HLO, whose op metadata names the scopes."""
        return self.step.as_text()

    def round(self):
        pb, opt, res = self.state
        batch = self.batches[self.rounds % len(self.batches)]
        pb, opt, res, loss = self.step(pb, opt, batch, res)
        self.state = (pb, opt, res)
        self.rounds += 1
        return loss

    # ------------------------------------------------------------ window
    def window(self, seconds: float, annotate=None) -> dict:
        """Rounds, each ended by block_until_ready, until `seconds` pass."""
        annotate = annotate or (lambda name: contextlib.nullcontext())
        t0 = last = time.perf_counter()
        durations = []
        while True:
            with annotate(f"bench.round{len(durations)}"):
                self.round().block_until_ready()
            now = time.perf_counter()
            durations.append(now - last)
            last = now
            if now - t0 >= seconds:
                break
        n, median = len(durations), float(np.median(durations))
        return {"elapsed_s": now - t0, "rounds": n,
                "median_round_s": median, "longest_round_s": max(durations),
                "slow_rounds": sum(d > 1.5 * median for d in durations),
                "flops": n * self.flops_per_round}

    def free(self) -> None:
        del self.state, self.batches, self.step
        gc.collect()

    # --------------------------------------------------------- reference
    def reference(self, mode: str = "float32") -> dict:
        """The first `check_rounds` rounds recomputed from the seed with the
        plain model, pod by pod: k momentum-SGD steps from the global
        weights, delta = w0 - wk; EF accumulator acc = delta + residual,
        every |acc| at or above the (n_blocks * budget)-th largest kept, at
        most `budget` per block in index order; w <- w - eta_g * mean over
        pods of the kept; residual <- acc - kept."""
        import jax
        import jax.numpy as jnp

        from bench.selection import block_budget_mask

        cfg, mix, model = self.cfg, self.mix, self.model
        nb, blk, dim = self.n_blocks, self.blk, self.dim
        rate = float(mix["rate"])
        budget = max(1, min(blk, int(round(rate * blk))))
        lr, mom, eta_g = float(mix["lr"]), float(mix["momentum"]), \
            float(mix["eta_g"])
        norms = jax.jit(leaf_norms_fn(self.spans))
        shapes = jax.eval_shape(lambda k: model.init(cfg, k),
                                jax.random.PRNGKey(0))
        treedef = jax.tree_util.tree_structure(shapes)
        leaves = jax.tree_util.tree_leaves(shapes)

        def flat(tree):
            return jnp.concatenate([x.reshape(-1) for x in
                                    jax.tree_util.tree_leaves(tree)])

        def unflat(w):
            return jax.tree_util.tree_unflatten(treedef, [
                w[a:b].reshape(x.shape) for (a, b), x in zip(self.spans,
                                                             leaves)])

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def local_step(p, mu, tokens, labels):
            loss, g = jax.value_and_grad(model.reference_loss, argnums=1)(
                cfg, p, {"tokens": tokens, "labels": labels}, mode)
            mu = jax.tree.map(lambda m, x: mom * m + x, mu, g)
            return jax.tree.map(lambda x, m: x - lr * m, p, mu), mu, loss

        @functools.partial(jax.jit, donate_argnums=(2,))
        def compress(w, pk, res):
            acc = jnp.pad(w - flat(pk), (0, nb * blk - dim)).reshape(nb, blk) \
                + res
            kept = jnp.where(block_budget_mask(acc, nb * budget, budget),
                             acc, 0.0)
            return kept, acc - kept

        @jax.jit
        def apply(w, total):
            return w - eta_g * (total / self.pods).reshape(-1)[:dim]

        init = jax.jit(lambda k: flat(model.init(cfg, k)))
        key = jax.random.PRNGKey(self.seeds[2])
        w = init(key)
        mus = [None] * self.pods
        res = [jnp.zeros((nb, blk), jnp.float32) for _ in range(self.pods)]
        losses = []
        for r in range(int(mix["check_rounds"])):
            total, round_loss = 0.0, []
            for q in range(self.pods):
                p = unflat(w)
                if mus[q] is None:
                    mus[q] = jax.tree.map(jnp.zeros_like, p)
                for j in range(int(mix["k"])):
                    s = self.stream[r, q, j]
                    p, mus[q], loss = local_step(p, mus[q], s[:, :-1],
                                                 s[:, 1:])
                    round_loss.append(float(loss))
                kept, res[q] = compress(w, p, res[q])
                del p
                total = total + kept
                del kept
            w = apply(w, total)
            del total
            losses.append(float(np.mean(round_loss)))
            if r == 0:
                update = np.asarray(norms(flat(mus[0])))
        del mus, res
        change = np.asarray(norms(w - init(key)))
        return {"loss": losses, "update": update, "change": change}


# ------------------------------------------------------------- faults
@contextlib.contextmanager
def _patched(obj, name, value):
    old = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, old)


def state_unchanged():
    from repro.dist import steps
    make = steps.make_pod_round_step

    def make_unchanged(*a, **kw):
        step = make(*a, **kw)

        def unchanged(params, opt_states, batches, residuals):
            loss = step(params, opt_states, batches, residuals)[3]
            return params, opt_states, residuals, loss
        return unchanged
    return _patched(steps, "make_pod_round_step", make_unchanged)


def half_batch():
    import jax

    from repro.models.transformer import LM
    loss = LM.loss

    def halved(self, params, batch):
        return loss(self, params, jax.tree.map(
            lambda x: x[: x.shape[0] // 2], batch))
    return _patched(LM, "loss", halved)


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch}
