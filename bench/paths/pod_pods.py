"""The `pod_pods` path: the `pod` path on several pods, one per chip, with
a reference that keeps pod q's momentum and EF residual on chip q.

The program under test is `pod.Cell`'s, unchanged. Only the reference
differs: `pod.Cell.reference` keeps every pod's state on the default
device, which for four pods of mamba2-780m (1.48 GB a copy of the
parameters) is 13.3 GB resident before a pod compresses, more than one
v5e holds. Here each pod's local steps and compression run on its own
chip, in parallel, and its kept entries are summed on chip 0 in pod order,
as `pod.Cell.reference` sums them.
"""
from __future__ import annotations

import functools
from pathlib import Path

import numpy as np

from bench.harness import load_module

pod = load_module(Path(__file__).with_name("pod.py"))
FAULTS = pod.FAULTS


class Cell(pod.Cell):
    def reference(self, mode: str = "float32") -> dict:
        """`pod.Cell.reference`, with pod q's state on `jax.devices()[q]`:
        the global weights w on chip 0, copied to each pod's chip at the
        start of a round; k momentum-SGD steps there; the EF accumulator,
        selection and residual there; w <- w - eta_g * (sum over pods of
        the kept, in pod order, on chip 0) / pods."""
        import jax
        import jax.numpy as jnp

        from bench.check import leaf_norms_fn
        from bench.selection import block_budget_mask

        cfg, mix, model = self.cfg, self.mix, self.model
        nb, blk, dim = self.n_blocks, self.blk, self.dim
        rate = float(mix["rate"])
        budget = max(1, min(blk, int(round(rate * blk))))
        lr, mom, eta_g = float(mix["lr"]), float(mix["momentum"]), \
            float(mix["eta_g"])
        devs = jax.devices()[:self.pods]
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
        w = jax.device_put(init(key), devs[0])
        mus = [None] * self.pods
        res = [jax.device_put(jnp.zeros((nb, blk), jnp.float32), d)
               for d in devs]
        losses = []
        for r in range(int(mix["check_rounds"])):
            round_loss, kept = [], []
            for q, d in enumerate(devs):
                wq = jax.device_put(w, d)
                p = unflat(wq)
                if mus[q] is None:
                    mus[q] = jax.device_put(jax.tree.map(jnp.zeros_like, p),
                                            d)
                for j in range(int(mix["k"])):
                    s = jax.device_put(self.stream[r, q, j], d)
                    p, mus[q], loss = local_step(p, mus[q], s[:, :-1],
                                                 s[:, 1:])
                    round_loss.append(loss)
                k_q, res[q] = compress(wq, p, res[q])
                del p, wq
                kept.append(k_q)
            total = kept.pop(0)
            while kept:
                total = total + jax.device_put(kept.pop(0), devs[0])
            w = apply(w, total)
            del total
            losses.append(float(np.mean([float(x) for x in round_loss])))
            if r == 0:
                update = np.asarray(norms(flat(mus[0])))
        del mus, res
        change = np.asarray(norms(w - jax.device_put(init(key), devs[0])))
        return {"loss": losses, "update": update, "change": change}
