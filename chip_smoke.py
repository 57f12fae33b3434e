"""Bring-up smoke test: the system's main paths on a TPU, checked for
correctness against the repo's own oracles.

    python chip_smoke.py               # one chip: phases fl, kernels, podround
    python chip_smoke.py --four-chips  # four chips: the pod-round step only

Phases (one chip):
  fl        the paper's asynchronous FL simulator through
            `repro.launch.train` (cnn_fmnist at its published widths,
            fedluck with error feedback, 10 devices, 6 rounds), then the
            sequential engine on the same fleet for comparison;
  kernels   the four Pallas kernels compiled for the chip at the flat dim
            of the fl model, each against its `kernels.ref` oracle;
  podround  `dist.steps.make_pod_round_step` on mamba2-780m at its
            published widths with n_layers cut to fit one chip, through
            the compact and the dense pod-sync, each against its oracle.

Each phase prints one JSON line; the last line is
{"ok": true, "device": {...}} only when every phase passed. Without a TPU
the script exits non-zero and prints no result. The compile cache goes
where `repro.launch.compile_cache` says.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "mamba2-780m"
# mamba2-780m cut from 48 layers, per chip count, to the deepest stack whose
# largest program here (f32 params, momentum, delta, residual and temps)
# leaves 2 GB of a v5e's 16 GiB free, by memory_analysis for a described
# v5e (see PERF.md). One chip: the compact step and the dense oracle's,
# 14.97 GB at 28 layers, 15.91 GB at 30. Four chips (one pod per chip):
# the compact step, 14.76 GB at 20 layers; the dense step 16.14 GB at 22.
PODROUND_LAYERS = {1: 28, 4: 20}
PODROUND = dict(k=2, batch=4, seq=128, blk=1024)
FL_ARGV = ["--task", "cnn_fmnist", "--method", "fedluck", "--error-feedback",
           "--devices", "10", "--rounds", "6", "--eval-every", "2",
           "--seed", "0", "--quiet"]


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class Clock:
    """Backend compile seconds (persistent-cache reads included) and
    persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def phase(self, fn, *args, **kw) -> tuple[dict, float, float]:
        c0, t0 = self.compile_s, time.perf_counter()
        out = fn(*args, **kw)
        wall = time.perf_counter() - t0
        compile_s = self.compile_s - c0
        return out, compile_s, wall - compile_s


# ------------------------------------------------------------------ checks
def _bitwise(a, b) -> bool:
    import numpy as np
    return bool(np.array_equal(np.asarray(a), np.asarray(b)))


def _close(a, b, rtol: float, atol: float) -> bool:
    import numpy as np
    return bool(np.all(np.abs(a - b) <= atol + rtol * np.abs(b)))


# ---------------------------------------------------------------- phase fl
def phase_fl() -> dict:
    import numpy as np

    from repro.launch import train

    args = train.parse_args(FL_ARGV)
    sim = train.make_fl_sim(args)
    res = train.run_fl(args, sim=sim)
    seq = train.make_fl_sim(args, engine="sequential")
    res_seq = train.run_fl(args, sim=seq)
    stack_platform = next(iter(sim._res_stack.devices())).platform
    out = {
        "task": args.task, "dim": sim.dim, "devices": args.devices,
        "rounds": res["rounds"], "final_accuracy": res["final_accuracy"],
        "final_loss": res["final_loss"], "events": res["events"],
        "residual_stack_platform": stack_platform,
        "sequential_rounds": res_seq["rounds"],
        "sequential_final_accuracy": res_seq["final_accuracy"],
        # reported, not gated: f32 matmuls at default precision may break
        # the CPU-bitwise equality of the two engines
        "max_abs_diff_vs_sequential": float(np.max(np.abs(
            np.asarray(sim.model.w) - np.asarray(seq.model.w)))),
    }
    out["ok"] = bool(res["rounds"] == args.rounds
                     and math.isfinite(res["final_accuracy"])
                     and math.isfinite(res["final_loss"])
                     and res["events"] > 0 and stack_platform == "tpu")
    return out


# ----------------------------------------------------------- phase kernels
def phase_kernels(d: int, rate: float = 0.05) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.dist.collectives import block_budget
    from repro.kernels import ops, ref
    from repro.kernels.compact_topk import compact_blocks
    from repro.kernels.ef_topk import ef_topk
    from repro.kernels.fused_momentum import fused_momentum
    from repro.kernels.magnitude_hist import magnitude_hist

    interpret = ops.resolve_interpret(None)
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(1), 4)
    g = jax.random.normal(k1, (d,)) * jnp.exp(jax.random.normal(k2, (d,)))
    r = 0.1 * jax.random.normal(k3, (d,))
    mu = jax.random.normal(k4, (d,))
    out = {"dim": d, "interpret": interpret}

    gmax = jnp.max(jnp.abs(g))
    edges = gmax * 2.0 ** (-jnp.arange(49, dtype=jnp.float32))
    out["magnitude_hist_bitwise"] = _bitwise(
        magnitude_hist(g, edges, interpret=interpret),
        ref.ref_magnitude_hist(g, edges))

    t = ops.solve_threshold(g + r, round(rate * d), interpret=interpret)
    got, want = ef_topk(g, r, t, interpret=interpret), ref.ref_ef_topk(g, r, t)
    out["ef_topk_out_bitwise"] = _bitwise(got[0], want[0])
    out["ef_topk_residual_bitwise"] = _bitwise(got[1], want[1])
    out["ef_topk_nnz"] = [float(got[2]), float(want[2])]

    blk = PODROUND["blk"]
    nb = -(-d // blk)
    budget = block_budget(blk, rate)
    acc = jnp.pad(g + r, (0, nb * blk - d)).reshape(nb, blk)
    tb = ops.solve_threshold(acc.reshape(-1), nb * budget,
                             interpret=interpret)
    got = compact_blocks(acc, tb, budget=budget, interpret=interpret)
    want = ref.ref_compact_blocks(acc, tb, budget)
    for name, a, b in zip(("values", "indices", "counts", "residual"),
                          got, want):
        out[f"compact_{name}_bitwise"] = _bitwise(a, b)
    out["compact_shipped"] = int(jnp.sum(got[2]))

    got = fused_momentum(g, mu, r, lr=0.05, momentum=0.9,
                         interpret=interpret)
    want = ref.ref_fused_momentum(g, mu, r, lr=0.05, momentum=0.9)
    got, want = [np.asarray(x) for x in got], [np.asarray(x) for x in want]
    out["momentum_max_abs_diff"] = float(max(
        np.max(np.abs(x - y)) for x, y in zip(got, want)))
    momentum_ok = all(_close(x, y, 2e-5, 1e-6) for x, y in zip(got, want))

    out["ok"] = bool(
        not interpret and momentum_ok
        and out["ef_topk_nnz"][0] == out["ef_topk_nnz"][1]
        and all(v for k, v in out.items() if k.endswith("_bitwise")))
    return out


# ---------------------------------------------------------- phase podround
def dense_oracle(mesh, dim: int, *, rate: float, n_blocks: int,
                 interpret=None):
    """The dense pod-sync on a pod-only mesh with its select pass from
    `kernels.ref`: per-pod histogram threshold over the whole vector,
    `ref_ef_topk`, psum over `pod` (eta_g = 1)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.kernels import ops, ref

    n_pods = mesh.shape["pod"]
    k = max(1, min(dim, round(rate * dim)))

    def shard_fn(p, d, r):
        g, res = d[0].reshape(dim), r[0].reshape(dim)
        t = ops.solve_threshold(g + res, k, interpret=interpret)
        out, res, _ = ref.ref_ef_topk(g, res, t)
        update = jax.lax.psum(out.reshape(p.shape), "pod") / n_pods
        return p - update, res.reshape(r.shape)

    return jax.shard_map(shard_fn, mesh=mesh,
                         in_specs=(P(None, None), P("pod", None, None),
                                   P("pod", None, None)),
                         out_specs=(P(None, None), P("pod", None, None)),
                         check_vma=False)


@dataclasses.dataclass
class PodRound:
    """mamba2-780m pod-round setup on a `pod` mesh: model, shapes and
    shardings, and the step/sync builders compared by phase podround."""
    mesh: object
    n_layers: int
    k: int
    batch: int
    seq: int
    blk: int
    interpret: object = None

    def __post_init__(self):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.configs import get_config
        from repro.kernels.compact_topk import ROWS
        from repro.models.transformer import LM
        from repro.optim import momentum_sgd

        self.cfg = dataclasses.replace(get_config(ARCH),
                                       n_layers=self.n_layers)
        self.lm = LM(self.cfg, dtype=jnp.float32, remat=True)
        self.opt = momentum_sgd(0.01, momentum=0.9)
        self.n_pods = self.mesh.shape["pod"]
        shapes = jax.eval_shape(self.lm.init, jax.random.PRNGKey(0))
        leaves, treedef = jax.tree_util.tree_flatten(shapes)
        self.spec = (treedef, [x.shape for x in leaves],
                     [x.dtype for x in leaves])
        self.dim = sum(math.prod(x.shape) for x in leaves)
        nb = -(-self.dim // self.blk)
        # a whole number of compact_blocks grid steps: no pad/slice copies
        self.n_blocks = -(-nb // ROWS) * ROWS
        rep = NamedSharding(self.mesh, P())
        pod = NamedSharding(self.mesh, P("pod"))
        opt_shapes = jax.eval_shape(self.opt.init, shapes)
        self.shardings = (rep, jax.tree.map(lambda _: pod, opt_shapes),
                          {"tokens": pod, "labels": pod}, pod)
        self.opt_shapes = opt_shapes

    def shapes(self):
        """ShapeDtypeStructs of the step's arguments, with shardings."""
        import jax
        import jax.numpy as jnp
        S = jax.ShapeDtypeStruct
        P_, nb, blk = self.n_pods, self.n_blocks, self.blk
        rep, opt_sh, batch_sh, pod = self.shardings
        opt = jax.tree.map(lambda x, s: S((P_,) + x.shape, x.dtype,
                                          sharding=s),
                           self.opt_shapes, opt_sh)
        tok = S((P_, self.k, self.batch, self.seq), jnp.int32, sharding=pod)
        return (S((nb, blk), jnp.float32, sharding=rep), opt,
                {"tokens": tok, "labels": tok},
                S((P_, nb, blk), jnp.float32, sharding=pod))

    def make_sync(self, rate: float, wire: str):
        from repro.dist.collectives import make_pod_sync
        dim = self.n_blocks * self.blk
        if wire == "dense_oracle":
            return dense_oracle(self.mesh, dim, rate=rate,
                                n_blocks=self.n_blocks,
                                interpret=self.interpret)
        return make_pod_sync(self.mesh, dim, rate=rate,
                             n_blocks=self.n_blocks, wire=wire,
                             interpret=self.interpret)

    def step(self, rate: float, wire: str):
        """The jitted pod-round step; params, opt states and residuals are
        donated (each compared chain owns its state)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.dist.steps import make_pod_round_step

        fn = make_pod_round_step(self.lm, self.opt, self.k,
                                 self.make_sync(rate, wire), spec=self.spec,
                                 dim=self.dim, n_blocks=self.n_blocks)
        rep, opt_sh, batch_sh, pod = self.shardings
        return jax.jit(fn, in_shardings=self.shardings,
                       out_shardings=(rep, opt_sh, pod,
                                      NamedSharding(self.mesh, P())),
                       donate_argnums=(0, 1, 3))

    def init_state(self, seed: int = 0):
        """Seeded (params, opt states, residuals) on the device, made by
        one program compiled once, so every call gives the same bits."""
        import jax
        import jax.numpy as jnp

        from repro.core import compression as C

        if not hasattr(self, "_init"):
            P_, nb, blk = self.n_pods, self.n_blocks, self.blk
            rep, opt_sh, _, pod = self.shardings

            def init(key):
                params = self.lm.init(key)
                flat, _ = C.flatten_pytree(params)
                pb = jnp.pad(flat, (0, nb * blk - self.dim)).reshape(nb, blk)
                opt = jax.tree.map(
                    lambda x: jnp.broadcast_to(x, (P_,) + x.shape),
                    self.opt.init(params))
                return pb, opt, jnp.zeros((P_, nb, blk), jnp.float32)

            self._init = jax.jit(init, out_shardings=(rep, opt_sh, pod))
        return self._init(jax.random.PRNGKey(seed))

    def batches(self, seed: int = 0):
        import jax
        import numpy as np

        from repro.data.synthetic import SyntheticTokens

        P_ = self.n_pods
        ds = SyntheticTokens(vocab=self.cfg.vocab, seq_len=self.seq + 1,
                             num_samples=P_ * self.k * self.batch, seed=seed)
        b = ds.batch(np.arange(len(ds)))
        shape = (P_, self.k, self.batch, self.seq)
        return {key: jax.device_put(v.reshape(shape), self.shardings[2][key])
                for key, v in b.items()}

    def random_deltas(self, seed: int):
        import jax
        import jax.numpy as jnp

        if not hasattr(self, "_deltas"):
            shape = (self.n_pods, self.n_blocks, self.blk)

            def make(key):
                k1, k2 = jax.random.split(key)
                return 1e-3 * jax.random.normal(k1, shape) \
                    * jnp.exp(jax.random.normal(k2, shape))

            self._deltas = jax.jit(make, out_shardings=self.shardings[3])
        return self._deltas(jax.random.PRNGKey(seed))

    def fingerprint(self, x):
        """Per pod, two wrapping uint32 sums of the bit patterns of
        x[pod]: plain, and weighted by an odd factor per position. Two
        arrays that differ in any one element always differ in the
        weighted sum. Lets residuals too large to hold twice on the chip be
        compared bit for bit one at a time."""
        import jax
        import jax.numpy as jnp

        if not hasattr(self, "_fingerprint"):
            def fp(x):
                u = jax.lax.bitcast_convert_type(x, jnp.uint32)
                u = u.reshape(x.shape[0], -1)
                w = (2 * jnp.arange(u.shape[1], dtype=jnp.uint32) + 1) \
                    * jnp.uint32(0x9E3779B1)
                return jnp.stack([jnp.sum(u, axis=1, dtype=jnp.uint32),
                                  jnp.sum(u * w, axis=1, dtype=jnp.uint32)],
                                 axis=1)

            self._fingerprint = jax.jit(fp)
        return self._fingerprint(x)


def compare_syncs(pr: PodRound, rate: float, wire: str, oracle: str,
                  rounds: int) -> dict:
    """The sync alone, `wire` then `oracle`, each for `rounds` EF rounds
    on the same seeded deltas. Selection reads only deltas and residuals,
    so residuals must stay bitwise equal; params may differ by the order
    of the cross-pod sum (an all-gather and scatter-add against a psum)."""
    import jax
    import numpy as np

    got = {}
    for side in (wire, oracle):
        jsync = jax.jit(pr.make_sync(rate, side), donate_argnums=(0, 2))
        pb, opt, res = pr.init_state()
        del opt
        for rnd in range(rounds):
            pb, res = jsync(pb, pr.random_deltas(100 + rnd), res)
        got[side] = np.asarray(pb), np.asarray(res)
        del pb, res
    (pa, ra), (pb, rb) = got[wire], got[oracle]
    return {"sync_residuals_bitwise": bool(np.array_equal(ra, rb)),
            "sync_residual_max_abs": float(np.max(np.abs(ra))),
            "sync_params_bitwise": bool(np.array_equal(pa, pb)),
            "sync_params_max_abs_diff": float(np.max(np.abs(pa - pb))),
            "sync_params_close": _close(pa, pb, 1e-5, 1e-6)}


def compare_steps(pr: PodRound, step_a, step_b, rounds: int) -> dict:
    """The whole pod-round step in lockstep: in round r both compiled
    steps start from the same state, the oracle's after r - 1 rounds (EF
    residuals carried). A free-running pair would drift: on several pods
    the two cross-pod sums differ in their last bits, the next round's
    local steps amplify that, and entries near the threshold flip. The
    device holds one state at a time, so each round's start is recomputed
    from the seed; residuals are compared by `PodRound.fingerprint`,
    params on the host."""
    import numpy as np

    batches = pr.batches()
    out = {"losses": [[], []], "residuals_bitwise": [], "params_bitwise": [],
           "params_max_abs_diff": [], "params_close": []}
    for rnd in range(rounds):
        got = []
        for side, step in enumerate((step_a, step_b)):
            pb, opt, res = pr.init_state()
            for _ in range(rnd):
                pb, opt, res, _ = step_b(pb, opt, batches, res)
            pb, opt, res, loss = step(pb, opt, batches, res)
            if side == 0 and rnd == rounds - 1:
                out["bytes_in_use"] = [
                    (dev.memory_stats() or {}).get("bytes_in_use")
                    for dev in pr.mesh.devices.flat]
            got.append((np.asarray(pb), np.asarray(pr.fingerprint(res))))
            out["losses"][side].append(float(loss))
            del pb, opt, res
        (pa, fa), (pb, fb) = got
        out["residuals_bitwise"].append(bool(np.array_equal(fa, fb)))
        out["params_bitwise"].append(bool(np.array_equal(pa, pb)))
        out["params_max_abs_diff"].append(float(np.max(np.abs(pa - pb))))
        out["params_close"].append(_close(pa, pb, 1e-5, 1e-6))
    return out


def compare_pod_round(pr: PodRound, rate: float, wire: str, oracle: str,
                      rounds: int = 3) -> dict:
    """`wire` vs `oracle` over `rounds` EF rounds, the sync alone and the
    whole step: residuals bitwise, params within the compact-vs-reference
    tolerances of tests/test_dist.py (rtol 1e-5, atol 1e-6)."""
    import numpy as np

    from repro.kernels import ops

    out = {"rate": rate, "oracle": oracle,
           **compare_syncs(pr, rate, wire, oracle, rounds)}
    step_a, step_b = (pr.step(rate, w).lower(*pr.shapes()).compile()
                      for w in (wire, oracle))
    steps = compare_steps(pr, step_a, step_b, rounds)
    ma = step_a.memory_analysis()
    out.update(
        path=pr.make_sync(rate, wire).path,
        tpu_custom_call="tpu_custom_call" in step_a.as_text(),
        step_memory_bytes={"arguments": ma.argument_size_in_bytes,
                           "outputs": ma.output_size_in_bytes,
                           "aliased": ma.alias_size_in_bytes,
                           "temps": ma.temp_size_in_bytes},
        device_bytes_in_use=steps.pop("bytes_in_use"),
        **{f"step_{k}": v for k, v in steps.items()})
    out["ok"] = bool(out["sync_residuals_bitwise"] and out["sync_params_close"]
                     and all(out["step_residuals_bitwise"])
                     and all(out["step_params_close"])
                     and np.all(np.isfinite(out["step_losses"]))
                     and out["tpu_custom_call"]
                     and not ops.resolve_interpret(pr.interpret))
    return out


def make_pod_mesh(n: int):
    import jax
    return jax.make_mesh((n,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=jax.devices()[:n])


def setup_podround(n_chips: int) -> PodRound:
    n_layers = PODROUND_LAYERS[n_chips]
    pr = PodRound(make_pod_mesh(n_chips), n_layers, **PODROUND)
    emit({"phase": "podround_setup", "arch": ARCH, "chips": n_chips,
          "n_layers": n_layers, "published_n_layers": 48,
          "d_model": pr.cfg.d_model, "ssm_state": pr.cfg.ssm_state,
          "vocab": pr.cfg.vocab, "params": pr.dim, "n_blocks": pr.n_blocks,
          "blk": pr.blk, **PODROUND})
    return pr


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod-round step across a pod=4 mesh")
    args = ap.parse_args(argv)

    import jax

    from repro.launch import compile_cache
    from repro.launch.mesh import device_info

    device = device_info()
    n_chips = 4 if args.four_chips else 1
    if device["platform"] != "tpu" or device["count"] < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chip(s); JAX found "
              f"{device['count']} {device['platform']} device(s)",
              file=sys.stderr)
        return 2
    cache_dir = compile_cache.enable()
    clock = Clock()
    emit({"phase": "setup", "cache_dir": cache_dir, "jax": jax.__version__,
          "device": device})

    failed = []

    def record(name, fn, *a):
        res, compile_s, run_s = clock.phase(fn, *a)
        emit({"phase": name, **res, "compile_s": compile_s, "run_s": run_s})
        if not res["ok"]:
            failed.append(name)
        return res

    def podround(n_chips, cases):
        pr = setup_podround(n_chips)
        for case in cases:
            record("podround", compare_pod_round, pr, *case)

    if args.four_chips:
        podround(4, [(0.05, "auto", "reference"),
                     (0.5, "auto", "dense_oracle")])
    else:
        fl = record("fl", phase_fl)
        record("kernels", phase_kernels, fl["dim"])
        podround(1, [(0.05, "compact", "reference"),
                     (0.05, "dense", "dense_oracle")])
    emit({"phase": "summary", "cache_dir": cache_dir,
          "compile_s": clock.compile_s, "cache_hits": clock.cache_hits,
          "failed": failed})
    if failed:
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
