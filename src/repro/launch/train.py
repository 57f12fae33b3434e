"""Training driver.

Two modes:

1. `--mode fl` (default — the paper's setting): asynchronous federated
   training of one of the paper's tasks under any of the 5 methods, on the
   event-driven simulator with real JAX compute, with checkpoint/restart
   (global model + residuals + controller plans survive a crash) and
   optional failure injection.

2. `--mode datacenter`: DiLoCo-style multi-"pod" local SGD on an assigned
   architecture's smoke config: each pod runs k local steps (Alg. 1 device
   loop, jitted lax.scan), compresses its pseudo-gradient with EF top-k at
   the controller-chosen δ, and syncs through the sparse aggregation
   collective (Eq. 6). On this CPU container pods are simulated as mesh
   rows of a local mesh; on real hardware the same code runs one process
   per pod.

Examples:
  PYTHONPATH=src python -m repro.launch.train --task cnn_fmnist \
      --method fedluck --rounds 60 --ckpt-dir /tmp/ck --resume
  PYTHONPATH=src python -m repro.launch.train --mode datacenter \
      --arch mamba2-780m --steps 40 --local-k 5 --rate 0.01
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.launch import compile_cache
from repro.obs import log


def make_obs(args):
    """(tracer, metrics) from --trace-out/--metrics-out, else (None, None)."""
    tracer = metrics = None
    if getattr(args, "trace_out", ""):
        from repro.obs import Tracer
        tracer = Tracer()
    if getattr(args, "metrics_out", ""):
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()
    return tracer, metrics


def export_obs(args, tracer, metrics, extra=None) -> None:
    """Write the trace/metrics artifacts named by the CLI flags."""
    if tracer is not None:
        from repro.obs import PerfettoExporter
        PerfettoExporter().export(tracer, args.trace_out)
        log.status(f"[obs] wrote trace: {args.trace_out} "
                   f"({len(tracer)} events)")
    if metrics is not None:
        metrics.to_json(args.metrics_out, extra=extra)
        log.status(f"[obs] wrote metrics: {args.metrics_out}")


# --------------------------------------------------------------------- FL mode
def fl_ckpt_state(sim) -> dict:
    """FL checkpoint payload: global model + round + per-device EF
    residuals (without the residuals, a resumed error-feedback run silently
    re-drops every deferred coordinate and diverges from the uninterrupted
    run). Residuals come via `residual_snapshot`, which works for both the
    batched (device-resident stack) and sequential (host dict) engines."""
    state = {"w": np.asarray(sim.model.w),
             "round": np.asarray(sim.model.round)}
    ids, stacked = sim.residual_snapshot()
    if len(ids):
        state["residual_ids"] = ids
        state["residuals"] = stacked
    return state


def restore_fl_state(sim, state) -> None:
    sim.model.w = np.asarray(state["w"])
    sim.model.round = int(state["round"])
    if "residuals" in state:
        sim.load_residuals(np.asarray(state["residual_ids"]),
                           np.asarray(state["residuals"]))


def make_fl_sim(args, engine: str = "batched"):
    """Build the AFL simulator (task, fleet plan, fault models, obs) that
    `run_fl` drives; `engine="sequential"` gives the tests' oracle engine
    on the same fleet."""
    import jax

    from repro.core import compression as C
    from repro.core.simulator import (AFLSimulator, STRATEGY_FOR_METHOD,
                                      make_heterogeneous_devices, plan_devices)
    from repro.data.partition import dirichlet_partition, iid_partition
    from repro.core.aggregation import SanitizerConfig
    from repro.ft import FailureSchedule, LossyChannel
    from repro.models.small import make_task

    task = make_task(args.task, num_samples=args.samples,
                     test_samples=args.test_samples,
                     batch_size=args.batch_size, noise=args.noise)
    params = task.init_fn(jax.random.PRNGKey(args.seed))
    flat, _ = C.flatten_pytree(params)
    model_bits = int(flat.size) * 32

    profiles = make_heterogeneous_devices(
        args.devices, model_bits, base_alpha=args.base_alpha, seed=args.seed)
    specs = plan_devices(profiles, args.method, args.round_period,
                         k_bounds=(1, args.k_max), fixed_k=args.fixed_k,
                         fixed_delta=args.fixed_delta,
                         error_feedback=args.error_feedback)
    if args.noniid:
        idx = dirichlet_partition(task.dataset.labels, args.devices,
                                  alpha=1.0, seed=args.seed)
    else:
        idx = iid_partition(len(task.dataset), args.devices, seed=args.seed)

    # --failure-rate N sets the per-device crash rate; the legacy
    # --inject-failures switch keeps its historical default of 0.2
    failure = None
    if args.failure_rate > 0 or args.inject_failures:
        failure = FailureSchedule.random(
            args.devices, args.rounds * args.round_period,
            rate_per_device=args.failure_rate or 0.2, seed=args.seed)
    channel = (LossyChannel(loss_prob=args.loss_rate, seed=args.seed)
               if args.loss_rate > 0 else None)
    sanitizer = None
    if args.tau_max is not None or args.clip_norm is not None:
        sanitizer = SanitizerConfig(tau_max=args.tau_max,
                                    clip_norm=args.clip_norm)

    tracer, metrics = make_obs(args)
    return AFLSimulator(task, specs, STRATEGY_FOR_METHOD[args.method],
                        round_period=args.round_period, eta_l=args.eta_l,
                        eta_g=args.eta_g, seed=args.seed, client_indices=idx,
                        failure_schedule=failure, channel=channel,
                        sanitizer=sanitizer, tracer=tracer, metrics=metrics,
                        engine=engine)


def run_fl(args, sim=None) -> dict:
    """Train `args.rounds` rounds on `sim` (default: `make_fl_sim(args)`)
    in checkpointed segments; returns the final summary."""
    from repro.checkpoint import CheckpointManager

    if sim is None:
        sim = make_fl_sim(args)
    tracer, metrics = sim._tracer, sim._metrics

    mgr = CheckpointManager(args.ckpt_dir, max_to_keep=2) \
        if args.ckpt_dir else None
    start_round = 0
    if mgr and args.resume:
        latest = mgr.latest_step()
        if latest is not None:
            state = mgr.restore(latest)
            restore_fl_state(sim, state)
            start_round = int(state["round"])
            log.status(f"[train] resumed from round {start_round}")

    # run in checkpointed segments so a crash loses at most one segment
    seg = max(1, args.ckpt_every)
    hist_all = []
    t0 = time.perf_counter()
    while sim.model.round < args.rounds:
        target = min(args.rounds, sim.model.round + seg)
        hist = sim.run(total_rounds=target, eval_every=args.eval_every)
        hist_all.extend(hist.records)
        if mgr:
            mgr.save(sim.model.round, fl_ckpt_state(sim))
            mgr.wait()
        r = hist.records[-1]
        log.status(f"[train] round={sim.model.round} acc={r.accuracy:.3f} "
                   f"sim_t={r.time:.1f}s comm={r.gbits:.3f}Gb "
                   f"wall={time.perf_counter()-t0:.0f}s")
    if not hist_all:
        # resumed at/past the target round: nothing to train, just eval
        hist_all.extend(
            sim.run(total_rounds=sim.model.round, eval_every=1).records)
    final = hist_all[-1]
    export_obs(args, tracer, metrics,
               extra={"engine": "batched" if sim._batched else "sequential",
                      "task": args.task, "method": args.method})
    return {"final_accuracy": final.accuracy, "final_loss": final.loss,
            "rounds": sim.model.round, "gbits": final.gbits,
            "sim_time": final.time, "events": sim.events_processed,
            "fault_counters": sim.fault_counters()}


# ------------------------------------------------------------- datacenter mode
def run_datacenter(args) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core import compression as C
    from repro.core.controller import DeviceProfile, FedLuckController
    from repro.data.synthetic import SyntheticTokens
    from repro.dist.steps import make_local_round_step
    from repro.models.transformer import LM
    from repro.optim import momentum_sgd
    from repro.checkpoint import CheckpointManager

    cfg = get_config(args.arch).smoke()
    lm = LM(cfg, dtype=jnp.float32, remat=False)
    opt = momentum_sgd(args.eta_l, momentum=0.9)
    n_pods = args.pods

    # ---- controller picks (k, δ) per pod from measured α and link β
    ctl = FedLuckController(round_period=args.round_period,
                            k_bounds=(1, args.local_k_max),
                            delta_bounds=(1e-3, 1.0))
    dim_probe = None

    params = [lm.init(jax.random.PRNGKey(args.seed)) for _ in range(n_pods)]
    opt_states = [opt.init(p) for p in params]
    flat0, spec0 = C.flatten_pytree(params[0])
    dim = int(flat0.size)
    residuals = [np.zeros((dim,), np.float32) for _ in range(n_pods)]

    if cfg.frontend != "tokens":
        raise SystemExit("datacenter demo supports token LMs")
    ds = SyntheticTokens(vocab=cfg.vocab, seq_len=65, num_samples=2048)

    local_round = {}
    # measure α on pod 0, derive β from a nominal 100 Gb/s DCN link
    def batches_for(k, rng):
        idx = rng.randint(0, len(ds), size=(k, args.batch_size))
        bs = [ds.batch(i) for i in idx]
        return {kk: np.stack([b[kk] for b in bs]) for kk in bs[0]}

    rng = np.random.RandomState(args.seed)
    probe = jax.jit(make_local_round_step(lm, opt, 2))
    t0 = time.perf_counter()
    probe(params[0], opt_states[0], batches_for(2, rng))
    t1 = time.perf_counter()
    out = probe(params[0], opt_states[0], batches_for(2, rng))
    jax.block_until_ready(out[3])
    alpha = (time.perf_counter() - t1) / 2
    beta = dim * 32 / args.dcn_bps
    plans = [ctl.register(DeviceProfile(i, alpha * (1 + 0.5 * i), beta))
             for i in range(n_pods)]
    log.status("[datacenter] plans:")
    log.status(ctl.summary())

    mgr = CheckpointManager(args.ckpt_dir, max_to_keep=2) \
        if args.ckpt_dir else None

    comm_bits = 0.0
    t0 = time.perf_counter()
    for step in range(args.steps):
        deltas = []
        losses = []
        for i in range(n_pods):
            k = plans[i].k if not args.local_k else args.local_k
            if k not in local_round:
                local_round[k] = jax.jit(make_local_round_step(lm, opt, k))
            p1, o1, delta, loss = local_round[k](
                params[i], opt_states[i], batches_for(k, rng))
            flat_d, _ = C.flatten_pytree(delta)
            rate = plans[i].delta if not args.rate else args.rate
            comp, residuals[i] = C.ef_compress(
                C.make_compressor("topk", rate), np.asarray(flat_d),
                residuals[i])
            deltas.append(np.asarray(comp.dense()))
            # payload-shape accounting: value/index bits + kept-count
            # header, matching the compact pod-sync wire format
            comm_bits += float(C.payload_bits(comp))
            opt_states[i] = o1
            losses.append(float(loss))
        # Eq. 6 aggregation (the sparse all-reduce in the real deployment)
        agg = np.mean(deltas, axis=0)
        flat_w, specw = C.flatten_pytree(params[0])
        new_flat = np.asarray(flat_w) - args.eta_g * agg
        new_params = C.unflatten_pytree(jnp.asarray(new_flat), specw)
        params = [new_params for _ in range(n_pods)]
        if mgr and (step + 1) % args.ckpt_every == 0:
            mgr.save(step + 1, {"w": new_flat})
            mgr.wait()
        if step % 5 == 0 or step == args.steps - 1:
            log.status(f"[datacenter] round={step} "
                       f"loss={np.mean(losses):.4f} "
                       f"comm={comm_bits/8e6:.1f}MB "
                       f"wall={time.perf_counter()-t0:.0f}s")
    return {"loss": float(np.mean(losses)), "comm_mb": comm_bits / 8e6}


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="fl", choices=["fl", "datacenter"])
    # fl
    ap.add_argument("--task", default="cnn_fmnist")
    ap.add_argument("--method", default="fedluck")
    ap.add_argument("--rounds", type=int, default=40)
    ap.add_argument("--devices", type=int, default=10)
    ap.add_argument("--round-period", type=float, default=1.0)
    ap.add_argument("--k-max", type=int, default=30)
    ap.add_argument("--fixed-k", type=int, default=10)
    ap.add_argument("--fixed-delta", type=float, default=0.1)
    ap.add_argument("--eta-l", type=float, default=0.05)
    ap.add_argument("--eta-g", type=float, default=1.0)
    ap.add_argument("--base-alpha", type=float, default=0.02)
    ap.add_argument("--samples", type=int, default=4000)
    ap.add_argument("--test-samples", type=int, default=800)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--noise", type=float, default=None)
    ap.add_argument("--error-feedback", action="store_true")
    ap.add_argument("--noniid", action="store_true")
    ap.add_argument("--inject-failures", action="store_true")
    ap.add_argument("--failure-rate", type=float, default=0.0,
                    help="mean crash windows per device over the run "
                         "(FailureSchedule.random rate_per_device)")
    ap.add_argument("--loss-rate", type=float, default=0.0,
                    help="per-attempt upload loss probability (LossyChannel "
                         "with default retry/backoff policy)")
    ap.add_argument("--tau-max", type=int, default=None,
                    help="staleness cap: aggregation drops updates with "
                         "τ > tau-max (enables the UpdateSanitizer)")
    ap.add_argument("--clip-norm", type=float, default=None,
                    help="L2 norm outlier guard on admitted updates "
                         "(enables the UpdateSanitizer)")
    ap.add_argument("--eval-every", type=int, default=2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    # datacenter
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--pods", type=int, default=2)
    ap.add_argument("--local-k", type=int, default=0)
    ap.add_argument("--local-k-max", type=int, default=10)
    ap.add_argument("--rate", type=float, default=0.0)
    ap.add_argument("--dcn-bps", type=float, default=100e9)
    # observability (fl mode)
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto/Chrome trace JSON of the run "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="write a metrics snapshot JSON "
                         "(repro.obs.MetricsRegistry)")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress status lines (final JSON still printed)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    log.set_quiet(args.quiet)
    compile_cache.enable()

    res = run_fl(args) if args.mode == "fl" else run_datacenter(args)
    print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
