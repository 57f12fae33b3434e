"""Simulator throughput benchmark: events/sec and wall-clock per sim-second.

Measures the batched device-resident engine (`AFLSimulator(engine="batched")`)
against the sequential pre-batching reference path on periodic-FedLuck
fleets, and emits `BENCH_simulator.json` — the perf-trajectory baseline the
ROADMAP simulator-performance item calls for.

  PYTHONPATH=src python benchmarks/sim_bench.py                # full run
  PYTHONPATH=src python benchmarks/sim_bench.py --smoke        # tiny CI fleet
  PYTHONPATH=src python benchmarks/sim_bench.py --out BENCH_simulator.json

Methodology: every measurement is steady-state — a short warmup segment
first runs both engines through their jit compiles, then the reported
`wall_s` covers exactly `rounds` simulated rounds. Warmup wall time is
reported separately as `warm_s`.

The headline is the engine-throughput configuration: a 100-device /
50-round periodic-FedLuck fleet on the compute-light `mlp_micro` task with
slow edge devices (base_alpha=0.2 → small k*). Per-cycle model compute is
negligible there, so the number isolates what this benchmark is about —
event-loop + dispatch throughput, where the batched engine must beat the
sequential path by >= 5x. The fleet sweep adds 10/50/200-device scaling
rows plus a compute-bound `mlp_fmnist` row (where both engines spend most
wall time in identical local-round FLOPs on one core, so the honest
speedup is small) and an error-feedback row exercising the device-resident
stacked-residual path.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.launch import compile_cache
from repro.obs import log

# plan-time k grid: collapses the number of distinct compiled local-round
# shapes (the batched engine jits one vmapped cycle per (k, bucket) pair)
K_GRID = [1, 2, 3, 4, 6, 8, 12, 16, 24, 30]


def _build_sim(engine: str, num_devices: int, *, task, seed: int = 0,
               error_feedback: bool = False, k_max: int = 30,
               base_alpha: float = 0.2, prefetch: int = 0):
    from repro.core import compression as C
    from repro.core.simulator import (AFLSimulator, make_heterogeneous_devices,
                                      plan_devices)
    import jax
    import numpy as np

    params = task.init_fn(jax.random.PRNGKey(seed))
    flat, _ = C.flatten_pytree(params)
    model_bits = int(np.asarray(flat).size) * 32
    profiles = make_heterogeneous_devices(num_devices, model_bits,
                                          base_alpha=base_alpha, seed=seed)
    specs = plan_devices(profiles, "fedluck", 1.0, k_bounds=(1, k_max),
                         error_feedback=error_feedback, k_grid=K_GRID)
    return AFLSimulator(task, specs, "periodic", round_period=1.0,
                        seed=seed, engine=engine, prefetch=prefetch)


def measure(engine: str, num_devices: int, rounds: int, *, task,
            error_feedback: bool = False, k_max: int = 30,
            base_alpha: float = 0.2, warmup_rounds: int = 5,
            prefetch: int = 0) -> dict:
    sim = _build_sim(engine, num_devices, task=task,
                     error_feedback=error_feedback, k_max=k_max,
                     base_alpha=base_alpha, prefetch=prefetch)
    t0 = time.perf_counter()
    sim.run(total_rounds=warmup_rounds, eval_every=0)
    warm = time.perf_counter() - t0
    ev0 = sim.events_processed
    t0 = time.perf_counter()
    hist = sim.run(total_rounds=warmup_rounds + rounds, eval_every=0)
    wall = time.perf_counter() - t0
    sim.close()
    events = sim.events_processed - ev0
    sim_time = hist.records[-1].time
    return {
        "engine": engine,
        "devices": num_devices,
        "rounds": rounds,
        "error_feedback": error_feedback,
        "warm_s": round(warm, 3),
        "wall_s": round(wall, 3),
        "events": events,
        "events_per_sec": round(events / wall, 2),
        "wall_per_sim_sec": round(wall / sim_time, 4) if sim_time else None,
        "sim_time_s": round(float(sim_time), 3),
        "final_acc": round(hist.final_accuracy(), 4),
        # resilience telemetry (zero on clean fleets): crash/channel drops,
        # retry counts, sanitizer rejections — see AFLSimulator.fault_counters
        "counters": sim.fault_counters(),
    }


def _pair(num_devices: int, rounds: int, *, task, ef: bool = False,
          k_max: int = 30, base_alpha: float = 0.2, warmup_rounds: int = 5,
          prefetch: int = 0, skip_sequential: bool = False) -> dict:
    out = {"devices": num_devices, "rounds": rounds, "error_feedback": ef,
           "task": task.name}
    for eng in ("batched",) if skip_sequential else ("batched", "sequential"):
        log.status(f"[sim_bench] task={task.name} devices={num_devices} "
                   f"rounds={rounds} ef={ef} {eng} ...")
        out[eng] = measure(eng, num_devices, rounds, task=task,
                           error_feedback=ef, k_max=k_max,
                           base_alpha=base_alpha, warmup_rounds=warmup_rounds,
                           prefetch=prefetch)
    if not skip_sequential:
        out["speedup_wall"] = round(
            out["sequential"]["wall_s"] / out["batched"]["wall_s"], 2)
    return out


def run_bench(smoke: bool = False, seed: int = 0, prefetch: int = 0) -> dict:
    from repro.launch.mesh import device_info
    from repro.models.small import make_task

    micro = make_task("mlp_micro", num_samples=2000, test_samples=200,
                      batch_size=32, seed=seed)
    report = {"bench": "simulator_events_per_sec",
              "strategy": "periodic (FedLuck plans)", "device": device_info(),
              "unit": "simulated events/sec; wall seconds per sim second",
              "methodology": "steady-state: jit warmup excluded (warm_s)",
              "prefetch": prefetch}
    if smoke:
        report["mode"] = "smoke"
        report["headline"] = _pair(4, 3, task=micro, warmup_rounds=2,
                                   prefetch=prefetch)
        report["fleets"] = [report["headline"]]
        return report

    report["mode"] = "full"
    # acceptance headline: 100-device / 50-round periodic-FedLuck run on the
    # engine-throughput (compute-light) configuration
    report["headline"] = _pair(100, 50, task=micro, prefetch=prefetch)
    fleets = [_pair(10, 20, task=micro, prefetch=prefetch),
              _pair(50, 20, task=micro, prefetch=prefetch),
              _pair(200, 20, task=micro, prefetch=prefetch)]
    # EF exercises the device-resident stacked-residual path
    fleets.append(_pair(50, 10, task=micro, ef=True, prefetch=prefetch))
    # prefetch row: background stacking thread (pays off with spare cores)
    fleets.append(_pair(50, 10, task=micro, prefetch=max(1, prefetch)))
    # compute-bound regime: both engines pay identical local-round FLOPs on
    # one core, so the gap narrows to the eliminated dispatch/sort overhead
    fmnist = make_task("mlp_fmnist", num_samples=2000, test_samples=200,
                       batch_size=32, seed=seed)
    fleets.append(_pair(20, 10, task=fmnist, warmup_rounds=3))
    report["fleets"] = fleets
    return report


def smoke_rows():
    """CSV rows for benchmarks.run integration: name,us_per_call,derived."""
    rep = run_bench(smoke=True)
    rows = []
    for eng in ("batched", "sequential"):
        r = rep["headline"][eng]
        us_per_event = 1e6 * r["wall_s"] / max(1, r["events"])
        rows.append((f"sim_{eng}_d{r['devices']}", us_per_event,
                     f"{r['events_per_sec']}ev/s"))
    rows.append(("sim_speedup", 0.0, f"{rep['headline']['speedup_wall']}x"))
    return rows


def _obs_sim(engine: str, num_devices: int, *, task, seed: int,
             tracer=None, metrics=None):
    """Fault-injected instrumented simulator: a mildly lossy channel plus
    crash windows and a sanitizer, so the exported trace/metrics carry
    nonzero retry/drop/corruption activity."""
    from repro.core import compression as C
    from repro.core.aggregation import SanitizerConfig
    from repro.core.simulator import (AFLSimulator, make_heterogeneous_devices,
                                      plan_devices)
    from repro.ft import FailureSchedule, LossyChannel
    import jax
    import numpy as np

    params = task.init_fn(jax.random.PRNGKey(seed))
    flat, _ = C.flatten_pytree(params)
    model_bits = int(np.asarray(flat).size) * 32
    profiles = make_heterogeneous_devices(num_devices, model_bits,
                                          base_alpha=0.2, seed=seed)
    specs = plan_devices(profiles, "fedluck", 1.0, k_bounds=(1, 30),
                         k_grid=K_GRID)
    return AFLSimulator(
        task, specs, "periodic", round_period=1.0, seed=seed, engine=engine,
        failure_schedule=FailureSchedule.random(
            num_devices, 20.0, rate_per_device=0.5, mean_downtime=0.5,
            seed=seed + 1),
        channel=LossyChannel(loss_prob=0.25, corrupt_prob=0.05,
                             seed=seed + 2),
        sanitizer=SanitizerConfig(tau_max=16),
        tracer=tracer, metrics=metrics)


def run_obs(args) -> int:
    """Instrumented dual-engine run behind --trace-out/--metrics-out.

    Gates (any violation exits nonzero):
      * batched and sequential emit IDENTICAL event sequences;
      * engine-agnostic metric snapshots are identical;
      * exported faults.* totals equal History.counters EXACTLY per engine;
      * optional --overhead-gate: a NullTracer run (every call site
        exercised, all no-ops) stays under gate x the default wall time.
    """
    from repro.models.small import make_task
    from repro.obs import (MetricsRegistry, NullTracer, PerfettoExporter,
                           Tracer)

    rounds = 6 if args.smoke else 20
    task = make_task("mlp_micro", num_samples=2000, test_samples=200,
                     batch_size=32, seed=args.seed)
    runs = {}
    for eng in ("batched", "sequential"):
        log.status(f"[sim_bench] obs run: {eng} devices={args.devices} "
                   f"rounds={rounds} ...")
        tracer, metrics = Tracer(), MetricsRegistry()
        sim = _obs_sim(eng, args.devices, task=task, seed=args.seed,
                       tracer=tracer, metrics=metrics)
        hist = sim.run(total_rounds=rounds, eval_every=2)
        sim.close()
        snap = metrics.snapshot()
        for k, v in hist.counters.items():
            if snap["counters"].get(f"faults.{k}") != float(v):
                print(f"[sim_bench] FAIL: {eng} faults.{k}="
                      f"{snap['counters'].get(f'faults.{k}')} != "
                      f"History.counters[{k!r}]={v}", file=sys.stderr)
                return 1
        runs[eng] = {"tracer": tracer, "metrics": metrics, "hist": hist}
    b, s = runs["batched"], runs["sequential"]
    if b["tracer"].events != s["tracer"].events:
        print("[sim_bench] FAIL: engines emitted different event sequences",
              file=sys.stderr)
        return 1
    if (b["metrics"].snapshot(engine_agnostic=True)
            != s["metrics"].snapshot(engine_agnostic=True)):
        print("[sim_bench] FAIL: engine-agnostic metrics differ",
              file=sys.stderr)
        return 1
    if b["hist"].counters["retries"] == 0:
        print("[sim_bench] FAIL: fault injection produced no retries",
              file=sys.stderr)
        return 1
    if args.trace_out:
        PerfettoExporter().export(b["tracer"], args.trace_out)
        log.status(f"[sim_bench] wrote trace: {args.trace_out} "
                   f"({len(b['tracer'])} events)")
    if args.metrics_out:
        doc = {"schema": "repro.obs.metrics/v1", "bench": "sim_bench_obs",
               "devices": args.devices, "rounds": rounds,
               "batched": b["metrics"].snapshot(),
               "sequential": s["metrics"].snapshot()}
        with open(args.metrics_out, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        log.status(f"[sim_bench] wrote metrics: {args.metrics_out}")

    if args.overhead_gate > 0:
        def wall(tracer):
            best = float("inf")
            for _ in range(3):
                sim = _obs_sim("batched", args.devices, task=task,
                               seed=args.seed, tracer=tracer)
                t0 = time.perf_counter()
                sim.run(total_rounds=rounds, eval_every=2)
                best = min(best, time.perf_counter() - t0)
                sim.close()
            return best
        plain = wall(None)          # default: guards skip every call site
        null = wall(NullTracer())   # every call site runs, all no-ops
        ratio = null / plain
        log.status(f"[sim_bench] no-op tracer overhead: {ratio:.3f}x "
                   f"(plain {plain:.3f}s, null {null:.3f}s, "
                   f"gate {args.overhead_gate}x)")
        if ratio > args.overhead_gate:
            print(f"[sim_bench] FAIL: no-op tracer overhead {ratio:.3f}x "
                  f"exceeds gate {args.overhead_gate}x", file=sys.stderr)
            return 1
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fleet / few rounds (CI smoke job)")
    ap.add_argument("--out", default="",
                    help="write the JSON report here (default: stdout only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prefetch", type=int, default=0,
                    help="StackedLoader prefetch depth for every fleet row "
                         "(bitwise-identical results; pays off with spare "
                         "cores)")
    ap.add_argument("--trace-out", default="",
                    help="run an instrumented fault-injected fleet and write "
                         "a Perfetto/Chrome trace (open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="write dual-engine metrics JSON from the "
                         "instrumented run")
    ap.add_argument("--overhead-gate", type=float, default=0.0,
                    help="assert a NullTracer run stays under this multiple "
                         "of the uninstrumented wall time (e.g. 1.05)")
    ap.add_argument("--devices", type=int, default=10,
                    help="fleet size for the instrumented obs run")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress status lines (JSON report still printed)")
    args = ap.parse_args(argv)
    log.set_quiet(args.quiet)
    compile_cache.enable()

    report = run_bench(smoke=args.smoke, seed=args.seed,
                       prefetch=args.prefetch)
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        log.status(f"[sim_bench] wrote {args.out}")

    # sanity gate so the CI smoke job fails loudly on a broken engine
    head = report["headline"]
    ok = (head["batched"]["events"] > 0
          and head["batched"]["events"] == head["sequential"]["events"]
          and abs(head["batched"]["final_acc"]
                  - head["sequential"]["final_acc"]) < 1e-6)
    if not ok:
        print("[sim_bench] FAIL: engines disagree", file=sys.stderr)
        return 1
    if args.trace_out or args.metrics_out or args.overhead_gate > 0:
        return run_obs(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
