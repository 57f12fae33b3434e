"""Chaos benchmark: method resilience under loss × crash × straggler drift.

Sweeps FedLuck against the FedPer / FedBuff baselines across escalating
fault levels — upload loss + NaN corruption (`repro.ft.LossyChannel`),
random crash windows (`repro.ft.FailureSchedule`), and a mid-run compute
slowdown (`repro.ft.StragglerDrift`) — with the aggregation-side
`UpdateSanitizer` guarding the global model. FedLuck runs with a live
`FedLuckController`, so the straggler's α drift triggers a mid-run
re-plan; the baselines ride out the same faults with their static plans.
Emits `BENCH_chaos.json` with per-cell accuracy, comm, and the full
drop/retry/replan counter block.

  PYTHONPATH=src python benchmarks/chaos_bench.py                 # full sweep
  PYTHONPATH=src python benchmarks/chaos_bench.py --smoke         # CI job
  PYTHONPATH=src python benchmarks/chaos_bench.py --out BENCH_chaos.json

Every invocation (smoke included) also runs the engine-equivalence gate: a
failure-injected FedLuck fleet must be *bitwise* identical between the
batched and sequential engines — weights, record timeline, and fault
counters. A mismatch exits nonzero so CI fails loudly.
"""
from __future__ import annotations

import argparse
import json
import sys

from repro.launch import compile_cache
from repro.obs import log

# fault intensity grid: per-attempt loss / corruption probability, mean
# crash windows per device over the run, straggler α multiplier (device 0,
# kicking in a third of the way through)
FAULT_LEVELS = {
    "clean":  dict(loss=0.0, corrupt=0.0, crash_rate=0.0, drift=0.0),
    "mild":   dict(loss=0.1, corrupt=0.02, crash_rate=0.5, drift=2.0),
    "severe": dict(loss=0.3, corrupt=0.1, crash_rate=1.5, drift=4.0),
}

METHODS = ["fedluck", "fedper", "fedbuff"]


def _fault_kwargs(level: dict, num_devices: int, horizon: float, seed: int):
    """Fresh fault-model instances per simulator (channels are stateful)."""
    from repro.ft import FailureSchedule, LossyChannel, StragglerDrift
    kw = {}
    if level["crash_rate"] > 0:
        kw["failure_schedule"] = FailureSchedule.random(
            num_devices, horizon, rate_per_device=level["crash_rate"],
            mean_downtime=horizon / 20, seed=seed + 1)
    if level["loss"] > 0 or level["corrupt"] > 0:
        kw["channel"] = LossyChannel(loss_prob=level["loss"],
                                     corrupt_prob=level["corrupt"],
                                     seed=seed + 2)
    if level["drift"] > 0:
        kw["stragglers"] = [StragglerDrift(0, horizon / 3.0, level["drift"])]
    return kw


def _build(method: str, engine: str, level: str, *, task, num_devices: int,
           rounds: int, seed: int = 0, tracer=None, metrics=None):
    import jax
    import numpy as np

    from repro.core import compression as C
    from repro.core.aggregation import SanitizerConfig
    from repro.core.controller import FedLuckController
    from repro.core.simulator import (AFLSimulator, STRATEGY_FOR_METHOD,
                                      make_heterogeneous_devices,
                                      plan_devices)

    params = task.init_fn(jax.random.PRNGKey(seed))
    flat, _ = C.flatten_pytree(params)
    model_bits = int(np.asarray(flat).size) * 32
    profiles = make_heterogeneous_devices(num_devices, model_bits,
                                          base_alpha=0.2, seed=seed)
    # only FedLuck gets the drift-aware controller: that asymmetry IS the
    # experiment — the baselines cannot re-plan around the straggler
    ctl = (FedLuckController(1.0, k_bounds=(1, 16))
           if method == "fedluck" else None)
    specs = plan_devices(profiles, method, 1.0, k_bounds=(1, 16),
                         fixed_k=4, fixed_delta=0.1, controller=ctl)
    kw = _fault_kwargs(FAULT_LEVELS[level], num_devices, float(rounds), seed)
    return AFLSimulator(task, specs, STRATEGY_FOR_METHOD[method],
                        round_period=1.0, seed=seed, engine=engine,
                        controller=ctl, sanitizer=SanitizerConfig(tau_max=10),
                        tracer=tracer, metrics=metrics, **kw)


def run_cell(method: str, level: str, *, task, num_devices: int, rounds: int,
             seed: int = 0, engine: str = "batched", tracer=None,
             metrics=None) -> dict:
    sim = _build(method, engine, level, task=task, num_devices=num_devices,
                 rounds=rounds, seed=seed, tracer=tracer, metrics=metrics)
    h = sim.run(total_rounds=rounds, eval_every=max(1, rounds // 4))
    out = {
        "method": method,
        "level": level,
        "final_acc": round(h.final_accuracy(), 4),
        "final_loss": round(h.records[-1].loss, 4),
        "sim_time_s": round(h.records[-1].time, 3),
        "gbits": round(h.records[-1].gbits, 4),
        "counters": h.counters,
    }
    sim.close()
    return out


def equivalence_gate(task, *, num_devices: int = 4, rounds: int = 4,
                     seed: int = 0) -> bool:
    """Failure-injected batched vs sequential must be bitwise identical."""
    import numpy as np
    outs = {}
    for eng in ("batched", "sequential"):
        sim = _build("fedluck", eng, "severe", task=task,
                     num_devices=num_devices, rounds=rounds, seed=seed)
        h = sim.run(total_rounds=rounds, eval_every=2)
        outs[eng] = (np.asarray(sim.model.w).copy(),
                     [(r.time, r.round, r.loss, r.gbits, r.drops)
                      for r in h.records],
                     sim.fault_counters())
        sim.close()
    b, s = outs["batched"], outs["sequential"]
    return bool(np.array_equal(b[0], s[0])) and b[1] == s[1] and b[2] == s[2]


def run_bench(smoke: bool = False, seed: int = 0, tracer=None,
              metrics=None) -> dict:
    from repro.launch.mesh import device_info
    from repro.models.small import make_task
    task = make_task("mlp_micro", num_samples=2000, test_samples=200,
                     batch_size=32, seed=seed)
    report = {"bench": "chaos_resilience_sweep", "device": device_info(),
              "sanitizer": "nonfinite guard + tau_max=10",
              "fault_levels": FAULT_LEVELS}
    if smoke:
        report["mode"] = "smoke"
        num_devices, rounds = 4, 4
        methods, levels = ["fedluck"], ["severe"]
    else:
        report["mode"] = "full"
        num_devices, rounds = 8, 16
        methods, levels = METHODS, list(FAULT_LEVELS)
    report["devices"], report["rounds"] = num_devices, rounds
    cells = []
    first = True
    for method in methods:
        for level in levels:
            log.status(f"[chaos_bench] {method} / {level} ...")
            # obs instrumentation attaches to the first cell only — one
            # run per trace keeps the Perfetto timeline readable
            cells.append(run_cell(
                method, level, task=task, num_devices=num_devices,
                rounds=rounds, seed=seed,
                tracer=tracer if first else None,
                metrics=metrics if first else None))
            first = False
    report["cells"] = cells
    log.status("[chaos_bench] engine equivalence gate ...")
    report["equivalence_ok"] = equivalence_gate(task, seed=seed)
    return report


def smoke_rows():
    """CSV rows for benchmarks.run integration: name,us_per_call,derived."""
    rep = run_bench(smoke=True)
    rows = []
    for c in rep["cells"]:
        rows.append((f"chaos_{c['method']}_{c['level']}", 0.0,
                     f"acc={c['final_acc']} "
                     f"drops={c['counters']['drops_total']}"))
    rows.append(("chaos_equivalence", 0.0,
                 "bitwise" if rep["equivalence_ok"] else "FAILED"))
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="one fedluck/severe cell + equivalence gate (CI)")
    ap.add_argument("--out", default="",
                    help="write the JSON report here (default: stdout only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-out", default="",
                    help="write a Perfetto/Chrome trace of the first cell "
                         "(open at ui.perfetto.dev)")
    ap.add_argument("--metrics-out", default="",
                    help="write the first cell's metrics snapshot JSON")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress status lines (JSON report still printed)")
    args = ap.parse_args(argv)
    log.set_quiet(args.quiet)
    compile_cache.enable()

    tracer = metrics = None
    if args.trace_out:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics_out:
        from repro.obs import MetricsRegistry
        metrics = MetricsRegistry()

    report = run_bench(smoke=args.smoke, seed=args.seed, tracer=tracer,
                       metrics=metrics)
    text = json.dumps(report, indent=1)
    print(text)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
        log.status(f"[chaos_bench] wrote {args.out}")
    if tracer is not None:
        from repro.obs import PerfettoExporter
        PerfettoExporter().export(tracer, args.trace_out)
        log.status(f"[chaos_bench] wrote trace: {args.trace_out} "
                   f"({len(tracer)} events)")
    if metrics is not None:
        metrics.to_json(args.metrics_out, extra={"bench": "chaos_bench"})
        log.status(f"[chaos_bench] wrote metrics: {args.metrics_out}")

    if not report["equivalence_ok"]:
        print("[chaos_bench] FAIL: batched and sequential engines disagree "
              "under injected failures", file=sys.stderr)
        return 1
    # every faulted cell must have survived with a finite model
    import math
    bad = [c for c in report["cells"] if not math.isfinite(c["final_loss"])]
    if bad:
        print(f"[chaos_bench] FAIL: non-finite final loss in {bad}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
