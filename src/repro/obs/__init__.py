"""Observability layer: structured event tracing, streaming metrics, and
profiling hooks for the AFL stack.

Four small pieces, composable and individually optional:

  trace      `Tracer` — per-device spans and instant events recorded in
             *simulated* time (local-round compute, upload attempt/retry/
             loss, crash/recovery windows, sanitizer rejections, controller
             re-plans, eval rounds). `NullTracer` keeps every call site a
             no-op so the hot path stays zero-cost when tracing is off.
  perfetto   `PerfettoExporter` — Chrome-trace/Perfetto JSON (one track per
             device plus server/controller tracks), loadable in
             ui.perfetto.dev. `validate_chrome_trace` is the schema gate
             (required keys: ph, ts, pid, tid, name) used by the tests and
             the CI obs-smoke job.
  metrics    `MetricsRegistry` — counters, gauges, and fixed-bucket
             histograms (pure host-side Python, no wall clock or RNG in
             hot paths): staleness per eval window, wire-bit breakdowns
             (payload / header / retransmission), batched-engine bucket
             occupancy and recompiles, channel/sanitizer/controller totals.
  profiling  `PhaseTimers` (perf_counter wall-clock phase accumulators for
             heap-drain / bucket dispatch / host aggregation) and
             `annotate()` — an optional `jax.profiler` trace-annotation
             context around the simulator's bucket dispatch
             (`sim.bucket_dispatch`), enabled via `set_profiling(True)` or
             REPRO_PROFILE=1.
  log        stdout-safe status lines: progress text goes to stderr (and a
             `--quiet` flag silences it), so benchmark JSON on stdout is
             never interleaved with progress prints.

The simulator (`repro.core.simulator.AFLSimulator(tracer=..., metrics=...)`)
injects all instrumentation at the engine-shared seams, so the batched and
sequential engines emit *identical* traces and metric totals on identical
runs — tested in tests/test_simulator_batched.py.
"""
from repro.obs import log
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               STALENESS_BUCKETS)
from repro.obs.perfetto import (PerfettoExporter, validate_chrome_trace,
                                validate_metrics_json)
from repro.obs.profiling import PhaseTimers, annotate, set_profiling
from repro.obs.trace import (NULL_TRACER, NullTracer, TraceEvent, Tracer,
                             CONTROLLER_TRACK, SERVER_TRACK, device_track)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "STALENESS_BUCKETS",
    "PerfettoExporter", "validate_chrome_trace", "validate_metrics_json",
    "PhaseTimers", "annotate", "set_profiling",
    "NULL_TRACER", "NullTracer", "TraceEvent", "Tracer",
    "CONTROLLER_TRACK", "SERVER_TRACK", "device_track", "log",
]
