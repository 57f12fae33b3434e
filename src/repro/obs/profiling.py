"""Profiling hooks: wall-clock phase timers and jax.profiler annotations.

`PhaseTimers` accumulates `time.perf_counter` wall-clock totals per named
phase (heap-drain, bucket dispatch, host aggregation, eval). perf_counter
is monotonic — immune to clock adjustments — and the timers live entirely
host-side, outside jit, so they never touch traced code.

`annotate(name)` wraps a host-side dispatch in a `jax.profiler`
TraceAnnotation when profiling is switched on (`set_profiling(True)` or
REPRO_PROFILE=1 in the environment), so `jax.profiler.trace()` captures
show it as a named host span: the simulator's `sim.bucket_dispatch`, to
which the benchmark's trace reduction puts the device's idle gaps down.
When profiling is off it returns a shared null context — one module-level
predicate per call, no allocation. The device's own layers are
`jax.named_scope`s in the jitted code, not annotations.
"""
from __future__ import annotations

import contextlib
import os
import time

_PROFILE = os.environ.get("REPRO_PROFILE", "") not in ("", "0", "false")
_NULL_CTX = contextlib.nullcontext()


def set_profiling(on: bool) -> None:
    """Globally enable/disable jax.profiler trace annotations."""
    global _PROFILE
    _PROFILE = bool(on)


def annotate(name: str):
    """Context manager: a jax.profiler TraceAnnotation named `name` when
    profiling is enabled, else a shared no-op context."""
    if not _PROFILE:
        return _NULL_CTX
    import jax
    return jax.profiler.TraceAnnotation(name)


class PhaseTimers:
    """Named wall-clock accumulators: `with timers.phase("drain"): ...`."""

    def __init__(self):
        self.totals: dict[str, float] = {}
        self.calls: dict[str, int] = {}

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self.totals[name] = self.totals.get(name, 0.0) + dt
            self.calls[name] = self.calls.get(name, 0) + 1

    def add(self, name: str, seconds: float) -> None:
        """Manual accumulation for phases that cannot use a with-block."""
        self.totals[name] = self.totals.get(name, 0.0) + float(seconds)
        self.calls[name] = self.calls.get(name, 0) + 1

    def snapshot(self) -> dict:
        return {name: {"seconds": round(self.totals[name], 6),
                       "calls": self.calls[name]}
                for name in sorted(self.totals)}

    def export_to(self, metrics) -> None:
        """Mirror totals into a MetricsRegistry under the time.* namespace
        (wall-clock: excluded from cross-engine equality by convention)."""
        for name, total in self.totals.items():
            metrics.counter(f"time.{name}_s").value = total
            metrics.counter(f"time.{name}_calls").value = \
                float(self.calls[name])
