"""Resolve a cell by name, run it once, and report it.

Everything particular to a cell is data or a file of its own, found by the
names in BENCHMARK.json:
  bench/configs/<config>.json   the configuration as run
  bench/configs/<config>.py     its weights, program entry and reference
  bench/traffic/<mix>.json      the traffic mix (read by bench/traffic.py)
  bench/paths/<path>.py         the program path the mix names, and the
                                faults it can have (`FAULTS`)
  bench/metrics/<metric>.py     one reader per metric
  bench/limits/<cell>.json      the limits of the correctness check
"""
from __future__ import annotations

import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoChip(RuntimeError):
    pass


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _named(entries: list, name: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"{name!r} is not in BENCHMARK.json")


def resolve(workload: str, bench: dict | None = None) -> dict:
    """The cell's entry, configuration, model module, traffic mix, limits
    and the metrics it reports, from BENCHMARK.json and the files named by
    it."""
    from bench import check, traffic
    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = _named(bench["workloads"], workload)
    conf = _named(bench["configs"], cell["config"])
    cfg_file = ROOT / conf["file"]
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return {"cell": cell, "cfg": json.loads(cfg_file.read_text()),
            "model": load_module(cfg_file.with_suffix(".py")),
            "mix": traffic.load(cell["traffic"]),
            "limits": check.load_limits(workload),
            "end_to_end": e2e, "per_layer": per_layer}


def device_info(chips: int) -> dict:
    """JAX's devices as a result names them; raises NoChip without a TPU
    or with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {info['count']} "
                     f"{info['platform']} device(s)")
    info["count"] = chips
    return info


class CompileCounter:
    """Backend compiles (persistent-cache loads included), from JAX's
    monitoring events."""

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def memory_peak(chips: int, program_peak: int | None = None) -> int | None:
    """The fullest chip's peak: the allocator's, or the compiled program's
    own (its arguments, outputs and temporaries, per chip) where that is
    larger, since on a TPU the allocator does not count a program's
    temporaries."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]] + [program_peak]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def profiler_options():
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    o.host_tracer_level = 1
    o.enable_hlo_proto = False
    return o


def run_cell(r: dict, seed: int, seconds: float, trace: bool, *,
             t_start: float, on_chip: bool = True) -> dict:
    """Set up, measure, check. Returns the result line's object with the
    compared numbers under `check` (last). `on_chip=False` (the CPU tests
    only) skips the look for a chip and the persistent compile cache."""
    import jax

    from bench import check, peaks, reduce

    cell, mix = r["cell"], r["mix"]
    chips = int(cell["chips"])
    if on_chip:
        device = device_info(chips)
        from repro.launch import compile_cache
        compile_cache.enable()
    else:
        d = jax.devices()
        device = {"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": chips}
    compiles = CompileCounter()
    path = load_module(BENCH / "paths" / f"{mix['path']}.py")
    run = path.Cell(r["cfg"], r["model"], mix, chips, seed, trace=trace)
    run.setup()
    # set-up leaves a large Python heap; a full collection of it inside the
    # window stalls the host for a tenth of a second or more. Collect once
    # here and keep what is left out of later collections.
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    ctx = types.SimpleNamespace(run=run, cfg=r["cfg"], mix=mix, chips=chips,
                                setup_s=setup_s, trace=None, device=device)
    n0 = compiles.n
    if trace:
        from repro.obs import profiling
        profiling.set_profiling(True)
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            with jax.profiler.trace(tdir, profiler_options=profiler_options()):
                work = run.window(seconds,
                                  annotate=jax.profiler.TraceAnnotation)
            ctx.trace = reduce.reduce_dir(
                tdir, chips, reduce.op_names(run.hlo_text())
                if hasattr(run, "hlo_text") else None)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
            profiling.set_profiling(False)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
    else:
        work = run.window(seconds)
    work["window_compiles"] = compiles.n - n0
    ctx.work = work
    device["memory_peak_bytes"] = memory_peak(
        chips, getattr(run, "program_peak_bytes", None))
    if device["platform"] == "tpu":
        ctx.peaks = peaks.lookup(device["kind"])
    else:
        ctx.peaks = None

    readings = run.readings
    gc.unfreeze()
    run.free()
    ref = run.reference("float32")
    gaps = check.gaps(readings, ref)
    correct, table = check.verdict(gaps, r["limits"])

    specs = r["per_layer"] if trace else r["end_to_end"]
    metrics = {}
    for m in specs:
        value = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(table),
           "failed": sum(1 for v in table.values()
                         if not v["value"] <= v["limit"]),
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = ctx.trace.breakdown()
    out["work"] = {k: v for k, v in work.items() if k != "flops"}
    out["check"] = table
    return out


def readings(r: dict, seed: int, fault: str = "", control: bool = False,
             *, on_chip: bool = True) -> dict:
    """One seed's set-up and reference, without a window: the program's
    gaps and, with `control`, the int8 control's."""
    import contextlib

    from bench import check

    chips = int(r["cell"]["chips"])
    if on_chip:
        device_info(chips)
        from repro.launch import compile_cache
        compile_cache.enable()
    path = load_module(BENCH / "paths" / f"{r['mix']['path']}.py")
    run = path.Cell(r["cfg"], r["model"], r["mix"], chips, seed)
    planted = path.FAULTS[fault]() if fault else contextlib.nullcontext()
    t0 = time.perf_counter()
    with planted:
        run.setup()
    t1 = time.perf_counter()
    run.free()
    ref = run.reference("float32")
    t2 = time.perf_counter()
    raw = {"program": run.readings, "reference": ref}
    out = {"seed": seed, "fault": fault,
           "program": check.gaps(run.readings, ref),
           "setup_s": t1 - t0, "reference_s": t2 - t1}
    if control:
        raw["control"] = run.reference("int8")
        out["control"] = check.gaps(raw["control"], ref)
    out["raw"] = {k: {q: [float(x) for x in v] for q, v in d.items()}
                  for k, d in raw.items()}
    return out


def main(workload: str, seed: int, seconds: float, trace: bool, *,
         t_start: float) -> int:
    r = resolve(workload)
    try:
        out = run_cell(r, seed, seconds, trace, t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, v in out["check"].items():
        print(f"check {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
