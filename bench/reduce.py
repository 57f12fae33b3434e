"""Profiler trace -> intervals -> the numbers the per-layer metrics read.

`reduce_dir(dir, chips)` reads the `.xplane.pb` that `jax.profiler.trace`
wrote and returns a `Reduction`:
  window_s         first to last event of the traced window (host spans
                   and device ops together);
  busy_s           the union of the op intervals on each device, averaged
                   over the chips used;
  by_op            device seconds per op name, summed over chips;
  by_scope(name)   device seconds of the ops whose HLO op name carries the
                   `jax.named_scope` `name`;
  gaps             idle intervals between a device's ops, each with the
                   benchmark or program host span that was open over most
                   of it.
Device ops are the innermost events of the "XLA Ops" line of each
`/device:TPU:N` plane (a while loop's event spans its body's ops and is
left out), named by their HLO instruction; the trace carries no scope, so
a scope comes from the compiled program's HLO text (`op_names`). Host
spans are the TraceAnnotation events of the `/host:CPU` plane. The device
clock is put onto the host's by the program launches both record: the
smallest shift that starts no program on the device before the host
enqueued it.
"""
from __future__ import annotations

import dataclasses
import glob
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
# host spans: the harness's own, and the program's (`sim.bucket_dispatch`)
HOST_PREFIXES = ("bench.", "sim.")


@dataclasses.dataclass
class Event:
    name: str
    start: float          # seconds
    end: float
    scope: str = ""       # the op's HLO op name (named scopes joined by /)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged union of [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _stat(ev, name):
    for k, v in ev.stats:
        if k == name:
            return str(v)
    return ""


def op_names(hlo_text: str) -> dict[str, str]:
    """HLO instruction name -> its `op_name` metadata (the jax named scopes
    and primitive that made it), from a compiled module's text."""
    rx = re.compile(r'^\s*(?:ROOT )?%?([\w.-]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')
    return {m.group(1): m.group(2) for m in map(rx.match,
                                                  hlo_text.splitlines())
            if m}


def _innermost(events: list[Event]) -> list[Event]:
    """Drop events that contain a later one (loops around their body)."""
    events = sorted(events, key=lambda e: (e.start, -e.end))
    return [e for i, e in enumerate(events)
            if not (i + 1 < len(events) and events[i + 1].start < e.end
                    and events[i + 1].end <= e.end)]


@dataclasses.dataclass
class Reduction:
    devices: dict[int, list[Event]]
    host: list[Event]
    window: tuple[float, float]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy(self, dev: int) -> list[tuple[float, float]]:
        return union([(e.start, e.end) for e in self.devices[dev]])

    @property
    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(d))
                   for d in self.devices) / len(self.devices)

    def by_op(self) -> dict[str, float]:
        out: dict[str, float] = {}
        for evs in self.devices.values():
            for e in evs:
                out[e.name] = out.get(e.name, 0.0) + (e.end - e.start)
        return out

    def by_scope(self, scope: str) -> float:
        """Device seconds (summed over chips) of ops under a named scope,
        also where a transform wraps it (`vmap(local_round)`)."""
        pat = re.compile(r"(^|[/(])" + re.escape(scope) + r"([/)]|$)")
        return sum(e.end - e.start for evs in self.devices.values()
                   for e in evs if pat.search(e.scope))

    def kernel_calls(self, names) -> list[tuple[float, float]]:
        """The calls of a kernel as device intervals: the union, on each
        device, of the events whose op name carries one of `names`, or
        whose HLO op name ends in that kernel's `pallas_call` (so that a
        call the trace records as more than one event counts once)."""
        return [iv for evs in self.devices.values() for iv in union(
            [(e.start, e.end) for e in evs
             if any(n in e.name or e.scope.endswith(f"({n})/pallas_call")
                    for n in names)])]

    def gaps(self) -> list[tuple[str, float]]:
        """Idle intervals between ops on each device (and before the first
        and after the last within the window), each named by the host span
        overlapping most of it ("" if none)."""
        out = []
        for d in self.devices:
            edges = [self.window[0]]
            for s, e in self.busy(d):
                edges += [s, e]
            edges.append(self.window[1])
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    out.append((self._host_over(s, e), e - s))
        return sorted(out, key=lambda g: -g[1])

    def _host_over(self, s: float, e: float) -> str:
        """The most specific host span open over the gap: the shortest one
        that covers at least half of it, else the one overlapping most."""
        over = [(min(e, h.end) - max(s, h.start), h) for h in self.host]
        over = [(o, h) for o, h in over if o > 0]
        if not over:
            return ""
        half = [h for o, h in over if o >= 0.5 * (e - s)]
        if half:
            return min(half, key=lambda h: h.end - h.start).name
        return max(over, key=lambda oh: oh[0])[1].name

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.by_op().items(), key=lambda x: -x[1])[:n]
        gaps: dict[str, float] = {}
        for name, sec in self.gaps():
            gaps[name or "no host span"] = gaps.get(name or "no host span",
                                                    0.0) + sec
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                    key=lambda x: -x[1])[:n]}


def reduce_file(path: str, chips: int,
                scopes: dict[str, str] | None = None) -> Reduction:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    scopes = scopes or {}
    devices: dict[int, list[Event]] = {}
    host: list[Event] = []
    launched: dict[str, float] = {}     # run_id -> host enqueue time
    started: dict[str, float] = {}      # run_id -> device program start
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) < chips:
            evs = devices.setdefault(int(m.group(1)), [])
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if line.name == OPS_LINE:
                        name = ev.name.split(" = ", 1)[0].lstrip("%")
                        evs.append(Event(name, s, s + ev.duration_ns * 1e-9,
                                         scopes.get(name, "")))
                    elif line.name == "XLA Modules":
                        run = _stat(ev, "run_id")
                        started[run] = min(started.get(run, s), s)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    if ev.name.startswith(HOST_PREFIXES):
                        host.append(Event(ev.name, s,
                                          s + ev.duration_ns * 1e-9))
                    elif ev.name == "DoEnqueueProgram":
                        launched[_stat(ev, "run_id")] = s
    shift = max([launched[r] - started[r] for r in started
                 if r in launched] or [0.0])
    spans = [(e.start, e.end) for e in host if e.name.startswith("bench.")]
    for d, evs in devices.items():
        evs = _innermost([dataclasses.replace(e, start=e.start + shift,
                                              end=e.end + shift)
                          for e in evs])
        devices[d] = evs
        spans = spans or [(e.start, e.end) for e in evs]
    window = (min(s for s, _ in spans), max(e for _, e in spans)) \
        if spans else (0.0, 0.0)
    for d in devices:
        devices[d] = [dataclasses.replace(e, start=max(e.start, window[0]),
                                          end=min(e.end, window[1]))
                      for e in devices[d]
                      if e.end > window[0] and e.start < window[1]]
    return Reduction(devices, host, window)


def reduce_dir(directory: str, chips: int,
               scopes: dict[str, str] | None = None) -> Reduction:
    files = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(files)}")
    return reduce_file(files[0], chips, scopes)
