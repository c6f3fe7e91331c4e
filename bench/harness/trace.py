"""Reduction of a profiler trace to the numbers the metrics read.

A trace is read with `jax.profiler.ProfileData` into two lists on one
clock: the device's operations (the "XLA Ops" line of every TPU plane,
each tagged with the program of the "XLA Modules" line that was running
it) and the benchmark's own host spans (`bench.<name>` annotations).
From them: the device-busy time (the union of operation intervals inside
the window, averaged over the chips), the idle gaps attributed to the
host span that was open at each gap's midpoint, and the device time of
the operations of a given name.

On a TPU v5e under JAX 0.9 an op event's name is its whole HLO
instruction ("%simplex_project.2 = f32[...] custom-call(...)") and it
carries no name-scope metadata; an op is known by the instruction name
before " = ".  A Pallas kernel's custom call takes the name of the
jitted function around it (`simplex_project`).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."


@dataclasses.dataclass
class Op:
    device: str
    start: float          # ns
    dur: float            # ns
    name: str             # HLO instruction name, e.g. "simplex_project.2"
    module: str           # the program running it, e.g. "jit__sgp_step_flows_impl"


@dataclasses.dataclass
class Span:
    start: float
    dur: float
    name: str


@dataclasses.dataclass
class Trace:
    ops: list
    spans: list

    @property
    def devices(self):
        return sorted({o.device for o in self.ops})

    def window(self):
        """(start, end) ns of the `bench.window` span."""
        w = [s for s in self.spans if s.name == "window"]
        if not w:
            raise ValueError("trace holds no bench.window span")
        return w[0].start, w[0].start + w[0].dur


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"{len(found)} xplane files under {log_dir}")
    return found[0]


def op_name(event_name: str) -> str:
    """"%fusion.50 = f32[...] fusion(...)" -> "fusion.50"."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def module_name(event_name: str) -> str:
    """"jit__sgp_step_flows_impl(98815...)" -> "jit__sgp_step_flows_impl"."""
    return event_name.split("(", 1)[0]


def _tag_modules(ops, modules):
    """Set each op's module to the program whose interval holds its start
    (both lists of one device, sorted by start)."""
    j = 0
    for o in ops:
        while j + 1 < len(modules) and modules[j + 1][0] <= o.start:
            j += 1
        if modules and modules[j][0] <= o.start < modules[j][1]:
            o.module = modules[j][2]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            mods, dev_ops = [], []
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                                   module_name(e.name)) for e in line.events)
                elif line.name == OPS_LINE:
                    dev_ops = [Op(plane.name, e.start_ns, e.duration_ns,
                                  op_name(e.name), "") for e in line.events]
            dev_ops.sort(key=lambda o: o.start)
            _tag_modules(dev_ops, mods)
            ops.extend(dev_ops)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(Span(e.start_ns, e.duration_ns,
                                          e.name[len(SPAN_PREFIX):]))
    return Trace(ops, spans)


def merged(intervals):
    """Union of [start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_intervals(trace: Trace, device: str):
    t0, t1 = trace.window()
    return merged((max(o.start, t0), min(o.start + o.dur, t1))
                  for o in trace.ops if o.device == device
                  and o.start < t1 and o.start + o.dur > t0)


def busy_s(trace: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    devs = trace.devices
    if not devs:
        return 0.0
    tot = sum(e - s for d in devs for s, e in busy_intervals(trace, d))
    return tot / len(devs) / 1e9


def window_s(trace: Trace) -> float:
    t0, t1 = trace.window()
    return (t1 - t0) / 1e9


def idle_by_span(trace: Trace, device: str | None = None):
    """Idle seconds of one chip inside the window, by the innermost
    benchmark span open at each gap's midpoint ("none" when only the
    window span was)."""
    device = device or trace.devices[0]
    t0, t1 = trace.window()
    busy = busy_intervals(trace, device)
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    inner = sorted((s for s in trace.spans if s.name != "window"),
                   key=lambda s: s.start)
    starts = [s.start for s in inner]
    out = defaultdict(float)
    for gs, ge in gaps:
        mid = 0.5 * (gs + ge)
        name = "none"
        best = None
        i = bisect.bisect_right(starts, mid)
        for sp in inner[max(0, i - 64):i]:
            if sp.start <= mid < sp.start + sp.dur:
                if best is None or sp.dur < best.dur:
                    best = sp
        if best is not None:
            name = best.name
        out[name] += (ge - gs) / 1e9
    return dict(out)


def named_time_s(trace: Trace, name: str) -> float:
    """Device seconds of the window's operations called `name` (or
    `name.<n>`), averaged over the chips (0.0 when there is none)."""
    t0, t1 = trace.window()
    tot = sum(o.dur for o in trace.ops if t0 <= o.start < t1
              and (o.name == name or o.name.startswith(name + ".")))
    return tot / max(len(trace.devices), 1) / 1e9


def top_ops(trace: Trace, n: int = 10):
    """[[module/op, seconds]] of the window's costliest operations."""
    t0, t1 = trace.window()
    agg = defaultdict(float)
    for o in trace.ops:
        if t0 <= o.start < t1:
            agg[f"{o.module}/{o.name}" if o.module else o.name] += o.dur
    devs = max(len(trace.devices), 1)
    return [[k, v / devs / 1e9]
            for k, v in sorted(agg.items(), key=lambda kv: -kv[1])[:n]]
