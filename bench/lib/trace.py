"""Reduce a profiler trace to device busy time, collective exposure, top
device ops and idle gaps attributed to the benchmark's host spans.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData``. Device planes are ``/device:TPU:<n>``;
each carries an ``XLA Ops`` line (one event per executed HLO op) and an
``XLA Modules`` line (one event per executed program). Host spans are the
events named ``bench.*`` that ``jax.profiler.TraceAnnotation`` writes on the
host plane; ``bench.window`` marks the traced window itself.

Every quantity per device is averaged over the device planes found.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.IGNORECASE)
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
TOP = 10


# ------------------------------------------------------------ interval math
def merge(intervals) -> list[tuple[float, float]]:
    """Sorted union of (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(merged) -> float:
    return sum(e - s for s, e in merged)


def overlap(a, b) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(merged, lo: float, hi: float) -> list[tuple[float, float]]:
    """The complement of ``merged`` inside [lo, hi]."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


# ------------------------------------------------------------ the reduction
@dataclass
class TraceFacts:
    window_s: float
    devices: int
    busy_s: float                       # mean over devices
    collective_s: float = 0.0           # mean over devices
    collective_exposed_s: float = 0.0   # collective time with no compute
    modules: dict = field(default_factory=dict)   # name -> [count, seconds]
    top_ops: list = field(default_factory=list)   # [[name, seconds], ...]
    idle_gaps: list = field(default_factory=list)  # [[span, seconds], ...]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module(self, pattern: str) -> tuple[float, float]:
        """(calls, seconds) per device of every program whose name holds
        ``pattern``."""
        n = s = 0.0
        for name, (count, secs) in self.modules.items():
            if pattern in name:
                n += count
                s += secs
        return n, s


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def _op_name(name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def _host_spans(planes) -> list[tuple[str, float, float]]:
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    spans.append((ev.name, ev.start_ns * 1e-9,
                                  (ev.start_ns + ev.duration_ns) * 1e-9))
    return spans


def _label(gap, spans) -> str:
    """The span that covers most of the gap; the shorter one on a tie."""
    best, best_key = "no bench span", (0.0, 0.0)
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > 0 and (ov, -(e - s)) > best_key:
            best, best_key = name, (ov, -(e - s))
    return best


def reduce_planes(planes) -> TraceFacts | None:
    """Reduce the planes of one trace; None where no device op is in it."""
    planes = list(planes)
    spans = _host_spans(planes)
    devices = []
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {line.name: line for line in plane.lines}
        ops = [(ev.name, ev.start_ns * 1e-9,
                (ev.start_ns + ev.duration_ns) * 1e-9)
               for ev in (lines[OPS_LINE].events if OPS_LINE in lines else ())]
        mods = [(ev.name, ev.start_ns * 1e-9,
                 (ev.start_ns + ev.duration_ns) * 1e-9)
                for ev in (lines[MODULES_LINE].events
                           if MODULES_LINE in lines else ())]
        if ops or mods:
            devices.append((int(m.group(1)), ops or mods, mods))
    if not devices:
        return None
    devices.sort()
    win = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for _, ops, _ in devices for _, s, _ in ops)
        hi = max(e for _, ops, _ in devices for _, _, e in ops)
    n = len(devices)
    busy = coll = exposed = 0.0
    modules: dict[str, list[float]] = {}
    op_time: dict[str, float] = {}
    for idx, ops, mods in devices:
        inside = [(name, max(s, lo), min(e, hi)) for name, s, e in ops
                  if e > lo and s < hi]
        merged = merge((s, e) for _, s, e in inside)
        busy += total(merged)
        c = merge((s, e) for name, s, e in inside if COLLECTIVE.search(name))
        other = merge((s, e) for name, s, e in inside
                      if not COLLECTIVE.search(name))
        coll += total(c)
        exposed += total(c) - overlap(c, other)
        for name, s, e in inside:
            op = _op_name(name)
            op_time[op] = op_time.get(op, 0.0) + (e - s)
        for name, s, e in mods:
            if s >= lo and e <= hi:
                rec = modules.setdefault(_module_name(name), [0.0, 0.0])
                rec[0] += 1
                rec[1] += e - s
        if idx == devices[0][0]:
            idle = sorted(gaps(merged, lo, hi), key=lambda g: g[0] - g[1])
            idle_gaps = [[_label(g, spans), g[1] - g[0]] for g in idle[:TOP]]
    top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    return TraceFacts(
        window_s=hi - lo, devices=n, busy_s=busy / n,
        collective_s=coll / n, collective_exposed_s=exposed / n,
        modules={k: [v[0] / n, v[1] / n] for k, v in modules.items()},
        top_ops=[[k, v / n] for k, v in top], idle_gaps=idle_gaps)


def newest_xplane(trace_dir: str) -> str | None:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def reduce_dir(trace_dir: str) -> TraceFacts | None:
    from jax.profiler import ProfileData

    path = newest_xplane(trace_dir)
    if path is None:
        return None
    return reduce_planes(ProfileData.from_file(path).planes)
