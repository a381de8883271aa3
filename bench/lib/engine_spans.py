"""The serving engine's spans, reduced.

The records are those of a ``repro.spans.Spans`` handed to ``ServeEngine``:
``serve.step`` around each step, ``serve.prefill``, ``serve.decode`` and
``serve.sync`` inside it, ``serve.queue`` for each request's wait, and
``host.gc`` for each garbage collection. ``summary`` gives, for the records
of the measured window, each span name's count, total and self seconds, the
longest steps with what ran inside them, and four per-layer numbers:

- ``queue_wait_p95_ms.serve``: 95th percentile of ``serve.queue`` over the
  requests admitted in the window;
- ``prefill_ms.serve``: mean ``serve.prefill``;
- ``engine_host_ms.serve``: mean, per ``serve.step``, of its duration less
  the ``serve.sync`` spans inside it, the host's own time in a step;
- ``engine_idle_share.serve``: device idle time inside ``serve.step``
  annotations over the traced window, in percent (with a trace only).

``idle_by_span`` reduces a profiler trace's planes, as ``bench.lib.trace``
does, to the device's idle seconds in the traced window put down to the
innermost engine span on the host plane around each stretch of them.
"""

from __future__ import annotations

import bisect

import numpy as np

from bench.lib.trace import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                             WINDOW_SPAN, gaps, merge, overlap, total)

ENGINE = ("serve.", "host.gc")
OUTSIDE = "outside the engine"
STEP = "serve.step"
LONG_STEP_S = 0.25
LONGEST = 5


# ------------------------------------------------------------------ records
def self_times(records) -> list[float]:
    """Each record's duration less that of the records directly inside it
    (those naming it as ``parent``)."""
    parents = {r.parent for r in records}
    out = [r.t1 - r.t0 for r in records]
    order = sorted((i for i, r in enumerate(records)
                    if r.parent is not None or r.name in parents),
                   key=lambda i: (records[i].t0, -records[i].t1))
    stack: list[int] = []
    for i in order:
        r = records[i]
        while stack and records[stack[-1]].t1 <= r.t0:
            stack.pop()
        if stack and r.parent == records[stack[-1]].name:
            out[stack[-1]] -= r.t1 - r.t0
        stack.append(i)
    return out


def _within_steps(steps, records) -> list[dict[str, float]]:
    """For each of ``steps`` (sorted by start, none overlapping), the
    seconds of each span name nested inside it."""
    starts = [r.t0 for r in steps]
    out: list[dict[str, float]] = [{} for _ in steps]
    for r in records:
        i = bisect.bisect_right(starts, r.t0) - 1
        if r.parent is None or r.name == STEP or i < 0 or r.t1 > steps[i].t1:
            continue
        out[i][r.name] = out[i].get(r.name, 0.0) + r.t1 - r.t0
    return out


def summary(records, lo: float, hi: float, counters: dict,
            idle: dict | None = None) -> dict:
    """What the records of [lo, hi] say: per span name ``count``,
    ``total_s`` and ``self_s``; the engine's ``counters``; the ``longest``
    steps, each with the seconds of every span name inside it; how many
    steps took over ``LONG_STEP_S``; and the per-layer ``metrics``."""
    recs = [r for r in records if r.t0 >= lo and r.t1 <= hi]
    spans: dict[str, dict] = {}
    for r, own in zip(recs, self_times(recs)):
        s = spans.setdefault(r.name, {"count": 0, "total_s": 0.0,
                                      "self_s": 0.0})
        s["count"] += 1
        s["total_s"] += r.t1 - r.t0
        s["self_s"] += own
    steps = sorted((r for r in recs if r.name == STEP), key=lambda r: r.t0)
    within = _within_steps(steps, recs)
    host = [r.t1 - r.t0 - w.get("serve.sync", 0.0)
            for r, w in zip(steps, within)]
    longest = [{"t_s": r.t0 - lo, "s": r.t1 - r.t0, "within": w}
               for r, w in sorted(zip(steps, within),
                                  key=lambda rw: rw[0].t0 - rw[0].t1)
               [:LONGEST]]
    queue = [r.t1 - r.t0 for r in recs if r.name == "serve.queue"]
    prefill = [r.t1 - r.t0 for r in recs if r.name == "serve.prefill"]
    metrics = {}
    if queue:
        metrics["queue_wait_p95_ms.serve"] = 1e3 * float(
            np.percentile(queue, 95))
    if prefill:
        metrics["prefill_ms.serve"] = 1e3 * float(np.mean(prefill))
    if host:
        metrics["engine_host_ms.serve"] = 1e3 * float(np.mean(host))
    if idle is not None:
        metrics["engine_idle_share.serve"] = \
            100.0 * idle["in_step_s"] / idle["window_s"]
    return {"spans": spans, "counters": counters, "longest": longest,
            "long_steps": sum(r.t1 - r.t0 > LONG_STEP_S for r in steps),
            "metrics": metrics, "idle": idle}


# -------------------------------------------------------------------- trace
def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    for ev in line.events]
    return []


def _innermost(spans, lo: float, hi: float) -> str:
    """The span covering all of [lo, hi] that started last (the shorter
    on a tie), or ``OUTSIDE``."""
    best = max(((s, s - e, name) for name, s, e in spans
                if s <= lo and e >= hi), default=None)
    return OUTSIDE if best is None else best[2]


def idle_by_span(planes) -> dict | None:
    """Per device, averaged over the devices: the traced window's length,
    its idle seconds, those seconds by the innermost engine span around
    each stretch (``by_span``, summing to ``idle_s``), and those inside a
    ``serve.step`` (``in_step_s``). None where no device op is traced."""
    planes = list(planes)
    spans, window = [], None
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = (ev.start_ns + ev.duration_ns) * 1e-9
                if ev.name == WINDOW_SPAN and window is None:
                    window = (s, e)
                elif ev.name.startswith(ENGINE):
                    spans.append((ev.name, s, e))
    devices = []
    for plane in planes:
        if DEVICE_PLANE.match(plane.name):
            ops = _events(plane, OPS_LINE) or _events(plane, MODULES_LINE)
            if ops:
                devices.append(ops)
    if not devices:
        return None
    lo, hi = window or (min(s for ops in devices for s, _ in ops),
                        max(e for ops in devices for _, e in ops))
    steps = merge((s, e) for name, s, e in spans if name == STEP)
    idle_s = in_step = 0.0
    by_span: dict[str, float] = {}
    for ops in devices:
        idle = gaps(merge((max(s, lo), min(e, hi)) for s, e in ops
                          if e > lo and s < hi), lo, hi)
        idle_s += total(idle)
        in_step += overlap(idle, steps)
        for a, b in idle:
            near = [sp for sp in spans if sp[1] < b and sp[2] > a]
            cuts = sorted({a, b, *(t for _, s, e in near for t in (s, e)
                                   if a < t < b)})
            for x, y in zip(cuts, cuts[1:]):
                label = _innermost(near, x, y)
                by_span[label] = by_span.get(label, 0.0) + y - x
    n = len(devices)
    return {"window_s": hi - lo, "idle_s": idle_s / n,
            "in_step_s": in_step / n,
            "by_span": {k: v / n for k, v in sorted(by_span.items(),
                                                    key=lambda kv: -kv[1])}}
