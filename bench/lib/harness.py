"""What every driver and metric reader shares: the cell being run, its
configuration, traffic and reference module found by name, host spans,
compile events, peak memory, the checks that decide ``correct``, and the
profiler window.

Nothing here touches JAX at import time; ``run.py`` sets the compile cache
and checks the device before a driver imports the program.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
OUT_DIR = os.path.join(ROOT, ".bench_out")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import a file by path (names may hold '-' and '.')."""
    name = "bench_" + os.path.relpath(path, BENCH).replace(os.sep, "_") \
        .replace("-", "_").replace(".", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def merged(base: dict, tiny: bool) -> dict:
    """``base`` with its ``tiny`` group laid over it (nested groups merge)."""
    out = {k: v for k, v in base.items() if k != "tiny"}
    if tiny:
        for k, v in base.get("tiny", {}).items():
            out[k] = {**out[k], **v} if isinstance(v, dict) and \
                isinstance(out.get(k), dict) else v
    return out


class Spans:
    """Host spans kept in memory, and written into the profiler's trace
    while one is being taken (``jax.profiler.TraceAnnotation``)."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = None
        if self.tracing:
            import jax
            ann = jax.profiler.TraceAnnotation(name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str, lo: float, hi: float) -> list[float]:
        return [e - s for n, s, e in self.records
                if n == name and s >= lo and e <= hi]


class CompileLog:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events, each stamped with the host clock. A cache hit still
    reports a backend compile: the time to load the executable."""

    def __init__(self):
        import jax

        self.compiles: list[tuple[float, float]] = []
        self.hits: list[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((time.perf_counter(), secs))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.perf_counter())

    def between(self, lo: float, hi: float) -> tuple[int, float, int]:
        """(compiles, their seconds, cache hits) stamped in [lo, hi]."""
        inside = [s for t, s in self.compiles if lo <= t <= hi]
        return (len(inside), sum(inside),
                sum(1 for t in self.hits if lo <= t <= hi))


def peak_bytes(devices) -> int | None:
    """Peak bytes on the fullest device: arrays in use plus, on TPU, the
    space reserved for compiled programs' temporaries, which
    ``peak_bytes_in_use`` leaves out."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(stats["peak_bytes_in_use"]
                         + stats.get("peak_bytes_reserved", 0))
    return max(peaks) if peaks else None


@dataclass
class Cell:
    """One run of one cell: what it was given, what it measured, and the
    numbers that decide ``correct``."""
    name: str
    config: dict
    model: object                 # the configuration's reference module
    traffic: dict
    chips: int
    seed: int
    seconds: float
    trace: bool
    tiny: bool
    devices: list
    device: dict
    t0: float                     # process start, host clock
    spans: Spans = field(default_factory=Spans)
    facts: dict = field(default_factory=dict)
    checks: dict = field(default_factory=dict)
    attempted: int = 0
    failed: int = 0
    trace_facts: object = None
    peaks: dict | None = None
    compile_log: CompileLog | None = None
    _trace_dir: str | None = None
    _window_ann: object = None

    # ------------------------------------------------------------- output
    def tag(self) -> str:
        d = self.device
        return f"[{d['platform']} {d['kind']} x{d['count']}]"

    def log(self, msg: str) -> None:
        print(f"{self.tag()} {self.name}: {msg}", file=sys.stderr, flush=True)

    def emit(self, obj: dict) -> None:
        print(json.dumps({**obj, "device": self.device}), flush=True)

    # ------------------------------------------------------------- checks
    def check(self, name: str, value: float) -> None:
        """Record one compared number beside its limit (pass: value <=
        limit)."""
        self.checks[name] = {"value": value,
                             "limit": self.traffic["limits"][name]}

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in self.checks.values())

    # ------------------------------------------------------------ profiler
    def trace_start(self) -> None:
        if not self.trace:
            return
        import jax
        import shutil

        self._trace_dir = os.path.join(OUT_DIR, "trace-" + self.name)
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self._trace_dir)
        self.spans.tracing = True
        self._window_ann = jax.profiler.TraceAnnotation("bench.window")
        self._window_ann.__enter__()
        self.facts["trace_start"] = time.perf_counter()

    def trace_stop(self) -> None:
        if self._window_ann is None:
            return
        import jax

        self._window_ann.__exit__(None, None, None)
        self._window_ann = None
        self.facts["trace_stop"] = time.perf_counter()
        self.spans.tracing = False
        jax.profiler.stop_trace()

    @property
    def trace_due(self) -> bool:
        return self._window_ann is not None and time.perf_counter() >= \
            self.facts["trace_start"] + self.traffic["trace_seconds"]
