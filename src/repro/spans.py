"""The program's span recorder.

A span is one stretch of host work, kept as ``Record(name, t0, t1, parent,
attr)`` on ``time.perf_counter``: ``parent`` names the span that was open
around it, and ``attr`` is the request it served (for ``host.gc``, the
generation collected). Records go into a bounded deque, so a long-lived
process keeps the newest ``maxlen`` and no more.

Each span is also a ``jax.profiler.TraceAnnotation`` of the same name: while
a profile is being taken it lands on the host plane, on the clock of the
device's ``XLA Ops``. Until the recorder is closed it also records each
garbage collection of the interpreter as ``host.gc``.

Nothing records unless a caller makes a recorder and hands it in. JAX is
imported when a recorder is made, not when this module is. The recorder
serves one thread.
"""

from __future__ import annotations

import contextlib
import gc
import time
from collections import deque
from typing import NamedTuple

__all__ = ["Record", "Spans"]


class Record(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: str | None
    attr: int | None


class Spans:
    def __init__(self, maxlen: int = 100_000):
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.records: deque[Record] = deque(maxlen=maxlen)
        self._open: list[str] = []
        self._gc: tuple | None = None
        gc.callbacks.append(self._on_gc)

    @contextlib.contextmanager
    def __call__(self, name: str, attr: int | None = None):
        """Record the body as span ``name``, inside the span open around it."""
        parent = self._open[-1] if self._open else None
        self._open.append(name)
        with self._annotation(name):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                t1 = time.perf_counter()
                self._open.pop()
                self.records.append(Record(name, t0, t1, parent, attr))

    def add(self, name: str, t0: float, t1: float,
            attr: int | None = None) -> None:
        """Record a span whose start lies in the past, such as a wait; it
        has no parent and stays out of the profiler's trace."""
        self.records.append(Record(name, t0, t1, None, attr))

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            ann = self._annotation("host.gc")
            ann.__enter__()
            self._gc = (time.perf_counter(), ann)
        elif self._gc is not None:
            t0, ann = self._gc
            t1 = time.perf_counter()
            ann.__exit__(None, None, None)
            self._gc = None
            self.records.append(Record("host.gc", t0, t1, self._open[-1]
                                       if self._open else None,
                                       info["generation"]))

    def close(self) -> None:
        """Stop recording garbage collections."""
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def __enter__(self) -> Spans:
        return self

    def __exit__(self, *exc) -> None:
        self.close()
