#!/usr/bin/env python3
"""One run of a serving cell with a span recorder handed to its engine.

    python3 bench/tools/spans.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1> [--tiny]

Runs ``bench/run.py`` with the same arguments, in this process, with a
``repro.spans.Spans`` passed to the ``ServeEngine`` the driver makes, and
prints its result line as usual. One ``{"phase": "spans"}`` line follows:
what ``bench.lib.engine_spans.summary`` makes of the window's records and
the engine's counters, with ``--trace 1`` also the traced window's device
idle time by the innermost engine span around it. The benchmark's own runs
never run this; with ``--trace 0`` it measures what the recorder costs.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> None:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), BENCH]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import run as bench_run
    from bench.lib import engine_spans, harness, trace
    from repro.serve import engine as serve_engine
    from repro.spans import Spans

    got: dict = {}

    class Recorded(serve_engine.ServeEngine):
        def __init__(self, *args, **kwargs):
            got["spans"] = Spans()
            super().__init__(*args, spans=got["spans"], **kwargs)
            got["counters"] = self.counters

    class Kept(harness.Cell):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            got["cell"] = self

    def reduce_dir(trace_dir):
        from jax.profiler import ProfileData

        path = trace.newest_xplane(trace_dir)
        if path is None:
            return None
        planes = list(ProfileData.from_file(path).planes)     # read once
        got["idle"] = engine_spans.idle_by_span(planes)
        return trace.reduce_planes(planes)

    serve_engine.ServeEngine, harness.Cell = Recorded, Kept
    trace.reduce_dir = reduce_dir
    try:
        bench_run.main(argv)
    finally:
        if "spans" in got:
            got["spans"].close()
    cell = got["cell"]
    lo = cell.facts["window_start"]
    line = engine_spans.summary(got["spans"].records, lo,
                                lo + cell.facts["window_s"],
                                dataclasses.asdict(got["counters"]),
                                got.get("idle"))
    print(json.dumps({"phase": "spans", **line, "device": cell.device}),
          flush=True)


if __name__ == "__main__":
    main()
