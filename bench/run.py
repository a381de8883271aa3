#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``; everything the
run needs is found by name from it: the configuration file and its
reference module (``bench/configs/<config>.json`` and ``.py``), the traffic
mix (``bench/traffic/<traffic>.json``), whose ``driver`` names the driver
(``bench/drivers/<driver>.py``), and one reader per metric
(``bench/metrics/<metric>.py``). With ``--trace 0`` the result carries the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics, read
from a profiler trace of the window's first ``trace_seconds``.

The run needs a TPU with at least the cell's chips and exits non-zero,
printing no result, without one. ``--tiny`` runs the same path at the
configuration's and traffic's ``tiny`` sizes on whatever JAX finds: a
rehearsal for the CPU, never a measurement.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (platform, kind, count,
peak memory; with a trace also ``busy_s`` and ``window_s``), with a trace
``breakdown``, and last ``checks``: each compared number beside its limit,
which the last lines of standard error repeat.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="rehearse at tiny sizes on any backend")
    return ap.parse_args(argv)


def fail(msg: str, code: int = 2):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def main(argv=None, t0: float = T0) -> dict:
    args = parse(argv)
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        fail(f"no workload {args.workload!r}; have {sorted(cells)}")
    w = cells[args.workload]
    entry = next(c for c in bench["configs"] if c["name"] == w["config"])

    # the compile cache lives at one fixed path inside the checkout, unless
    # the environment names one; set before JAX is imported
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax

    import repro  # noqa: F401  (the system under test must be present)
    from bench.lib import harness, trace

    devices = jax.devices()
    dev = devices[0]
    if (dev.platform != "tpu" and not args.tiny) or len(devices) < w["chips"]:
        fail(f"{args.workload} needs {w['chips']} TPU chip(s); JAX finds "
             f"{len(devices)} {dev.platform} device(s)", 3)
    peaks = harness.load_json(os.path.join(BENCH, "peaks.json"))
    if dev.platform == "tpu" and dev.device_kind not in peaks:
        fail(f"no peaks for device kind {dev.device_kind!r}", 3)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    cfg_path = os.path.join(ROOT, entry["file"])
    traffic = harness.load_json(os.path.join(BENCH, "traffic",
                                             w["traffic"] + ".json"))
    cell = harness.Cell(
        name=args.workload,
        config=harness.merged(harness.load_json(cfg_path), args.tiny),
        model=harness.load_module(cfg_path[:-len(".json")] + ".py"),
        traffic=harness.merged(traffic, args.tiny),
        chips=w["chips"], seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), tiny=args.tiny,
        devices=devices[:w["chips"]],
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": w["chips"]},
        t0=t0, peaks=peaks.get(dev.device_kind))
    cell.compile_log = harness.CompileLog()
    cell.emit({"phase": "start", "workload": cell.name, "seed": args.seed,
               "seconds": args.seconds, "trace": args.trace,
               "jax": jax.__version__})

    driver = harness.load_module(os.path.join(
        BENCH, "drivers", cell.traffic["driver"] + ".py"))
    try:
        driver.run(cell)
        if cell.trace and cell._trace_dir:
            cell.trace_facts = trace.reduce_dir(cell._trace_dir)
    finally:
        if cell._trace_dir:
            shutil.rmtree(cell._trace_dir, ignore_errors=True)

    kind = "per_layer" if cell.trace else "end_to_end"
    metrics = {}
    for m in bench[kind]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        reader = harness.load_module(os.path.join(BENCH, "metrics",
                                                  m["name"] + ".py"))
        value = reader.read(cell)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {**cell.device, "memory_peak_bytes":
              cell.facts.get("memory_peak_bytes")}
    result = {"correct": cell.correct, "attempted": cell.attempted,
              "failed": cell.failed, "metrics": metrics, "device": device}
    tf = cell.trace_facts
    if tf is not None:
        device.update(busy_s=tf.busy_s, window_s=tf.window_s)
        result["breakdown"] = {"device_ops": tf.top_ops,
                               "idle_gaps": tf.idle_gaps}
    result["checks"] = cell.checks
    for name, c in cell.checks.items():
        cell.log(f"check {name} = {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
