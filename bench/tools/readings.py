#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/tools/readings.py --workload <cell> --seeds 1,2,3 \\
        [--seconds 20] [--tiny]

A whole run of the serving cell per seed (``--seconds`` long), in one
process, whose checked sample is also read by the control: the reference
one precision below the configuration's bfloat16 (every matrix product's
operands and every activation rounded to fp8 e4m3, one scale per tensor),
which at each position of the same prompts and served tokens reads the
reference gap of the token the fp8 forward puts first. Each reading is one
JSON line; the benchmark's runs never run this.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), BENCH]
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    import run as bench_run
    from bench.lib import harness

    drv = harness.load_module(os.path.join(BENCH, "drivers", "serve.py"))
    drv.CONTROL = True
    for seed in seeds:
        bench_run.main(["--workload", args.workload, "--seed", str(seed),
                        "--seconds", str(args.seconds)]
                       + (["--tiny"] if args.tiny else []))


if __name__ == "__main__":
    main()
