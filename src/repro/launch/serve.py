"""Serving launcher — continuous batching over a persistent sharded cache.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b \
        --requests 12 --max-batch 4 --max-new 16

On CPU this serves the reduced smoke config of any assigned architecture;
on TPU the same entry point takes ``--full``, and ``--tp2d`` selects the
serving rules (resident 2-D-sharded weights, so a decode step gathers no
weights).
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import configs
from repro.launch.cache import configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="granite-8b",
                    choices=configs.ARCHS + ["tiny"])
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--tp2d", action="store_true",
                    help="serving rule set (resident 2-D-sharded weights)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = configs.get(args.arch) if args.full else configs.get_smoke(args.arch)
    if jax.default_backend() == "cpu":
        cfg = cfg.replace(dtype="float32")
    mesh = make_mesh(jax.devices(), (-1, 1))
    rules = shd.make_rules(multi_pod=False, tp2d=args.tp2d)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    engine = ServeEngine(cfg, mesh, rules, params,
                         max_batch=args.max_batch, max_len=args.max_len)

    rng = np.random.default_rng(args.seed)
    with mesh:
        for _ in range(args.requests):
            plen = int(rng.integers(4, args.max_len // 3))
            engine.submit(rng.integers(0, cfg.vocab_size, plen).tolist(),
                          max_new_tokens=int(rng.integers(2, args.max_new)))
        t0 = time.perf_counter()
        done = engine.run()
        dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in done)
    c = engine.counters
    print(f"arch={cfg.name} served {len(done)} requests, {total} tokens in "
          f"{c.steps} steps ({dt:.1f}s)")
    print(f"slot efficiency "
          f"{c.slot_steps_active / (c.steps * args.max_batch):.1%}, "
          f"{c.overlapped_steps} decodes dispatched over an unread one, "
          f"{c.discarded_tokens} tokens of finished rows dropped")


if __name__ == "__main__":
    main()
