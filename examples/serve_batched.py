"""Serving example: continuous batching with the real pjit'd decode step.

A tiny dense model serves 8 requests of different prompt/output lengths
through 4 decode slots: finished rows free immediately and queued requests
splice in (per-row prefill → batched cache), so the decode step never idles
— the serving analogue of the scheduler's backfilling.

    PYTHONPATH=src python examples/serve_batched.py
"""

import time

import jax
import numpy as np

from repro import configs
from repro.launch.mesh import make_mesh
from repro.models import model as M
from repro.parallel import sharding as shd
from repro.serve.engine import ServeEngine


def main() -> None:
    cfg = configs.get_smoke("granite-8b").replace(dtype="float32")
    mesh = make_mesh(jax.devices(), (-1, 1))
    rules = shd.make_rules(multi_pod=False)
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    engine = ServeEngine(cfg, mesh, rules, params, max_batch=4, max_len=96)
    rng = np.random.default_rng(0)
    for i in range(8):
        plen = int(rng.integers(4, 40))
        prompt = rng.integers(0, cfg.vocab_size, plen).tolist()
        engine.submit(prompt, max_new_tokens=int(rng.integers(4, 24)))

    t0 = time.perf_counter()
    done = engine.run(max_steps=500)
    dt = time.perf_counter() - t0

    total_new = sum(len(r.generated) for r in done)
    print(f"{len(done)} requests, {total_new} tokens generated in "
          f"{engine.steps_run} decode steps ({dt:.1f}s wall)")
    print(f"slot efficiency: {total_new / (engine.steps_run * 4):.1%} "
          f"(continuous batching keeps slots busy)")
    for r in done:
        print(f"  req {r.rid}: prompt {len(r.prompt):>2} tok → "
              f"generated {len(r.generated):>2} tok: {r.generated[:8]}…")


if __name__ == "__main__":
    main()
