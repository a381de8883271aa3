"""Training launcher — the end-to-end driver behind ``--arch <id>``.

    PYTHONPATH=src python -m repro.launch.train --arch tiny --steps 200 \
        --global-batch 8 --seq-len 128 --ckpt-dir /tmp/tiny_run

On a CPU it trains the reduced/smoke config of any assigned architecture
(or the full ``tiny`` ~100M config); on a TPU slice the same entry point
takes ``--full`` and ``--model-parallel``. Checkpoint/restart: re-running
with the same --ckpt-dir resumes from the latest step (kill it mid-run to
test).
"""

from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.launch.cache import configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
from repro.train.loop import train_loop
from repro.train.optimizer import OptConfig


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="tiny",
                    choices=configs.ARCHS + ["tiny"])
    ap.add_argument("--full", action="store_true",
                    help="full published config (TPU); default is the "
                         "reduced smoke config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fsdp", action="store_true")
    args = ap.parse_args(argv)
    configure_compile_cache()

    cfg = configs.get(args.arch) if (args.full or args.arch == "tiny") \
        else configs.get_smoke(args.arch)
    if jax.default_backend() == "cpu":
        cfg = cfg.replace(dtype="float32")
    mesh = make_mesh(jax.devices(), (-1, args.model_parallel))
    rules = shd.make_rules(multi_pod=False, fsdp=args.fsdp)
    print(f"arch={cfg.name} params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)} backend={jax.default_backend()}")

    def log(step, m):
        print(f"step {step:5d}  loss {m['loss']:.4f}  "
              f"gnorm {m['grad_norm']:.3f}  {m['sec_per_step']:.3f}s/step")

    result = train_loop(
        cfg, mesh, rules, steps=args.steps, global_batch=args.global_batch,
        seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, seed=args.seed,
        opt=OptConfig(lr=args.lr), microbatches=args.microbatches,
        on_metrics=log)
    print(f"status={result.status} final_step={result.step} "
          f"final_loss={result.metrics.get('loss', float('nan')):.4f}")
    first = result.history[0]["loss"] if result.history else float("nan")
    last = result.metrics.get("loss", float("nan"))
    print(f"loss {first:.4f} -> {last:.4f}")


if __name__ == "__main__":
    main()
