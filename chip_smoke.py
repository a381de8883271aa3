#!/usr/bin/env python3
"""Chip smoke test: the scheduler's JAX payload path, end to end, on a TPU.

    python chip_smoke.py             # one chip: phases (a) (b) (c)
    python chip_smoke.py --chips 4   # four chips: 4-way data parallel vs one
    JAX_PLATFORMS=cpu python chip_smoke.py --tiny              # CPU rehearsal
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python chip_smoke.py --tiny --chips 4                   # 4 CPU devices

Phases, each printing one JSON line of facts:

(a) train  — the OAR control plane places two mamba2-130m jobs at their
    published widths (``api.oarsub`` → ``MetaScheduler`` → ``Executor`` →
    ``ClusterRunner`` → ``train_loop``). The second reuses the first's
    checkpoint directory and must resume at step 4. Step-0 loss is checked
    against ``M.loss_fn`` in float32.
(b) serve  — ``ServeEngine`` on granite-8b at published widths, depth cut
    to 4 of 36 layers, answers 8 requests; one request's prefill logits
    are checked against ``M.forward`` in float32.
(c) kernels — each Pallas kernel once, compiled for the chip (never in
    interpret mode there), against its ``ref.py``.

``--chips 4`` runs only the training job of (a) on a (4, 1) data-parallel
mesh over all four devices, and the same 4 steps on one device as the
comparison. The last line of standard output is one JSON object with
``ok`` and the device as JAX reports it. Without a TPU (and without
``--tiny``) the script exits non-zero before it runs anything. Every failed
check exits non-zero; nothing is caught and continued.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, ".chip_smoke")     # git-ignored; removed at exit

# bf16 compute against an f32 reference at "highest" matmul precision.
# bf16 keeps 8 mantissa bits (unit roundoff 2**-9 ≈ 2e-3); the training
# loss is a mean over 16k tokens of log-softmax sums, so its rounding
# noise averages down: 1e-2 relative still flags a wrong model (a wrong
# layer moves the loss by whole nats), not bf16 noise.
LOSS_RTOL = 1e-2
# Prefill logits after 4 bf16 layers at d_model 4096: every matmul rounds
# its inputs to bf16, so each logit carries ~1e-2 relative error; judged
# as a relative L2 norm over the vocab so one outlier does not decide.
LOGIT_REL_L2 = 5e-2
# Kernels: bf16 inputs, f32 math inside, bf16 output; the reference runs
# in f32 on the same (upcast) inputs. Error is relative to max |ref|.
KERNEL_REL = 2e-2
# 4-way data parallel vs one device: the same bf16 math with a different
# gradient reduction order, so losses agree to bf16 noise over 4 steps.
DP_RTOL = 5e-3


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke FAILED: {msg}")


class CompileLog:
    """Backend-compile seconds (all programs, and the train step alone) and
    persistent-cache hits, from JAX's own monitoring events; ``take()``
    returns and clears what accumulated. A cache hit still reports a
    backend compile: the time to load the executable."""

    def __init__(self):
        self.secs, self.step_secs, self.hits = 0.0, 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, fun_name="", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs
            if fun_name == "jit(train_step)":
                self.step_secs += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def take(self) -> dict:
        out = {"compile_s": self.secs, "step_compile_s": self.step_secs,
               "cache_hits": self.hits}
        self.secs, self.step_secs, self.hits = 0.0, 0.0, 0
        return out


def step_times(history: list[dict]) -> dict:
    """Wall time of the first step (compile + run) and the mean of the rest;
    ``sec_per_step`` in the history is a running mean and every logged step
    ended in a device sync."""
    start = history[0]["step"]
    elapsed = [h["sec_per_step"] * (h["step"] - start + 1) for h in history]
    rest = (elapsed[-1] - elapsed[0]) / (len(elapsed) - 1)
    return {"first_step_s": elapsed[0], "sec_per_step_after_first": rest}


def peak_bytes() -> dict:
    """Device 0's peaks: arrays (in use) and, on TPU, the space reserved for
    compiled programs' temporaries, which ``peak_bytes_in_use`` leaves out."""
    stats = jax.devices()[0].memory_stats() or {}
    return {k: stats.get(k) for k in ("peak_bytes_in_use",
                                      "peak_bytes_reserved")}


# --------------------------------------------------------------- phase (a)
def train_spec(args, ckpt_dir: str | None, steps: int) -> dict:
    spec = {"kind": "train", "arch": "mamba2-130m", "smoke": args.tiny,
            "steps": steps, "global_batch": 4 if args.tiny else 8,
            "seq_len": 64 if args.tiny else 2048, "log_every": 1}
    if ckpt_dir:
        spec["ckpt_dir"] = ckpt_dir
    return spec


def run_job(spec: dict, timeout: float = 600.0):
    """Submit ``spec`` through the scheduler, tick until the job ends, and
    return its TrainResult; anything but Terminated fails."""
    from repro.core import (CentralModule, Executor, MetaScheduler,
                            SimTransport, TaktukLauncher, api, connect)
    from repro.launch.cluster import ClusterRunner

    db = connect()
    api.add_resources(db, ["host0"], weight=1)
    executor = Executor(db, launcher=TaktukLauncher(SimTransport()),
                        check_nodes=False)
    runner = ClusterRunner(db, executor)
    executor.runner = runner
    central = CentralModule(db, scheduler=MetaScheduler(db), executor=executor)

    job_id = api.oarsub(db, spec, nb_nodes=1, max_time=3600)
    deadline = time.monotonic() + timeout
    while True:
        central.tick()
        state = api.oarstat(db, job_id)[0]["state"]
        if state in ("Terminated", "Error"):
            break
        check(time.monotonic() < deadline,
              f"job {job_id} still {state} after {timeout:.0f}s")
        time.sleep(0.2)
    runner.wait_all(60)
    result = runner.results.get(job_id)
    if isinstance(result, Exception):
        raise result
    check(state == "Terminated", f"job {job_id} ended {state}: "
          f"{api.oarstat(db, job_id)[0]['message']}")
    return result


def job_facts(result) -> dict:
    losses = [h["loss"] for h in result.history]
    check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    return {"start_step": result.history[0]["step"], "end_step": result.step,
            "losses": losses, **step_times(result.history)}


def reference_loss(cfg, spec: dict) -> float:
    """``M.loss_fn`` in float32 at "highest" precision on the job's step-0
    params and batch (``train_loop`` seeds both with 0)."""
    from repro.data.pipeline import make_batch
    from repro.models import model as M

    ref_cfg = cfg.replace(dtype="float32")
    params = M.init_params(ref_cfg, jax.random.PRNGKey(0))
    batch = make_batch(ref_cfg, spec["global_batch"], spec["seq_len"],
                       seed=0, step=0)
    with jax.default_matmul_precision("highest"):
        loss = jax.jit(M.loss_fn, static_argnums=1)(params, ref_cfg, batch)
    return float(loss)


def phase_train(args, clog: CompileLog) -> None:
    from repro import configs

    ckpt = os.path.join(OUT_DIR, "ckpt")
    first = train_spec(args, ckpt, steps=4)
    clog.take()
    res1 = run_job(first)
    f1 = {**job_facts(res1), **clog.take()}
    check(f1["start_step"] == 0 and f1["end_step"] == 4, f"job 1: {f1}")
    res2 = run_job(train_spec(args, ckpt, steps=6))
    f2 = {**job_facts(res2), **clog.take()}
    check(f2["start_step"] == 4 and f2["end_step"] == 6,
          f"resumed job did not run steps 4..6: {f2}")
    peak = peak_bytes()

    cfg = configs.get_smoke("mamba2-130m") if args.tiny \
        else configs.get("mamba2-130m")
    ref = reference_loss(cfg, first)
    err = abs(f1["losses"][0] - ref) / abs(ref)
    emit({"phase": "train", "arch": cfg.name, "params": cfg.param_count(),
          "global_batch": first["global_batch"], "seq_len": first["seq_len"],
          "job1": f1, "job2_resumed": f2, "peak_bytes": peak,
          "step0_loss_ref_f32": ref, "step0_loss_rel_err": err,
          "rtol": LOSS_RTOL})
    check(err <= LOSS_RTOL, f"step-0 loss {f1['losses'][0]} vs f32 "
          f"reference {ref}: rel err {err:.2e} > {LOSS_RTOL}")


# --------------------------------------------------------------- phase (b)
def phase_serve(args) -> None:
    from repro import configs
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel import sharding as shd
    from repro.serve.engine import ServeEngine

    if args.tiny:
        cfg = configs.get_smoke("granite-8b").replace(dtype="float32")
        plens, max_len = (8, 24), 64
    else:
        cfg = configs.get("granite-8b").replace(num_layers=4)
        plens, max_len = (128, 512), 1024
    mesh = make_mesh(jax.devices()[:1], (1, 1))
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed),
                           M.compute_dtype(cfg))
    engine = ServeEngine(cfg, mesh, shd.make_rules(multi_pod=False), params,
                         max_batch=4, max_len=max_len)
    rng = np.random.default_rng(args.seed)
    prompts = [rng.integers(0, cfg.vocab_size, plens[i % 2]).tolist()
               for i in range(8)]
    budgets = [int(rng.integers(8, 17)) for _ in prompts]
    t0 = time.perf_counter()
    with mesh:
        for prompt, n in zip(prompts, budgets):
            engine.submit(prompt, max_new_tokens=n)
        done = engine.run()
    wall = time.perf_counter() - t0
    complete = [r for r in done
                if r.done and len(r.generated) == r.max_new_tokens]

    # the engine's own compiled prefill for request 0, against the f32 forward
    with mesh:
        logits, _ = engine._prefill_fn(len(prompts[0]))(
            params, {"tokens": jnp.asarray([prompts[0]], jnp.int32)})
    ref_cfg = cfg.replace(dtype="float32")
    params32 = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)

    def last_logits(p, tokens):
        return M.forward(p, ref_cfg, {"tokens": tokens})[0][:, -1]

    with jax.default_matmul_precision("highest"):
        ref = jax.jit(last_logits)(params32,
                                   jnp.asarray([prompts[0]], jnp.int32))
    got, want = np.asarray(logits, np.float32), np.asarray(ref, np.float32)
    rel_l2 = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    emit({"phase": "serve", "arch": cfg.name, "params": cfg.param_count(),
          "reduced": "num_layers 36 -> 4 (published widths kept)"
          if not args.tiny else "smoke config",
          "requests": len(prompts), "completed": len(complete),
          "tokens": sum(len(r.generated) for r in done),
          "decode_steps": engine.steps_run, "wall_s": wall,
          "peak_bytes": peak_bytes(),
          "prefill_logits_rel_l2": rel_l2,
          "prefill_logits_max_abs_err": float(np.max(np.abs(got - want))),
          "tol_rel_l2": LOGIT_REL_L2})
    check(len(complete) == len(prompts),
          f"{len(complete)}/{len(prompts)} requests completed")
    check(rel_l2 <= LOGIT_REL_L2,
          f"prefill logits rel L2 {rel_l2:.2e} > {LOGIT_REL_L2}")


# --------------------------------------------------------------- phase (c)
def phase_kernels(args) -> None:
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru.ops import lru_scan
    from repro.kernels.rglru.ref import lru_scan_ref
    from repro.kernels.ssd.ops import ssd
    from repro.kernels.ssd.ref import ssd_ref

    bf = jnp.bfloat16
    S = 256 if args.tiny else 2048
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 16))

    def normal(shape, dtype=bf):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    # flash attention at granite-8b widths: 32 q heads, 8 kv heads, dim 128
    H, K, D = (4, 2, 128) if args.tiny else (32, 8, 128)
    q, k, v = normal((1, S, H, D)), normal((1, S, K, D)), normal((1, S, K, D))
    fa = (lambda *a: flash_attention(*a, causal=True, use_pallas=True),
          lambda *a: attention_ref(*a, causal=True), (q, k, v))
    # SSD at mamba2-130m widths: 24 heads of dim 64, state 128, chunk 256
    H, P, N = (4, 64, 128) if args.tiny else (24, 64, 128)
    dt = jax.nn.softplus(normal((2, S, H), jnp.float32))
    A = -jnp.exp(normal((H,), jnp.float32))
    sd = (lambda *a: ssd(*a, chunk=256, use_pallas=True),
          lambda *a: ssd_ref(*a, chunk=256),
          (normal((2, S, H, P)), dt, A, normal((2, S, N)), normal((2, S, N))))
    # RG-LRU at recurrentgemma-2b's lru_width 2560
    W = 256 if args.tiny else 2560
    lr = (lambda a, b: lru_scan(a, b, use_pallas=True), lru_scan_ref,
          (jax.nn.sigmoid(normal((2, S, W), jnp.float32)).astype(bf),
           normal((2, S, W))))

    facts = {}
    for name, (kern, ref_fn, inputs) in (("flash_attention", fa),
                                         ("ssd", sd), ("rglru", lr)):
        compiled = jax.jit(kern).lower(*inputs).compile()
        out = np.asarray(compiled(*inputs), np.float32)
        up = [x.astype(jnp.float32) for x in inputs]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ref_fn)(*up), np.float32)
        err = float(np.max(np.abs(out - want)) / np.max(np.abs(want)))
        facts[name] = {"shape": list(inputs[0].shape), "rel_max_err": err,
                       "mosaic": "tpu_custom_call" in compiled.as_text()}
        check(np.all(np.isfinite(out)), f"{name}: non-finite output")
        check(err <= KERNEL_REL,
              f"{name}: rel max err {err:.2e} > {KERNEL_REL}")
        check(args.tiny or facts[name]["mosaic"],
              f"{name}: no Mosaic kernel in the compiled program")
    emit({"phase": "kernels", **facts, "tol_rel_max": KERNEL_REL})


# ------------------------------------------------------------- --chips 4
def phase_data_parallel(args, clog: CompileLog) -> None:
    from repro import configs
    from repro.data.pipeline import make_batch
    from repro.launch.mesh import make_mesh
    from repro.models import model as M
    from repro.parallel import sharding as shd
    from repro.parallel.steps import batch_shardings, train_state_shardings
    from repro.train.loop import train_loop

    check(len(jax.devices()) == 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(jax.devices())}")
    spec = train_spec(args, None, steps=4)
    clog.take()
    res4 = run_job(spec)
    f4 = {**job_facts(res4), **clog.take()}

    cfg = configs.get_smoke(spec["arch"]) if args.tiny \
        else configs.get(spec["arch"])
    if jax.default_backend() == "cpu":
        cfg = cfg.replace(dtype="float32")
    rules = shd.make_rules(multi_pod=False)
    one = make_mesh(jax.devices()[:1], (1, 1))
    res1 = train_loop(cfg, one, rules, steps=spec["steps"],
                      global_batch=spec["global_batch"],
                      seq_len=spec["seq_len"], log_every=1)
    f1 = {**job_facts(res1), **clog.take()}
    pairs = list(zip(f4["losses"], f1["losses"]))
    worst = max(abs(a - b) / abs(b) for a, b in pairs)

    # where the step's in_shardings put one parameter and one batch
    mesh4 = make_mesh(jax.devices(), (-1, 1))
    embed = jax.device_put(
        M.init_params(cfg, jax.random.PRNGKey(0))["embed"],
        train_state_shardings(cfg, mesh4, rules)["params"]["embed"])
    tokens = jax.device_put(
        make_batch(cfg, spec["global_batch"], spec["seq_len"], seed=0,
                   step=0)["tokens"],
        batch_shardings(cfg, mesh4, rules)["tokens"])

    def per_device(x):
        return {str(s.device.id): s.data.nbytes for s in x.addressable_shards}

    param_bytes, batch_bytes = per_device(embed), per_device(tokens)
    emit({"phase": "data_parallel", "arch": cfg.name, "mesh": {"data": 4,
          "model": 1}, "losses_4dev_vs_1dev": pairs, "max_rel_diff": worst,
          "rtol": DP_RTOL, "job_4dev": f4, "run_1dev": f1,
          "param_embed_bytes_per_device": param_bytes,
          "batch_tokens_bytes_per_device": batch_bytes,
          "peak_bytes_dev0": peak_bytes()})
    check(res4.step == res1.step == spec["steps"], "step counts differ")
    check(worst <= DP_RTOL, f"4-device vs 1-device loss rel diff {worst:.2e}")
    check(len(batch_bytes) == 4 and len(set(batch_bytes.values())) == 1
          and sum(batch_bytes.values()) == tokens.nbytes,
          f"batch not split evenly over 4 devices: {batch_bytes}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at smoke sizes; never on the chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.tiny:
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        raise SystemExit(2)

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    emit({"phase": "setup", "jax": jax.__version__, "platform": dev.platform,
          "device_kind": dev.device_kind, "devices": len(jax.devices()),
          "compile_cache": cache_dir})
    clog = CompileLog()
    t0 = time.perf_counter()
    os.makedirs(OUT_DIR, exist_ok=True)
    try:
        if args.chips == 4:
            phase_data_parallel(args, clog)
        else:
            phase_train(args, clog)
            phase_serve(args)
            phase_kernels(args)
    finally:
        shutil.rmtree(OUT_DIR, ignore_errors=True)
    emit({"phase": "done", "wall_s": time.perf_counter() - t0})
    emit({"ok": True, "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}})


if __name__ == "__main__":
    main()
