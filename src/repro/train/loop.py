"""The training loop: restore → step → checkpoint, with preemption and
fault hooks.

The loop is deliberately OAR-aware without importing OAR: ``preempt_check``
is any callable; the cluster runner wires it to the job's ``toCancel`` flag
in the DB, so a best-effort training job checkpoints and yields within one
step of the scheduler requesting its resources (§3.3 of the paper, upgraded
from kill-and-restart to checkpoint-and-resume)."""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import jax

from repro.data.pipeline import data_iterator
from repro.parallel.steps import (init_train_state, make_train_step,
                                  abstract_train_state, train_state_shardings)
from repro.train import checkpoint as ckpt
from repro.train.optimizer import OptConfig

__all__ = ["TrainResult", "train_loop"]


@dataclass
class TrainResult:
    status: str                 # done | preempted
    step: int
    metrics: dict = field(default_factory=dict)
    history: list = field(default_factory=list)


def train_loop(cfg, mesh, rules, *, steps: int, global_batch: int,
               seq_len: int, ckpt_dir: str | None = None,
               ckpt_every: int = 100, keep: int = 3, seed: int = 0,
               opt: OptConfig | None = None, microbatches: int = 1,
               log_every: int = 10,
               preempt_check: Callable[[], bool] | None = None,
               on_metrics: Callable[[int, dict], None] | None = None
               ) -> TrainResult:
    train_step = make_train_step(cfg, mesh, rules, opt=opt,
                                 microbatches=microbatches)
    state, start = None, 0
    if ckpt_dir:
        state, restored = ckpt.restore_latest(
            ckpt_dir, abstract_train_state(cfg))
        if restored is not None:
            start = restored
    if state is None:
        state = init_train_state(cfg, jax.random.PRNGKey(seed))
    # the step's outputs carry its shardings; a fresh or restored state
    # placed the same way keeps every call on one compiled program
    state = jax.device_put(state, train_state_shardings(cfg, mesh, rules))

    it = data_iterator(cfg, global_batch, seq_len, seed=seed, start_step=start)
    history, metrics = [], {}
    t0 = time.perf_counter()
    try:
        for step in range(start, steps):
            if preempt_check is not None and preempt_check():
                if ckpt_dir:
                    ckpt.save(ckpt_dir, state, step, keep=keep)
                return TrainResult("preempted", step, metrics, history)
            batch = next(it)
            if microbatches > 1:
                batch = {k: v.reshape(microbatches, v.shape[0] // microbatches,
                                      *v.shape[1:]) for k, v in batch.items()}
            state, metrics = train_step(state, batch)
            if step % log_every == 0 or step == steps - 1 or step == start:
                m = {k: float(v) for k, v in metrics.items()}
                m["step"] = step
                m["sec_per_step"] = (time.perf_counter() - t0) / max(1, step - start + 1)
                history.append(m)
                if on_metrics:
                    on_metrics(step, m)
            if ckpt_dir and ckpt_every and (step + 1) % ckpt_every == 0:
                ckpt.save(ckpt_dir, state, step + 1, keep=keep)
        if ckpt_dir:
            ckpt.save(ckpt_dir, state, steps, keep=keep)
        return TrainResult("done", steps,
                           {k: float(v) for k, v in metrics.items()}, history)
    finally:
        if hasattr(it, "close"):
            it.close()
