"""Cluster runner: the bridge between the OAR control plane and the JAX
data plane.

A job's ``command`` column carries a JSON spec::

    {"kind": "train", "arch": "tiny", "steps": 200, "global_batch": 8,
     "seq_len": 128, "ckpt_dir": "/tmp/job7"}

The :class:`ClusterRunner` is plugged into ``Executor(runner=...)``: when
the launcher moves a job to Running it hands the spec to a worker thread
which runs the real training loop. The loop's ``preempt_check`` polls the
job's ``toCancel`` flag — the scheduler's §3.3 best-effort preemption
checkpoint-and-yields the data plane within one step. Completion calls back
into the Executor, which frees resources through the DB like any other job.
"""

from __future__ import annotations

import threading

import jax

from repro import configs
from repro.launch.cache import configure_compile_cache
from repro.launch.mesh import make_mesh
from repro.parallel import sharding as shd
from repro.train.loop import train_loop

__all__ = ["ClusterRunner"]


class ClusterRunner:
    """Runs 'train' job specs on the local devices, one thread per job."""

    def __init__(self, db, executor, *, default_rules=None):
        configure_compile_cache()
        self.db = db
        self.executor = executor
        self.rules = default_rules or shd.make_rules(multi_pod=False)
        self.threads: dict[int, threading.Thread] = {}
        self.results: dict[int, object] = {}

    # Executor runner entry point: (spec, hosts) -> start async work
    def __call__(self, spec: dict, hosts: list[str]) -> None:
        if spec.get("kind") != "train":
            return                       # sim payloads etc. are no-ops here
        t = threading.Thread(target=self._run, args=(spec,), daemon=True)
        self.threads[spec["idJob"]] = t
        t.start()

    def _preempt_check(self, job_id: int):
        def check() -> bool:
            row = self.db.query_one(
                "SELECT toCancel, state FROM jobs WHERE idJob=?", (job_id,))
            return row is None or row["toCancel"] == 1 or \
                row["state"] not in ("Running", "Launching")
        return check

    def _run(self, spec: dict) -> None:
        job_id = spec["idJob"]
        try:
            cfg = configs.get_smoke(spec.get("arch", "tiny")) \
                if spec.get("smoke", True) else configs.get(spec["arch"])
            if jax.default_backend() == "cpu":
                cfg = cfg.replace(dtype="float32")
            mesh = make_mesh(jax.devices(), (-1, 1))
            result = train_loop(
                cfg, mesh, self.rules,
                steps=spec.get("steps", 100),
                global_batch=spec.get("global_batch", 8),
                seq_len=spec.get("seq_len", 128),
                ckpt_dir=spec.get("ckpt_dir"),
                ckpt_every=spec.get("ckpt_every", 50),
                preempt_check=self._preempt_check(job_id),
                log_every=spec.get("log_every", 20),
            )
            self.results[job_id] = result
            if result.status == "done":
                self.executor.complete(job_id, ok=True,
                                       message=f"trained to step {result.step}")
            # preempted: the cancellation module owns the state transition;
            # the checkpoint makes the resubmitted clone resume.
        except Exception as exc:  # noqa: BLE001 — job failure, not ours
            self.results[job_id] = exc
            self.executor.complete(job_id, ok=False, message=repr(exc))

    def wait_all(self, timeout: float = 300.0) -> None:
        for t in list(self.threads.values()):
            t.join(timeout)
