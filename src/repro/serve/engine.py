"""Serving engine: continuous batching over a persistent sharded KV cache.

The engine owns ``max_batch`` decode slots. Requests queue (FIFO — the
OAR 'interactive' queue discipline); a free slot triggers a prefill whose
per-layer cache rows are spliced into the batched cache; every ``step()``
advances all active slots by one token (per-row positions — rows are at
different depths, which is the whole point of continuous batching).
Finished slots free immediately and the next request is admitted, so
utilisation stays high under mixed-length workloads — the serving analogue
of the paper's backfilling argument.

Every request carries four host-clock stamps (submitted, admitted, first
token, done), and ``counters`` holds the work done so far. A caller that
hands in a ``repro.spans.Spans`` also gets the spans of each step:
``serve.step`` around one ``step()``; inside it ``serve.prefill`` per
admission (batch-1 prefill, cache splice, first-token fetch),
``serve.decode`` (the token and position arrays, their copy to the device,
the dispatch of the decode step) and ``serve.sync`` around each host wait
on a device result; and ``serve.queue`` for each request's wait from
``submit()`` to its admission.
"""

from __future__ import annotations

import contextlib
import itertools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.parallel.steps import make_prefill_step, make_serve_step

__all__ = ["Counters", "Request", "ServeEngine"]

_OFF = contextlib.nullcontext()


@dataclass
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: int | None = None
    generated: list[int] = field(default_factory=list)
    done: bool = False
    # time.perf_counter() at submit(), admission, first token and finish
    t_submit: float | None = None
    t_admit: float | None = None
    t_first: float | None = None
    t_done: float | None = None


@dataclass
class Counters:
    """Work the engine has done since it was made."""
    steps: int = 0              # decode steps
    prefills: int = 0           # admissions, one batch-1 prefill each
    prompt_tokens: int = 0      # tokens those prefills read
    decoded_tokens: int = 0     # tokens the decode steps produced
    slot_steps_active: int = 0  # rows active, summed over decode steps
    # live cache positions of the active rows, min(pos + 1, max_len) each,
    # summed over decode steps: what their attention has to read
    live_positions: int = 0


@dataclass
class _Slot:
    active: bool = False
    rid: int | None = None
    pos: int = 0                 # absolute position of the NEXT token to write
    budget: int = 0


class ServeEngine:
    def __init__(self, cfg, mesh, rules, params, *, max_batch: int = 4,
                 max_len: int = 256, greedy: bool = True, spans=None):
        self.cfg, self.mesh, self.rules = cfg, mesh, rules
        self.params = params
        self.max_batch, self.max_len = max_batch, max_len
        self.decode = make_serve_step(cfg, mesh, rules,
                                      global_batch=max_batch, max_len=max_len)
        self._prefill_cache = {}
        self.cache = M.init_cache(cfg, max_batch, max_len)
        self.slots = [_Slot() for _ in range(max_batch)]
        self.queue: list[Request] = []
        self.requests: dict[int, Request] = {}
        self._ids = itertools.count()
        self.counters = Counters()
        self.spans = spans

    @property
    def steps_run(self) -> int:
        """Decode steps run so far."""
        return self.counters.steps

    def _span(self, name: str, attr: int | None = None):
        return _OFF if self.spans is None else self.spans(name, attr)

    # ------------------------------------------------------------- requests
    def submit(self, prompt: list[int], *, max_new_tokens: int = 16,
               eos_id: int | None = None) -> int:
        rid = next(self._ids)
        req = Request(rid, list(prompt), max_new_tokens, eos_id,
                      t_submit=time.perf_counter())
        self.requests[rid] = req
        self.queue.append(req)
        return rid

    # -------------------------------------------------------------- interns
    def _prefill_fn(self, plen: int):
        if plen not in self._prefill_cache:
            self._prefill_cache[plen] = make_prefill_step(
                self.cfg, self.mesh, self.rules, global_batch=1,
                seq_len=plen, max_len=self.max_len)
        return self._prefill_cache[plen]

    def _splice(self, row_cache, b: int):
        """Insert a batch-1 prefill cache into batched cache row ``b``."""
        def one(path, full, row):
            # layer-stacked leaves are (L, B, ...); unstacked are (B, ...)
            if M.stacked(path):
                return full.at[:, b].set(row[:, 0])
            return full.at[b].set(row[0])

        self.cache = jax.tree_util.tree_map_with_path(one, self.cache,
                                                      row_cache)

    def _admit(self):
        for slot_id, slot in enumerate(self.slots):
            if slot.active or not self.queue:
                continue
            req = self.queue.pop(0)
            req.t_admit = time.perf_counter()
            if self.spans is not None:
                self.spans.add("serve.queue", req.t_submit, req.t_admit,
                               req.rid)
            plen = len(req.prompt)
            with self._span("serve.prefill", req.rid):
                prefill = self._prefill_fn(plen)
                batch = {"tokens": jnp.asarray([req.prompt], jnp.int32)}
                if self.cfg.family == "vlm":
                    batch["vision_embeds"] = jnp.zeros(
                        (1, self.cfg.frontend_tokens, self.cfg.d_model),
                        M.compute_dtype(self.cfg))
                if self.cfg.family == "audio":
                    batch["audio_embeds"] = jnp.zeros(
                        (1, self.cfg.frontend_tokens, self.cfg.d_model),
                        M.compute_dtype(self.cfg))
                logits, row_cache = prefill(self.params, batch)
                self._splice(row_cache, slot_id)
                with self._span("serve.sync"):
                    first = int(jnp.argmax(logits[0]))
            req.t_first = time.perf_counter()
            self.counters.prefills += 1
            self.counters.prompt_tokens += plen
            req.generated.append(first)
            F = self.cfg.frontend_tokens if self.cfg.family == "vlm" else 0
            slot.active, slot.rid = True, req.rid
            slot.pos = F + plen             # next write position
            slot.budget = req.max_new_tokens - 1
            if slot.budget <= 0 or first == req.eos_id:
                req.done, slot.active = True, False
                req.t_done = req.t_first

    # ----------------------------------------------------------------- step
    def step(self) -> bool:
        """Admit + one decode step. Returns True while work remains."""
        with self._span("serve.step"):
            self._admit()
            active = [s for s in self.slots if s.active]
            if not active:
                return bool(self.queue)
            with self._span("serve.decode"):
                tokens = np.zeros((self.max_batch, 1), np.int32)
                pos = np.zeros((self.max_batch,), np.int32)
                for i, slot in enumerate(self.slots):
                    if slot.active:
                        tokens[i, 0] = self.requests[slot.rid].generated[-1]
                        pos[i] = slot.pos
                logits, self.cache = self.decode(
                    self.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(pos))
            with self._span("serve.sync"):
                nxt = np.asarray(jnp.argmax(logits, axis=-1))
            now = time.perf_counter()
            c = self.counters
            c.steps += 1
            c.slot_steps_active += len(active)
            c.live_positions += sum(min(s.pos + 1, self.max_len)
                                    for s in active)
            c.decoded_tokens += len(active)
            for i, slot in enumerate(self.slots):
                if not slot.active:
                    continue
                req = self.requests[slot.rid]
                tok = int(nxt[i])
                req.generated.append(tok)
                slot.pos += 1
                slot.budget -= 1
                if slot.budget <= 0 or tok == req.eos_id or \
                        slot.pos >= self.max_len - 1:
                    req.done, slot.active = True, False
                    req.t_done = now
        return True

    def run(self, max_steps: int = 10_000) -> list[Request]:
        for _ in range(max_steps):
            if not self.step() and not any(s.active for s in self.slots):
                break
        return [self.requests[r] for r in sorted(self.requests)]
