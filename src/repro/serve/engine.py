"""Serving engine: continuous batching over a persistent sharded KV cache.

The engine owns ``max_batch`` decode slots. Requests queue (FIFO — the
OAR 'interactive' queue discipline); a free slot triggers a prefill whose
per-layer cache rows are spliced into the batched cache; every ``step()``
advances all active slots by one token (per-row positions — rows are at
different depths, which is the whole point of continuous batching).
Finished slots free immediately and the next request is admitted, so
utilisation stays high under mixed-length workloads — the serving analogue
of the paper's backfilling argument.

One decode is kept in flight. The decode step takes the argmax itself, and
its next tokens and positions stay on the device as the next decode's
inputs; the host writes a row there only when it admits a request into it
(or idles a freed row). Each ``step()``:

1. admits into the slots the host knows are free: the batch-1 prefill,
   the cache splice (once the cache it replaces is written), and the
   patch of the row's first token (still on the device) and start
   position;
2. dispatches decode n+1 if any row is active, and starts the copy of its
   tokens to the host;
3. reads, in one host wait, decode n's tokens and the new rows' first
   tokens;
4. hands them to their requests and frees the finished slots.

Decode n+1 was dispatched before decode n's tokens were read, so it also
computes a token for each row that finished at n; each in-flight decode
records which request owned each row, and such a token is dropped. Once
no row is active, the decode in flight holds nothing but such tokens and
is dropped unread, so ``run()`` ends with no decode in flight. A slot
freed at one ``step()`` is admitted at the next.

Every request carries four host-clock stamps (submitted, admitted, first
token, done), and ``counters`` holds the work done so far. A caller that
hands in a ``repro.spans.Spans`` also gets the spans of each step:
``serve.step`` around one ``step()``; inside it ``serve.prefill`` per
admission (batch-1 prefill and cache splice, with a ``serve.sync``
inside it that waits for the cache the splice replaces), ``serve.decode``
around
each write into the decode's device inputs (one per admission, with the
rows it idles before a dispatch) and around the dispatch of a decode,
``serve.sync`` around the step's one host wait (decode n's tokens and the
new rows' first tokens); and ``serve.queue`` for each request's wait from
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
from repro.parallel.steps import (make_prefill_step, make_row_patch,
                                  make_serve_step, row_shardings)

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
    """Work the engine has done since it was made. The decode counts take
    only the tokens handed to requests."""
    steps: int = 0              # decodes that handed a request a token
    prefills: int = 0           # admissions, one batch-1 prefill each
    prompt_tokens: int = 0      # tokens those prefills read
    decoded_tokens: int = 0     # tokens the decode steps handed to requests
    slot_steps_active: int = 0  # rows handed a token, summed over decodes
    # live cache positions of those rows, min(pos + 1, max_len) each,
    # summed over decode steps: what their attention has to read
    live_positions: int = 0
    # decodes dispatched while the previous decode's tokens were unread
    overlapped_steps: int = 0
    # tokens an in-flight decode computed for rows already finished
    discarded_tokens: int = 0


@dataclass
class _Slot:
    active: bool = False
    # the request the device's row decodes for: kept after it finishes until
    # the row is patched, None once the row idles at position 0
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
        self._patch = make_row_patch(mesh, rules, global_batch=max_batch)
        self._prefill_cache = {}
        self.cache = M.init_cache(cfg, max_batch, max_len)
        # the next decode's inputs, on the device, placed as the steps
        # return them so that no call meets a new input type after warm-up
        tok_sh, pos_sh = row_shardings(mesh, rules, max_batch)
        self._tokens = jax.device_put(np.zeros((max_batch, 1), np.int32),
                                      tok_sh)
        self._pos = jax.device_put(np.zeros((max_batch,), np.int32), pos_sh)
        self._idle_token = jax.device_put(
            np.zeros((1, 1), np.int32), row_shardings(mesh, rules, 1)[0])
        # the decode not read yet: its tokens and each row's request
        self._inflight: tuple[jax.Array, list[int | None]] | None = None
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

    def _set_row(self, b: int, token, pos: int):
        self._tokens, self._pos = self._patch(self._tokens, self._pos, b,
                                              token, pos)

    def _admit(self) -> list[tuple[Request, _Slot, jax.Array]]:
        """Prefill the queue's head into each free slot; returns each new
        row's request, slot and first token, still on the device."""
        new = []
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
                first, row_cache = prefill(self.params, batch)
                first.copy_to_host_async()
                # each splice writes a new whole cache, allocated when it is
                # queued: wait until the cache it replaces is written (the
                # last decode's or splice's), so that at most two are live
                with self._span("serve.sync"):
                    jax.block_until_ready(self.cache)
                self._splice(row_cache, slot_id)
            self.counters.prefills += 1
            self.counters.prompt_tokens += plen
            F = self.cfg.frontend_tokens if self.cfg.family == "vlm" else 0
            slot.active, slot.rid = True, req.rid
            slot.pos = F + plen             # next write position
            slot.budget = req.max_new_tokens - 1
            with self._span("serve.decode"):
                self._set_row(slot_id, first, slot.pos)
            new.append((req, slot, first))
        return new

    def _dispatch(self) -> None:
        """Idle the rows of finished requests, then put the next decode on
        the device."""
        for b, slot in enumerate(self.slots):
            if not slot.active and slot.rid is not None:
                self._set_row(b, self._idle_token, 0)
                slot.rid = None
        tokens, self._pos, self.cache = self.decode(
            self.params, self.cache, self._tokens, self._pos)
        tokens.copy_to_host_async()
        self._tokens = tokens
        self._inflight = (tokens, [s.rid if s.active else None
                                   for s in self.slots])

    @staticmethod
    def _finish(req: Request, slot: _Slot, now: float) -> None:
        req.done, req.t_done, slot.active = True, now, False

    def _hand_decoded(self, toks: np.ndarray, rows: list[int | None],
                      now: float) -> None:
        """Append a read decode's tokens to the requests that owned its rows
        at dispatch, and drop those of requests that finished since."""
        c, handed = self.counters, 0
        for b, rid in enumerate(rows):
            if rid is None:
                continue
            req = self.requests[rid]
            if req.done:
                c.discarded_tokens += 1
                continue
            slot, tok = self.slots[b], int(toks[b, 0])
            c.live_positions += min(slot.pos + 1, self.max_len)
            req.generated.append(tok)
            handed += 1
            slot.pos += 1
            slot.budget -= 1
            if slot.budget <= 0 or tok == req.eos_id or \
                    slot.pos >= self.max_len - 1:
                self._finish(req, slot, now)
        if handed:
            c.steps += 1
            c.slot_steps_active += handed
            c.decoded_tokens += handed

    # ----------------------------------------------------------------- step
    def step(self) -> bool:
        """Admit, dispatch the next decode, then read the one before it.
        Returns True while requests are queued or active."""
        with self._span("serve.step"):
            new = self._admit()
            last, self._inflight = self._inflight, None
            if any(s.active for s in self.slots):
                self.counters.overlapped_steps += last is not None
                with self._span("serve.decode"):
                    self._dispatch()
            if last is not None or new:
                self._read(last, new)
            if self._inflight and not any(s.active for s in self.slots):
                # every row of the decode in flight is a finished request's
                self.counters.discarded_tokens += sum(
                    rid is not None for rid in self._inflight[1])
                self._inflight = None
        return bool(self.queue) or any(s.active for s in self.slots)

    def _read(self, last, new) -> None:
        """The step's one host wait: the last decode's tokens and the new
        rows' first tokens, handed to their requests."""
        with self._span("serve.sync"):
            got = jax.device_get([None if last is None else last[0]]
                                 + [first for _, _, first in new])
        now = time.perf_counter()
        for (req, slot, _), first in zip(new, got[1:]):
            tok = int(first[0, 0])
            req.generated.append(tok)
            req.t_first = now
            if slot.budget <= 0 or tok == req.eos_id:
                self._finish(req, slot, now)
        if last is not None:
            self._hand_decoded(got[0], last[1], now)

    def run(self, max_steps: int = 10_000) -> list[Request]:
        """Step until nothing is queued or active, and so none in flight."""
        for _ in range(max_steps):
            if not self.step():
                break
        return [self.requests[r] for r in sorted(self.requests)]
